"""``from_jax`` — trace a JAX function into :class:`~repro.ir.graph_ir.GraphIR`.

This is the "bring your own workload" importer for code instead of JSON:
give it any JAX-traceable CNN forward function and example inputs, and it
walks the jaxpr mapping compute primitives onto :class:`repro.core.graph.
Layer` kinds:

    ==========================  =====================================
    jaxpr primitive             Layer kind
    ==========================  =====================================
    conv_general_dilated        conv (dwconv when feature_group_count
                                == input channels)
    dot_general                 fc
    reduce_window_max/sum/min   pool (global_pool when the window
                                covers the whole spatial extent)
    reduce_sum/max over H,W     global_pool
    add/sub/max/min (2 tensors) add
    mul/div      (2 tensors)    mul
    concatenate                 concat
    ==========================  =====================================

Everything elementwise or shape-plumbing (relu via ``max(x, 0)``, bias
adds, activations, reshape/transpose/broadcast, dtype casts) is *folded*
into its producer — those ops move no DRAM traffic the fusion cost model
accounts separately.  ``jit`` / ``custom_jvp_call`` bodies are walked
recursively, so ``jax.jit``- or ``jax.nn``-wrapped models trace the same
as raw ``lax`` code.

The walker is intentionally a CNN-shaped subset: batch size must be 1
(the paper's edge-inference setting) and an unsupported primitive raises
:class:`TraceError` naming it, rather than guessing.  The resulting IR is
run through the full canonicalization pipeline (``repro.ir.passes``), so
dead branches and identity glue never reach a search.

Example::

    import jax.numpy as jnp
    from jax import lax

    def cnn(x, w1, w2):
        y = lax.conv_general_dilated(x, w1, (1, 1), "SAME")
        y = jnp.maximum(y, 0.0)
        y = lax.reduce_window(y, -jnp.inf, lax.max,
                              (1, 1, 2, 2), (1, 1, 2, 2), "VALID")
        return lax.conv_general_dilated(y, w2, (1, 1), "SAME")

    ir = from_jax(cnn, (jnp.zeros((1, 3, 32, 32)),
                        jnp.zeros((8, 3, 3, 3)),
                        jnp.zeros((16, 8, 3, 3))), name="tiny")
    graph = ir.build()            # ready for repro.search
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Any, Callable, Dict, List, Optional, Tuple

from repro.ir.graph_ir import GraphIR
from repro.ir.passes import canonicalize


class TraceError(ValueError):
    """The traced function uses a primitive/shape outside the supported
    CNN subset; the message names it."""


#: primitives folded into their producer (elementwise / shape plumbing)
_ALIAS_PRIMS = frozenset({
    "abs", "broadcast_in_dim", "ceil", "clamp", "convert_element_type",
    "copy", "cos", "cosh", "device_put", "erf", "exp", "expand_dims",
    "floor", "integer_pow", "log", "log1p", "logistic", "neg", "pow",
    "reshape", "round", "rsqrt", "select_n", "sign", "sin", "sinh", "sqrt",
    "squeeze", "stop_gradient", "tan", "tanh", "transpose",
})

_ADD_PRIMS = frozenset({"add", "add_any", "sub", "max", "min"})
_MUL_PRIMS = frozenset({"mul", "div"})
_WINDOW_PRIMS = frozenset({"reduce_window_max", "reduce_window_sum",
                           "reduce_window_min"})
_REDUCE_PRIMS = frozenset({"reduce_sum", "reduce_max", "reduce_min"})
#: call-like primitives whose inner jaxpr is walked in place (jax 0.9 names)
_CALL_PRIMS = frozenset({"jit", "closed_call", "custom_jvp_call",
                         "custom_vjp_call", "remat2"})


@dataclass
class _Val:
    """What the walker knows about one jaxpr value."""
    node: Optional[str]          # producing IR node name; None = parameter
    chw: Tuple[int, int, int]    # logical activation shape (C, H, W)
    shape: Tuple[int, ...]       # raw array shape
    #: rank-4 dim order ("NCHW"/"NHWC"), learned from conv dimension
    #: numbers and propagated — pooling/reduction/concat dims depend on it
    layout: Optional[str] = None


def _is_literal(v: Any) -> bool:
    return not hasattr(v, "count")       # jax Var has .count, Literal doesn't


class _Walker:
    def __init__(self, name: str) -> None:
        self.name = name
        self.nodes: List[Dict[str, Any]] = []
        self._uid = 0
        self.env: Dict[Any, _Val] = {}

    # ---- node emission ---------------------------------------------------------
    def _emit(self, base: str, kind: str, inputs: List[str],
              **geom: Any) -> str:
        self._uid += 1
        name = f"{base}_{self._uid}"
        node: Dict[str, Any] = {"name": name, "kind": kind,
                                "inputs": inputs, **geom}
        self.nodes.append(node)
        return name

    def _chw_of_shape(self, shape: Tuple[int, ...]) -> Tuple[int, int, int]:
        if len(shape) == 4:
            if shape[0] != 1:
                raise TraceError(
                    f"activations must have batch size 1 (the paper's edge "
                    f"setting), got shape {shape}")
            return (shape[1], shape[2], shape[3])     # assume NCHW
        if len(shape) == 3:
            return (shape[0], shape[1], shape[2])
        if len(shape) == 2:
            if shape[0] != 1:
                raise TraceError(
                    f"2-d activations must be (1, features), got {shape}")
            return (shape[1], 1, 1)
        if len(shape) == 1:
            return (shape[0], 1, 1)
        raise TraceError(f"unsupported activation rank {len(shape)} "
                         f"(shape {shape})")

    def _as_data(self, val: _Val,
                 chw: Optional[Tuple[int, int, int]] = None) -> _Val:
        """Promote a parameter value to a traced activation: the model
        input becomes an ``input`` node on first data use."""
        if val.node is not None:
            return val
        c, h, w = chw if chw is not None else self._chw_of_shape(val.shape)
        node = self._emit("input", "input", [], m=c, p=h, q=w)
        val.node, val.chw = node, (c, h, w)
        return val

    # ---- value lookup ----------------------------------------------------------
    def _val(self, v: Any) -> _Val:
        if _is_literal(v):
            shape = tuple(getattr(getattr(v, "aval", None), "shape", ()))
            return _Val(None, (0, 0, 0), shape)
        if v not in self.env:
            shape = tuple(v.aval.shape)
            self.env[v] = _Val(None, (0, 0, 0), shape)
        return self.env[v]

    def _bind(self, outvar: Any, val: _Val) -> None:
        if not _is_literal(outvar):       # dropvars are fine to bind too
            self.env[outvar] = val

    # ---- primitive handlers ----------------------------------------------------
    def walk(self, jaxpr: Any) -> None:
        for eqn in jaxpr.eqns:
            self._eqn(eqn)

    def _eqn(self, eqn: Any) -> None:
        prim = eqn.primitive.name
        if prim == "conv_general_dilated":
            return self._conv(eqn)
        if prim == "dot_general":
            return self._dot(eqn)
        if prim in _WINDOW_PRIMS:
            return self._reduce_window(eqn)
        if prim in _REDUCE_PRIMS:
            return self._reduce(eqn)
        if prim in _ADD_PRIMS or prim in _MUL_PRIMS:
            return self._binary(eqn, "add" if prim in _ADD_PRIMS else "mul")
        if prim == "concatenate":
            return self._concat(eqn)
        if prim in _CALL_PRIMS:
            return self._call(eqn)
        if prim in _ALIAS_PRIMS:
            return self._alias(eqn)
        raise TraceError(
            f"unsupported primitive {prim!r} in traced function; the "
            f"importer understands convolutions (conv_general_dilated), "
            f"matmuls (dot_general), pooling (reduce_window_*, reduce_sum "
            f"over H,W), elementwise add/mul, and concatenate — write this "
            f"op in those terms or author the workload as GraphIR JSON")

    def _conv(self, eqn: Any) -> None:
        p = eqn.params
        dn = p["dimension_numbers"]
        lb, lf, *lspat = dn.lhs_spec
        rof, rif, *rspat = dn.rhs_spec
        ob, of, *ospat = dn.out_spec
        if len(lspat) != 2:
            raise TraceError(
                f"only 2-d convolutions are supported, got "
                f"{len(lspat)} spatial dims")
        lhs, rhs = eqn.invars[:2]
        lshape = tuple(lhs.aval.shape)
        if lshape[lb] != 1:
            raise TraceError(f"conv batch size must be 1, got {lshape[lb]}")
        c, h, w = lshape[lf], lshape[lspat[0]], lshape[lspat[1]]
        lval = self._as_data(self._val(lhs), (c, h, w))
        assert lval.node is not None      # _as_data promoted it
        lval.layout = "NHWC" if lf == 3 else "NCHW" if lf == 1 else None
        rshape = tuple(rhs.aval.shape)
        oshape = tuple(eqn.outvars[0].aval.shape)
        m = oshape[of]
        pq = (oshape[ospat[0]], oshape[ospat[1]])
        r, s = rshape[rspat[0]], rshape[rspat[1]]
        groups = int(p.get("feature_group_count", 1))
        # Layer.padding is symmetric; 'SAME' on even inputs lowers to
        # (lo, hi)=(0, 1) — max() keeps the halo the receptive-field
        # backtrace needs (the zoo writes the same geometry as pad=k//2)
        pad = tuple(max(int(lo), int(hi)) for lo, hi in p["padding"])
        kind, base = ("dwconv", "dw") if groups == c and groups > 1 \
            else ("conv", "conv")
        node = self._emit(
            base, kind, [lval.node], c=c, h=h, w=w, m=m, p=pq[0], q=pq[1],
            r=r, s=s, stride=list(map(int, p["window_strides"])),
            padding=list(pad),
            dilation=list(map(int, p["rhs_dilation"])), groups=groups)
        layout = "NHWC" if of == 3 else "NCHW" if of == 1 else None
        self._bind(eqn.outvars[0],
                   _Val(node, (m, pq[0], pq[1]), oshape, layout))

    def _dot(self, eqn: Any) -> None:
        (lc, rc), (lbat, rbat) = eqn.params["dimension_numbers"]
        lhs, rhs = eqn.invars[:2]
        lval, rval = self._val(lhs), self._val(rhs)
        if lval.node is not None and rval.node is not None:
            # both operands are traced activations: this is an attention/
            # bilinear product, not a weighted fc layer — an fc node would
            # keep only one branch and dead-eliminate the other silently
            raise TraceError(
                "dot_general of two traced activations (activation x "
                "activation, e.g. attention) is not an fc layer this IR "
                "models; only activation x parameter matmuls trace")
        # the operand with a traced producer is the data; weights stay
        # parameters.  With neither traced yet, lhs is the data (x @ W).
        if lval.node is None and rval.node is not None:
            data, dcontract = rval, rc
        else:
            data, dcontract = lval, lc
        data = self._as_data(data)
        assert data.node is not None      # _as_data promoted it
        cdim = math.prod(data.shape[d] for d in dcontract)
        oshape = tuple(eqn.outvars[0].aval.shape)
        m = math.prod(s for i, s in enumerate(oshape)
                      if i not in range(len(lbat))) if oshape else 1
        node = self._emit("fc", "fc", [data.node], c=cdim, h=1, w=1,
                          m=m, p=1, q=1)
        self._bind(eqn.outvars[0], _Val(node, (m, 1, 1), oshape))

    def _reduce_window(self, eqn: Any) -> None:
        p = eqn.params
        win = tuple(p["window_dimensions"])
        strides = tuple(p["window_strides"])
        pads = tuple(p.get("padding") or ((0, 0),) * len(win))
        val = self._val(eqn.invars[0])
        windowed = [i for i, k in enumerate(win) if k > 1]
        if not windowed:
            if val.node is None:
                val = self._as_data(val)
            return self._bind(eqn.outvars[0], val)     # degenerate window
        if len(win) != 4 or len(windowed) > 2:
            raise TraceError(
                f"unsupported reduce_window over rank-{len(win)} input "
                f"with window {win}; expected NCHW pooling")
        # pick the two spatial axes: trust the layout learned from the
        # producing conv; fall back to window-shape inference (NHWC when
        # the window sits on dims (1,2) leaving the trailing channel dim
        # alone, else NCHW — which also covers 1-d pools ((1,1,1,k):
        # r=1, s=k, q halves))
        if val.layout is not None:
            spatial = (1, 2) if val.layout == "NHWC" else (2, 3)
        elif win[3] == 1 and strides[3] == 1 and 1 in windowed:
            spatial = (1, 2)
        else:
            spatial = (2, 3)
        if val.node is None:
            # promote the raw input with the layout the window implies —
            # _chw_of_shape's NCHW default would garble NHWC geometry
            ishape = val.shape
            chw = (ishape[3], ishape[1], ishape[2]) if spatial == (1, 2) \
                else (ishape[1], ishape[2], ishape[3])
            val = self._as_data(val, chw)
            val.layout = "NHWC" if spatial == (1, 2) else "NCHW"
        if any(i not in spatial for i in windowed):
            raise TraceError(
                f"reduce_window window {win} pools a non-spatial dim for "
                f"the inferred layout (spatial dims {spatial})")
        assert val.node is not None       # promoted above when raw
        c, h, w = val.chw
        oshape = tuple(eqn.outvars[0].aval.shape)
        r, s = win[spatial[0]], win[spatial[1]]
        pq = (oshape[spatial[0]], oshape[spatial[1]])
        if (r, s) == (h, w) and pq == (1, 1):
            node = self._emit("gpool", "global_pool", [val.node],
                              c=c, h=h, w=w, m=c, p=1, q=1, r=h, s=w)
        else:
            node = self._emit(
                "pool", "pool", [val.node], c=c, h=h, w=w, m=c,
                p=pq[0], q=pq[1], r=r, s=s,
                stride=[int(strides[spatial[0]]), int(strides[spatial[1]])],
                # symmetric Layer.padding keeps the SAME halo (see _conv)
                padding=[max(int(lo), int(hi)) for lo, hi in
                         (pads[spatial[0]], pads[spatial[1]])])
        self._bind(eqn.outvars[0],
                   _Val(node, (c, pq[0], pq[1]), oshape, val.layout))

    def _reduce(self, eqn: Any) -> None:
        axes = tuple(eqn.params.get("axes", ()))
        val = self._val(eqn.invars[0])
        if val.node is None:              # reducing a parameter: constant
            return self._bind(eqn.outvars[0], val)
        spatial = ({1, 2} if val.layout == "NHWC" else {2, 3}) \
            if len(val.shape) == 4 else set()
        oshape = tuple(eqn.outvars[0].aval.shape)
        if spatial and spatial.issubset(set(axes)):
            assert val.node is not None
            c, h, w = val.chw
            node = self._emit("gpool", "global_pool", [val.node],
                              c=c, h=h, w=w, m=c, p=1, q=1, r=h, s=w)
            return self._bind(eqn.outvars[0], _Val(node, (c, 1, 1), oshape))
        if spatial & set(axes):
            # a partial spatial reduction (sum over H only) is real
            # compute with no Layer kind — folding it would silently
            # drop it and garble every downstream geometry
            raise TraceError(
                f"reduction over axes {axes} covers only part of the "
                f"spatial dims {sorted(spatial)}; only full global "
                f"pooling (both spatial dims) is supported")
        # softmax-style reductions along features: fold into the producer
        self._bind(eqn.outvars[0], _Val(val.node, val.chw, oshape))

    def _binary(self, eqn: Any, kind: str) -> None:
        a, b = (self._val(v) for v in eqn.invars[:2])
        oshape = tuple(eqn.outvars[0].aval.shape)
        if a.node is not None and b.node is not None and a.node != b.node:
            # two distinct traced operands = a real merge layer, even when
            # one side broadcasts (squeeze-excite: y * se(y) with se shaped
            # (1,C,1,1)) — folding it would dead-eliminate the whole branch
            big = a if math.prod(a.shape or (1,)) >= \
                math.prod(b.shape or (1,)) else b
            c, h, w = big.chw
            node = self._emit(kind, kind, [a.node, b.node],
                              c=c, h=h, w=w, m=c, p=h, q=w)
            return self._bind(eqn.outvars[0],
                              _Val(node, big.chw, oshape, big.layout))
        # bias add / relu(x) = max(x, 0) / scaling / x over its own
        # reduction (softmax): fold into the producer
        src = a if a.node is not None else b
        if src.node is None:
            return self._bind(eqn.outvars[0],
                              _Val(None, (0, 0, 0), oshape))  # const fold
        self._bind(eqn.outvars[0], _Val(src.node, src.chw, oshape))

    def _concat(self, eqn: Any) -> None:
        vals = [self._val(v) for v in eqn.invars]
        traced = [v for v in vals if v.node is not None]
        if not traced:
            return self._bind(eqn.outvars[0],
                              _Val(None, (0, 0, 0),
                                   tuple(eqn.outvars[0].aval.shape)))
        oshape = tuple(eqn.outvars[0].aval.shape)
        dim = int(eqn.params["dimension"])
        layout = next((v.layout for v in traced if v.layout), "NCHW")
        if len(oshape) == 4:
            feature_dim = 3 if layout == "NHWC" else 1
            if dim != feature_dim:
                raise TraceError(
                    f"only feature-dim concatenation is supported (got "
                    f"dimension={dim} on a {layout} activation, feature "
                    f"dim {feature_dim}); spatial concat is not a CNN "
                    f"layer this cost model knows")
        _c, h, w = traced[0].chw
        ctot = oshape[dim] if dim < len(oshape) else sum(
            v.chw[0] for v in traced)
        node = self._emit("cat", "concat",
                          [v.node for v in traced if v.node is not None],
                          c=ctot, h=h, w=w, m=ctot, p=h, q=w)
        self._bind(eqn.outvars[0], _Val(node, (ctot, h, w), oshape,
                                        layout if len(oshape) == 4
                                        else None))

    def _call(self, eqn: Any) -> None:
        params = eqn.params
        inner = params.get("jaxpr") or params.get("call_jaxpr") \
            or params.get("fun_jaxpr")
        if inner is None:
            raise TraceError(
                f"cannot find inner jaxpr of {eqn.primitive.name!r}")
        jaxpr = getattr(inner, "jaxpr", inner)     # ClosedJaxpr -> Jaxpr
        for iv, ov in zip(jaxpr.invars, eqn.invars):
            self.env[iv] = self._val(ov)
        self.walk(jaxpr)
        for ov, iv in zip(eqn.outvars, jaxpr.outvars):
            self._bind(ov, self._val(iv))

    def _alias(self, eqn: Any) -> None:
        vals = [self._val(v) for v in eqn.invars]
        src = next((v for v in vals if v.node is not None), vals[0])
        oshape = tuple(eqn.outvars[0].aval.shape)
        chw = src.chw
        if src.node is not None and len(oshape) <= 2 \
                and oshape != src.shape:
            # flatten before a classifier head: (1, C, H, W) -> (1, CHW)
            chw = (math.prod(oshape) if oshape else 1, 1, 1)
        layout = src.layout if len(oshape) == 4 else None
        if eqn.primitive.name == "transpose" and layout is not None:
            perm = tuple(eqn.params["permutation"])
            cpos = perm.index(1 if layout == "NCHW" else 3)
            layout = {1: "NCHW", 3: "NHWC"}.get(cpos)
        for ov in eqn.outvars:
            self._bind(ov, _Val(src.node, chw, oshape, layout))


def from_jax(fn: Callable[..., Any], example_args: Tuple[Any, ...], *,
             name: str = "traced_cnn",
             canonical: bool = True) -> GraphIR:
    """Trace ``fn(*example_args)`` into a (by default canonicalized)
    :class:`GraphIR`.

    ``example_args`` only supply shapes/dtypes — zeros work fine.  Raises
    :class:`TraceError` when the function strays outside the supported
    CNN primitive subset, and ``ImportError`` when jax itself is absent.
    """
    import jax                                     # deferred: optional dep

    closed = jax.make_jaxpr(fn)(*example_args)
    walker = _Walker(name)
    walker.walk(closed.jaxpr)
    outputs: List[str] = []
    for ov in closed.jaxpr.outvars:
        val = walker._val(ov)
        if val.node is None:
            raise TraceError(
                "a model output does not depend on any traced layer — "
                "is the function returning a constant?")
        if val.node not in outputs:
            outputs.append(val.node)
    ir = GraphIR(name=name, nodes=walker.nodes, outputs=outputs)
    return canonicalize(ir) if canonical else ir
