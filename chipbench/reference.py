"""Plain reference of the schedule semantics the benchmark checks answers by.

A straightforward implementation of what a fusion schedule costs on one of
the paper's machines (arXiv:2311.12235 §II-§IV), written from the
configuration's own data and importing nothing of the program under test:

* the graph is the configuration's layer table (``graph`` in
  ``configs/<config>.json``), edges in producer order;
* a genome is an edge bitmask: fused edges join layers into groups
  (weakly connected components);
* a genome is legal when the groups' condensation is acyclic and every
  group with more than one MAC layer fits a line-buffer tile of at least
  one output row in the activation buffer;
* a group's cost is the sum of its layers' costs under the closed-form
  mapper, with edges inside the group kept on chip and weights re-streamed
  once per tile pass when the group's weights exceed the weight buffer;
* a schedule's cost is the sum over its groups, and the EDP fitness is the
  layer-by-layer schedule's EDP over the genome's EDP (0 when illegal).

``dtype`` is the float type of every non-integer quantity: ``float`` is the
configuration's stated float64; ``numpy.float32`` is the control, the
nearest precision below it.
"""
from __future__ import annotations

import math
import re
from typing import Dict, List, Optional, Sequence, Tuple

COMPUTE_KINDS = ("conv", "dwconv", "fc")
_REPART = re.compile(r"^(?P<base>[\w.-]+)@act(?P<delta>[+-]\d+)$")


class Layer:
    """One node of the layer table: input C x H x W, output M x P x Q,
    filter R x S."""

    __slots__ = ("name", "kind", "c", "h", "w", "m", "p", "q", "r", "s",
                 "stride", "dilation", "groups")

    def __init__(self, row: Dict):
        for k in self.__slots__:
            setattr(self, k, row[k])

    @property
    def input_size(self) -> int:
        return self.c * self.h * self.w

    @property
    def output_size(self) -> int:
        return self.m * self.p * self.q

    @property
    def weight_size(self) -> int:
        if self.kind == "conv":
            return self.m * (self.c // self.groups) * self.r * self.s
        if self.kind == "dwconv":
            return self.m * self.r * self.s
        if self.kind == "fc":
            return self.m * self.c
        return 0

    @property
    def macs(self) -> int:
        if self.kind == "conv":
            return (self.m * self.p * self.q * (self.c // self.groups)
                    * self.r * self.s)
        if self.kind == "dwconv":
            return self.m * self.p * self.q * self.r * self.s
        if self.kind == "fc":
            return self.m * self.c
        if self.kind in ("add", "mul"):
            return self.output_size
        return 0


class Graph:
    """The configuration's layer table as a DAG over node ids (table order,
    which is topological)."""

    def __init__(self, table: Dict):
        fields = table["fields"]
        rows = [dict(zip(fields, node)) for node in table["nodes"]]
        self.name = table["name"]
        self.names = [r["name"] for r in rows]
        idx = {nm: i for i, nm in enumerate(self.names)}
        self.layers = [Layer(r) for r in rows]
        self.n = len(rows)
        self.preds: List[List[int]] = [[idx[s] for s in r["inputs"]]
                                       for r in rows]
        self.succs: List[List[int]] = [[] for _ in rows]
        for v, ps in enumerate(self.preds):
            for u in ps:
                self.succs[u].append(v)
        # edge i is bit i of a genome: producers in table order, each
        # producer's consumers in the order they were attached
        self.edges = list(dict.fromkeys(
            (u, v) for u in range(self.n) for v in self.succs[u]))


class Machine:
    """One of Table I's edge machines, flat: a PE array over an activation
    and a weight buffer over LPDDR4."""

    def __init__(self, spec: Dict, energy: Dict, dtype=float):
        self.name = spec["name"]
        self.pe_x, self.pe_y = spec["pe_x"], spec["pe_y"]
        self.macs_per_pe = spec["macs_per_pe"]
        self.act_buf_kib = spec["act_buf_kib"]
        self.weight_buf_kib = spec["weight_buf_kib"]
        self.dataflow = spec["dataflow"]
        self.word_bytes = spec["word_bytes"]
        self.pe_count = self.pe_x * self.pe_y
        self.peak = self.pe_count * self.macs_per_pe
        self.act_words = self.act_buf_kib * 1024 // self.word_bytes
        self.weight_words = self.weight_buf_kib * 1024 // self.word_bytes
        f = dtype
        self.dram_words_per_cycle = (f(spec["dram_gbps"]) * f(1e9)
                                     / (f(spec["clock_mhz"]) * f(1e6))
                                     / f(self.word_bytes))
        self.e = {k: f(v) for k, v in energy.items()}
        self.f = f

    def e_sram(self, kib: int):
        """Per-word access energy of a banked SRAM of ``kib`` KiB."""
        e, f = self.e, self.f
        if kib <= 0:
            return e["rf"]
        return max(f(0.6), e["sram_anchor"]
                   * (f(kib) / e["sram_anchor_kib"]) ** e["sram_exponent"])


def machine_for(accelerator: str, machines: Dict[str, Dict], energy: Dict,
                dtype=float) -> Machine:
    """The machine an accelerator spec names: a Table I entry, or one with
    ``@act<delta>`` KiB moved from the weight to the activation buffer
    (iso-capacity, paper Fig. 11)."""
    m = _REPART.match(accelerator)
    base = m.group("base") if m else accelerator
    spec = dict(machines[base])
    if m:
        d = int(m.group("delta"))
        spec["act_buf_kib"] += d
        spec["weight_buf_kib"] -= d
        if spec["act_buf_kib"] <= 0 or spec["weight_buf_kib"] <= 0:
            raise ValueError(f"{accelerator}: a buffer would vanish")
    spec["name"] = accelerator
    return Machine(spec, energy, dtype)


# ---- one layer ------------------------------------------------------------
def _lanes(n: int, lanes: int):
    if n <= 0 or lanes <= 0:
        return 1.0
    return n / (math.ceil(n / lanes) * lanes)


def _utilization(ly: Layer, mc: Machine, dataflow: str):
    if ly.kind not in COMPUTE_KINDS:
        return mc.f(1.0)
    f = mc.f
    if dataflow == "weight_stationary":
        u = (f(_lanes(ly.m, mc.pe_count))
             * f(_lanes(max(ly.c // ly.groups, 1), mc.macs_per_pe)))
    else:
        r = max(ly.r, 1)
        if r <= mc.pe_y:
            v = f(r * (mc.pe_y // r)) / f(mc.pe_y)
        else:
            v = f(_lanes(r, mc.pe_y))
        u = v * f(_lanes(max(ly.q, 1), mc.pe_x))
    return max(u, f(1.0) / f(mc.peak))


def _dataflow(ly: Layer, mc: Machine) -> str:
    if mc.dataflow != "flexible":
        return mc.dataflow
    ws = _utilization(ly, mc, "weight_stationary")
    rs = _utilization(ly, mc, "row_stationary")
    return "weight_stationary" if ws >= rs else "row_stationary"


def layer_cost(ly: Layer, mc: Machine, inputs_off: bool, outputs_off: bool,
               passes: int) -> Tuple:
    """(energy, compute cycles, DRAM cycles, DRAM reads, DRAM writes,
    activation tensors written, MACs) of one layer."""
    f, e = mc.f, mc.e
    if ly.macs == 0 and ly.kind == "input":
        return (f(0.0), f(0.0), f(0.0), 0, 0, 0, 0)
    I, O, W = ly.input_size, ly.output_size, ly.weight_size
    reads = writes = 0
    if W > 0:
        if W <= mc.weight_words or I <= mc.act_words:
            w_dram, i_dram = W, I
        else:
            n_w = math.ceil(W / mc.weight_words)
            n_i = math.ceil(I / mc.act_words)
            if W + I * n_w <= I + W * n_i:
                w_dram, i_dram = W, I * n_w
            else:
                w_dram, i_dram = W * n_i, I
        reads += w_dram * max(passes, 1)
    else:
        i_dram = I
    if inputs_off:
        reads += i_dram
    act_writes_dram = 0
    if outputs_off and O:
        writes += O
        act_writes_dram = 1

    df = _dataflow(ly, mc)
    if df == "weight_stationary":
        in_amort = min(max(ly.m // max(ly.groups, 1), 1), mc.macs_per_pe)
        w_amort = min(max(ly.p * ly.q, 1), 1024)
    else:
        in_amort = min(max(ly.r, 1), mc.pe_y)
        w_amort = min(max(ly.q, 1), 256)
    macs = f(ly.macs)
    act_reads = macs / f(max(in_amort, 1))
    act_fill = f((I if inputs_off else 0) + O)
    wbuf_reads = macs / f(max(w_amort, 1))
    wbuf_writes = f(W * max(passes, 1))
    energy = (macs * e["mac"]
              + f(3.0) * macs * e["rf"]
              + (act_reads + act_fill) * mc.e_sram(mc.act_buf_kib)
              + (wbuf_reads + wbuf_writes) * mc.e_sram(mc.weight_buf_kib)
              + (act_reads + wbuf_reads) * f(0.5) * e["noc"]
              + f(reads + writes) * e["dram"])
    util = _utilization(ly, mc, df)
    compute = macs / (f(mc.peak) * util) if ly.macs else f(0.0)
    dram = f(reads + writes) / mc.dram_words_per_cycle
    return (energy, compute, dram, reads, writes, act_writes_dram, ly.macs)


# ---- groups ---------------------------------------------------------------
def _input_rows(ly: Layer, rows_out: int) -> int:
    rows_out = min(rows_out, ly.p) if ly.p else rows_out
    if ly.kind in ("conv", "dwconv", "pool"):
        need = ((rows_out - 1) * ly.stride[0]
                + (ly.r - 1) * ly.dilation[0] + 1)
        return min(max(need, 1), ly.h) if ly.h else need
    if ly.kind in ("fc", "global_pool"):
        return ly.h if ly.h else 1
    if ly.kind == "upsample":
        return min(max(math.ceil(rows_out * max(ly.h, 1) / max(ly.p, 1)), 1),
                   max(ly.h, 1))
    return rows_out


def footprint(g: Graph, order: Sequence[int], t: int) -> int:
    """Activation-buffer words to stream a group at sink tile height ``t``:
    each member's live output window plus each outside input's window."""
    mset = set(order)
    rows: Dict[int, int] = {}
    for i in reversed(order):
        ly = g.layers[i]
        inner = [v for v in g.succs[i] if v in mset]
        if not inner:
            need = t
        else:
            need = max([1] + [_input_rows(g.layers[v], rows[v])
                              for v in inner])
        rows[i] = min(need, ly.p) if ly.p else need
    total = 0
    staged = set()
    for i in order:
        ly = g.layers[i]
        if ly.output_size:
            total += ly.m * ly.q * min(rows[i], ly.p or rows[i])
        for u in g.preds[i]:
            if u in mset or u in staged:
                continue
            staged.add(u)
            src = g.layers[u]
            if src.output_size:
                win = _input_rows(ly, rows[i])
                total += src.m * src.q * min(win, src.p or win)
    return total


def tile_rows(g: Graph, order: Sequence[int], capacity: int) -> int:
    """Largest sink tile height that fits ``capacity`` words (0: none)."""
    if footprint(g, order, 1) > capacity:
        return 0
    lo, hi = 1, max(max(g.layers[i].p or 1 for i in order), 1)
    while lo < hi:
        mid = (lo + hi + 1) // 2
        if footprint(g, order, mid) <= capacity:
            lo = mid
        else:
            hi = mid - 1
    return lo


def _kahn(nodes: Sequence[int], succs) -> Optional[List[int]]:
    """Kahn order of ``nodes`` (first-ready first); None on a cycle."""
    nset = set(nodes)
    indeg = {v: 0 for v in nodes}
    for u in nodes:
        for v in succs(u):
            if v in nset:
                indeg[v] += 1
    ready = [v for v in nodes if indeg[v] == 0]
    out = []
    while ready:
        u = ready.pop(0)
        out.append(u)
        for v in succs(u):
            if v in nset:
                indeg[v] -= 1
                if indeg[v] == 0:
                    ready.append(v)
    return out if len(out) == len(nodes) else None


class Reference:
    """Costs and legality of genomes of one (graph, machine) pair; group
    costs are memoized by member set."""

    def __init__(self, graph: Graph, machine: Machine):
        self.g = graph
        self.mc = machine
        self._memo: Dict[frozenset, Optional[Dict]] = {}
        self._base = None

    def groups(self, mask: int) -> List[List[int]]:
        """Groups (member ids ascending), ordered by smallest member."""
        g = self.g
        parent = list(range(g.n))

        def find(x):
            while parent[x] != x:
                parent[x] = parent[parent[x]]
                x = parent[x]
            return x

        for i, (u, v) in enumerate(g.edges):
            if (mask >> i) & 1:
                ru, rv = find(u), find(v)
                if ru != rv:
                    parent[max(ru, rv)] = min(ru, rv)
        comp: Dict[int, List[int]] = {}
        for v in range(g.n):
            comp.setdefault(find(v), []).append(v)
        return [comp[k] for k in sorted(comp)]

    def schedulable(self, groups: List[List[int]]) -> bool:
        of = {}
        for gi, ms in enumerate(groups):
            for v in ms:
                of[v] = gi
        out = [set() for _ in groups]
        for u, v in self.g.edges:
            if of[u] != of[v]:
                out[of[u]].add(of[v])
        return _kahn(range(len(groups)), lambda x: sorted(out[x])) is not None

    def group(self, members: Sequence[int]) -> Optional[Dict]:
        """One group's cost, or None when it does not fit on chip."""
        key = frozenset(members)
        if key in self._memo:
            return self._memo[key]
        g, mc, f = self.g, self.mc, self.mc.f
        order = _kahn(sorted(members), lambda u: g.succs[u])
        multi = sum(1 for i in order if g.layers[i].macs) > 1
        passes, t = 1, 0
        if multi and len(order) > 1:
            t = tile_rows(g, order, mc.act_words)
            if t == 0:
                self._memo[key] = None
                return None
            if sum(g.layers[i].weight_size for i in order) > mc.weight_words:
                passes = math.ceil(max(g.layers[i].p or 1 for i in order) / t)
        e = c = d = f(0.0)
        reads = writes = acts = macs = 0
        for i in order:
            ps, ss = g.preds[i], g.succs[i]
            lc = layer_cost(g.layers[i], mc,
                            inputs_off=(not ps) or any(p not in key
                                                       for p in ps),
                            outputs_off=(not ss) or any(s not in key
                                                        for s in ss),
                            passes=passes if multi else 1)
            e, c, d = e + lc[0], c + lc[1], d + lc[2]
            reads, writes = reads + lc[3], writes + lc[4]
            acts, macs = acts + lc[5], macs + lc[6]
        out = {"members": sorted(g.names[i] for i in order),
               "energy_pj": e, "compute_cycles": c, "dram_cycles": d,
               "dram_read_words": reads, "dram_write_words": writes,
               "act_write_events": acts, "macs": macs, "tile_rows": t,
               "weight_passes": passes}
        self._memo[key] = out
        return out

    def schedule(self, mask: int) -> Optional[Dict]:
        """The genome's schedule cost and groups, or None when illegal."""
        groups = self.groups(mask)
        if not self.schedulable(groups):
            return None
        costs = []
        for ms in groups:
            gc = self.group(ms)
            if gc is None:
                return None
            costs.append(gc)
        f = self.mc.f
        e = cyc = f(0.0)
        for gc in costs:
            e = e + gc["energy_pj"]
            cyc = cyc + max(gc["compute_cycles"], gc["dram_cycles"])
        return {"energy_pj": e, "cycles": cyc,
                "dram_read_words": sum(x["dram_read_words"] for x in costs),
                "dram_write_words": sum(x["dram_write_words"]
                                        for x in costs),
                "act_write_events": sum(x["act_write_events"]
                                        for x in costs),
                "macs": sum(x["macs"] for x in costs),
                "n_groups": len(costs), "groups": costs}

    def baseline(self) -> Dict:
        if self._base is None:
            self._base = self.schedule(0)
        return self._base

    def fitness(self, mask: int):
        """EDP fitness: the layer-by-layer EDP over the genome's (0 when
        the genome is illegal)."""
        s = self.schedule(mask)
        if s is None:
            return self.mc.f(0.0)
        b = self.baseline()
        new = s["energy_pj"] * s["cycles"]
        return b["energy_pj"] * b["cycles"] / new if new > 0 else 0.0
