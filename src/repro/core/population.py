"""Array-native population evaluation: the GA hot loop, vectorized.

The incremental engine (``repro.core.fusion`` + ``Evaluator._fitness_fast``)
scores one genome at a time: per-offspring union-find maintenance, per-group
dict lookups, per-state Kahn checks.  Profiling a MobileNet-v3 run shows most
of the wall time is that per-genome structure maintenance, not the cost model
— every group cost is already cached after the first few generations.

This module scores a whole population at once.  A batch of genomes becomes a
``(P, n_edges)`` bool matrix and every per-genome quantity is computed with a
handful of numpy kernels over :class:`repro.core.graph.CompiledGraph`'s
integer arrays:

* **group labels** — CNN graphs are chains plus a few skip edges, so nodes
  are first labeled by maximal runs of consecutive fused chain edges
  (one ``maximum.accumulate`` for the whole batch), then the few non-adjacent
  fused edges are folded in with a Shiloach–Vishkin style hook-to-min /
  pointer-jump loop.  Labels equal each group's minimum member id, matching
  ``FusionState.group_masks()`` order exactly.
* **group identity** — each multi-member group's member bitmask is recovered
  exactly (no hashing): one ``bincount`` over the flattened labels sums
  per-node powers of two *offset by the group's minimum member*, giving the
  span pattern ``gmask >> label`` — sums of distinct powers spanning at most
  52 bits are exact in float64.  Narrow groups (span <= 52, i.e. essentially
  all of them on real CNNs) pack ``(min_member << 53) | pattern`` into a
  sorted int64 key table; wider groups fall back to reconstructing the exact
  python-int bitmask per slot (graphs beyond 1024 nodes skip the packed path
  entirely).  A table row carries the group's cached cost *correction*
  (group cost minus its members' singleton costs) plus two pure graph-shape
  flags:

  - ``low_exit`` — some edge leaves the group below its maximum member;
  - ``self_bad`` — some exit's strict closure re-enters the group
    (an immediate condensation cycle through this group alone).

* **schedulability** — node ids are topological by construction, so if every
  multi-member group's exit edges land *above* the group's maximum member,
  the condensation is acyclic (around any condensation cycle the per-group
  maximum would have to strictly increase).  A genome is therefore
  schedulable unless some group has ``low_exit``; any group with
  ``self_bad`` proves a cycle outright.  The rare residue — suspect genomes
  whose groups are all individually cycle-free — gets an exact batched
  check: per-group reachability unions over the static strict transitive
  closure, closed by boolean matrix squaring (:meth:`_sched_exact`).
* **fitness** — the layerwise baseline plus each group's correction, summed
  ``base + corrections`` in ascending group-min-member order via one
  ``bincount`` (which accumulates sequentially in input order), bit-for-bit
  identical to the canonical scalar path in ``Evaluator._fitness_fast``.
  Novel groups are costed through the evaluator's cost model only once a
  schedulable genome needs them, exactly like the scalar path.

Backends: ``numpy`` (default) and ``jax`` (opt-in via
``REPRO_POP_ENGINE=jax`` or ``PopulationEvaluator(backend="jax")``), which
runs the label pass as a jitted kernel (:func:`label_kernel`) on JAX's
default device and keeps the cost gathers in numpy.  The kernel is the same
algorithm as the numpy labels, with a fixed round count, written without a
single data-dependent gather or scatter: a TPU runs those one element at a
time, so each becomes a dense int32 compare against a one-hot mask and a
``max``/``min`` reduction, which its vector unit runs in parallel
(:func:`_labels_jax`).  Labels are integers, so the jax path stays
bit-identical.  A jax engine that cannot run raises; it never falls back to
numpy.  Its compiled programs go to JAX's persistent compilation cache
(:func:`enable_compile_cache`).  Set ``REPRO_POP_ENGINE=off`` to force the
per-state scalar path.

Spacemap interaction (``SearchSpec(spacemap=True)``): statically frozen
genes are masked out *upstream*, in :class:`repro.core.problem.
FusionProblem`'s operators — every genome this engine receives simply has
those mask bits permanently 0, so the ``(P, n_edges)`` matrices carry
all-zero columns for frozen edges and no engine change (or conditional) is
needed here.  The chain-run labeling is indifferent to which bits can vary,
and the cost-correction table never sees a group that crosses a frozen
edge because no genome ever fuses one.
"""
from __future__ import annotations

import functools
import os
import threading
from pathlib import Path
from typing import Dict, List, Optional, Sequence

import numpy as np

from repro.obs import Phases

_MISSING = object()

#: smallest batch routed through the array engine; below this the per-state
#: canonical path wins on fixed overheads (both paths are bit-identical)
MIN_BATCH = 16

_I64 = np.int64
_U64 = np.uint64


def engine_mode() -> str:
    """Requested engine backend: ``numpy`` (default), ``jax``, or ``off``."""
    mode = os.environ.get("REPRO_POP_ENGINE", "numpy").lower()
    if mode not in ("numpy", "jax", "off"):
        raise ValueError(
            f"REPRO_POP_ENGINE={mode!r}; valid: numpy, jax, off")
    return mode


#: where the compile cache goes when no directory is configured: a fixed
#: path in the checkout, so that later processes find what earlier ones wrote
_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> None:
    """Turn on JAX's persistent compilation cache for this process.

    Call before the first compile.  A directory already configured wins:
    ``JAX_COMPILATION_CACHE_DIR``, which JAX reads itself, or one the caller
    set; otherwise the cache lives in ``<repo root>/.jax_cache``.  Every
    compile is kept: the label kernels compile in 0.27-1.51 s on a TPU v5e,
    mostly under JAX's default 1 s floor for caching."""
    import jax
    if jax.config.jax_compilation_cache_dir is None:
        jax.config.update("jax_compilation_cache_dir", str(_CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)


class StaticTables:
    """Per-:class:`CompiledGraph` integer arrays shared by every batch (and,
    under the island backend, by every forked worker via COW)."""

    def __init__(self, cg):
        self.cg = cg
        n, m = cg.n, cg.m
        self.n = n
        self.m = m
        self.W = (n + 63) // 64                   # bitset words per node set
        self.mask_bytes = (m + 7) // 8
        eu = np.asarray(cg.eu, dtype=_I64)
        ev = np.asarray(cg.ev, dtype=_I64)
        self.eu, self.ev = eu, ev
        # chain edges (u -> u+1) drive the run-labeling pass; the rest
        # ("extra" edges: skips, concat fan-ins) go through hook/jump
        chain = ev == eu + 1
        self.chain_nodes = eu[chain]              # run break positions
        self.chain_eids = np.nonzero(chain)[0]
        self.extra_eids = np.nonzero(~chain)[0]
        self.xu = eu[~chain]
        self.xv = ev[~chain]
        # direct successors / strict transitive closure, as python ints
        # (flag computation for novel groups) and packed bitset rows
        # (the exact residue check)
        succ_int = [0] * n
        reach_int = [0] * n
        for u in range(n - 1, -1, -1):
            r = 0
            s = 0
            for v in cg.succ_ids[u]:
                s |= 1 << v
                r |= (1 << v) | reach_int[v]
            succ_int[u] = s
            reach_int[u] = r
        self.succ_int = succ_int
        self.reach_int = reach_int
        self.Eb = _pack_rows(succ_int, self.W)    # (n, W) direct successors
        self.Cp = _pack_rows(reach_int, self.W)   # (n, W) strict closure
        self.nodebit = _pack_rows([1 << u for u in range(n)], self.W)
        self.ar_n = np.arange(n, dtype=_I64)
        # span-offset powers of two: exact float64 for offsets <= 52 (the
        # group-key fast path); larger offsets only occur on wide groups,
        # which are routed to the exact python path before these are trusted
        self.pow2 = np.ldexp(1.0, np.minimum(self.ar_n, 1023).astype(np.int32))
        self.bitpos = np.arange(64, dtype=_U64)
        self._grids: Dict[int, tuple] = {}        # per-population-size caches

    def grids(self, p: int) -> tuple:
        g = self._grids.get(p)
        if g is None:
            n = self.n
            rowbase = np.repeat(np.arange(p, dtype=_I64) * n, n)
            ar_flat = np.tile(self.ar_n, p)
            if len(self._grids) > 16:             # bound the per-P cache
                self._grids.clear()
            g = (rowbase, ar_flat)
            self._grids[p] = g
        return g

    def group_flags(self, gmask: int) -> tuple:
        """(low_exit, self_bad) for one member bitmask — graph-shape-only
        properties, computed once per distinct group (python bitset math)."""
        succ = self.succ_int
        ex = 0
        mm = gmask
        while mm:
            b = mm & -mm
            ex |= succ[b.bit_length() - 1]
            mm ^= b
        ex &= ~gmask                              # exit targets
        low_exit = bool(ex & ((1 << (gmask.bit_length() - 1)) - 1))
        self_bad = False
        reach = self.reach_int
        mm = ex
        while mm:
            b = mm & -mm
            if reach[b.bit_length() - 1] & gmask:
                self_bad = True
                break
            mm ^= b
        return low_exit, self_bad


def _pack_rows(ints: Sequence[int], w: int) -> np.ndarray:
    out = np.zeros((len(ints), w), dtype=_U64)
    mask = (1 << 64) - 1
    for i, val in enumerate(ints):
        for j in range(w):
            out[i, j] = (val >> (64 * j)) & mask
    return out


class PopulationEvaluator:
    """Batched fitness/schedulability over ``(P, n_edges)`` genome matrices.

    Owned by (and sharing caches with) one
    :class:`repro.costmodel.evaluator.Evaluator`; obtained via
    ``Evaluator.population()``.  Results are bit-for-bit identical to the
    canonical scalar path (pinned by ``tests/test_population_engine.py``).
    """

    def __init__(self, evaluator, backend: Optional[str] = None,
                 phases: Optional[Phases] = None):
        self.ev = evaluator
        #: named spans of each batch's phases (``pop.*``), see stats()
        self.phases = phases if phases is not None else Phases()
        self.t = StaticTables(evaluator.cg)
        self.backend = backend or engine_mode()
        if self.backend == "off":
            self.backend = "numpy"
        self._jax_labels = None
        if self.backend == "jax":
            self._jax_labels = _JaxLabels(self.t, self.phases)
        # one batch at a time: island threads share this engine's tables
        self._lock = threading.Lock()
        # persistent group table (parallel arrays over row ids)
        self._ikeys = np.empty(0, dtype=_I64)     # sorted span-offset keys
        self._irows = np.empty(0, dtype=_I64)     # ... their row ids
        self._key_dict: Dict[int, int] = {}       # gmask -> row (insert side)
        self._corr_tab = np.empty((0, 6), dtype=np.float64)
        self._tvalid = np.empty(0, dtype=bool)    # correction is not None
        self._costed = np.empty(0, dtype=bool)    # correction computed yet?
        # low_exit / self_bad flags, packed (2**32 * self_bad + low_exit) so
        # one bincount recovers both per-genome any()s exactly: each weight
        # is 0 / 1 / 2**32 / 2**32+1 and per-genome sums stay far below 2**53
        self._lowsb = np.empty(0, dtype=np.float64)
        self._gmasks: List[int] = []              # row id -> member bitmask
        self._pending: List[tuple] = []           # rows awaiting commit
        self.states_scored = 0
        self.residue_checks = 0                   # exact pair-closure runs

    # ---- public API ---------------------------------------------------------------
    def fitness_masks(self, masks: Sequence[int], objective: str = "edp"
                      ) -> np.ndarray:
        """Fitness per genome mask (float64 array), canonical order."""
        with self._lock:
            with self.phases.span("pop.batch"):
                out = self._fitness_masks(masks, objective)
            self.states_scored += len(masks)
        return out

    def schedulable_masks(self, masks: Sequence[int]) -> np.ndarray:
        """Batched exact schedulability (bool array)."""
        with self._lock:
            return self._analyze(masks)[5]

    def group_labels(self, masks: Sequence[int]) -> np.ndarray:
        """(P, n) min-member group label per node (for tests/tools)."""
        with self._lock:
            lf = self._labels(self._unpack(masks))[0]
        return lf.reshape(len(masks), self.t.n)

    def stats(self) -> Dict[str, object]:
        """Engine counters.  ``device_platform``/``device_kind`` name the
        device the jax kernel's labels were computed on (None until the
        first jax batch, and always None on the numpy backend).
        ``batches`` and ``batch_time_s`` are the ``pop.batch`` span's calls
        and seconds; ``phases`` holds every ``pop.*`` span:

        * ``pop.build`` — this engine's construction by
          ``Evaluator.population()``: the layerwise baseline, the static
          tables and (jax) placing them on the device;
        * ``pop.batch`` — one :meth:`fitness_masks` call, lock held;
        * ``pop.unpack`` — mask ints to the ``(P, m)`` bit matrix;
        * ``pop.labels.launch`` — padding, host-to-device copy and dispatch
          of the label kernel; ``pop.labels.wait`` — reading its labels
          back (the device time still running, then device-to-host);
          ``pop.labels.check`` — int64 conversion and the fixpoint check;
          ``pop.labels.host`` — the numpy backend's label pass;
        * ``pop.maxmem`` — each node's group maximum member;
        * ``pop.rows`` — group slots, keys and table lookup/insert;
        * ``pop.sched`` — per-genome flags and the exact residue check;
        * ``pop.cost`` — cost-model runs for novel groups;
        * ``pop.gather`` — validity and the fitness bincounts."""
        dev = self._jax_labels.device if self._jax_labels else None
        batch_time = self.phases.seconds("pop.batch")
        return {
            "backend": self.backend,
            "device_platform": dev.platform if dev else None,
            "device_kind": dev.device_kind if dev else None,
            "batches": self.phases.calls("pop.batch"),
            "states_scored": self.states_scored,
            "batch_time_s": batch_time,
            "batch_evals_per_sec": (self.states_scored / batch_time
                                    if batch_time else 0.0),
            "group_table_rows": len(self._gmasks),
            "residue_checks": self.residue_checks,
            "phases": self.phases.snapshot(),
        }

    # ---- batch pipeline -------------------------------------------------------------
    def _unpack(self, masks: Sequence[int]) -> np.ndarray:
        t = self.t
        nb = t.mask_bytes
        with self.phases.span("pop.unpack"):
            buf = b"".join(mk.to_bytes(nb, "little") for mk in masks)
            raw = np.frombuffer(buf, dtype=np.uint8).reshape(len(masks), nb)
            return np.unpackbits(raw, axis=1, bitorder="little")[:, :t.m]

    def _analyze(self, masks: Sequence[int]) -> tuple:
        """Shared front half: labels, group slots, table rows, and exact
        per-genome schedulability — no cost-model work."""
        t = self.t
        p, n = len(masks), t.n
        span = self.phases.span
        bits = self._unpack(masks)
        lf, mx = self._labels(bits)
        rowbase, ar_flat = t.grids(p)
        with span("pop.rows"):
            # one slot per multi-member group: its min member ("label") node
            slot_mask = (lf == ar_flat) & (mx > ar_flat)
            gslots = np.nonzero(slot_mask)[0]     # ascending (genome, label)
            gp = gslots // n
            if gslots.size:
                rows = self._rows_for_slots(lf, mx, gslots)
            else:
                rows = np.empty(0, dtype=_I64)
        if not gslots.size:
            return lf, mx, gslots, gp, rows, np.ones(p, dtype=bool)
        with span("pop.sched"):
            flags = np.bincount(gp, weights=self._lowsb.take(rows),
                                minlength=p).astype(_I64)
            unsched = (flags >> np.int64(32)) > 0
            suspect = (flags & np.int64(0xFFFFFFFF)) > 0
            residue = np.nonzero(suspect & ~unsched)[0]
            if residue.size:                      # rare: multi-group cycles
                self.residue_checks += residue.size
                cyc = self._sched_exact(lf.reshape(p, n)[residue],
                                        mx.reshape(p, n)[residue])
                unsched[residue] |= cyc
        return lf, mx, gslots, gp, rows, ~unsched

    def _fitness_masks(self, masks, objective) -> np.ndarray:
        base = self.ev._ensure_base()
        _, _, gslots, gp, rows, ok = self._analyze(masks)
        # cost-model work only for schedulable genomes' novel groups,
        # mirroring the scalar path's laziness
        keep = None
        if rows.size:
            keep = ok.take(gp)
            need = rows[keep & ~self._costed.take(rows)]
            if need.size:
                with self.phases.span("pop.cost"):
                    self._cost_rows(need)
        with self.phases.span("pop.gather"):
            return self._gather(base, gp, rows, ok, keep, objective)

    def _gather(self, base, gp, rows, ok, keep, objective) -> np.ndarray:
        """Fitness per genome: the base sums plus the corrections of the
        groups of schedulable genomes (``keep``: which group slots those
        are; None when no genome has a multi-member group)."""
        p = ok.size
        if keep is not None:
            gp = gp[keep]
            rows = rows[keep]
            bad = np.bincount(gp, weights=~self._tvalid.take(rows),
                              minlength=p) > 0
        else:
            bad = np.zeros(p, dtype=bool)
        valid = ok & ~bad
        # canonical sums: base first, then corrections ascending by group
        # min member (bincount accumulates sequentially in input order)
        m2 = gp.size
        cat = np.empty(p + m2, dtype=_I64)
        cat[:p] = np.arange(p, dtype=_I64)
        cat[p:] = gp
        corr = self._corr_tab
        w = np.empty(p + m2)

        def comp(c: int) -> np.ndarray:
            w[:p] = base[c]
            w[p:] = corr[rows, c]
            return np.bincount(cat, weights=w, minlength=p)

        if objective == "edp":
            new = comp(0) * comp(1)
        elif objective == "energy":
            new = comp(0)
        elif objective == "cycles":
            new = comp(1)
        elif objective == "dram":
            new = comp(2) + comp(3)
        else:
            raise ValueError(f"unknown objective {objective!r}")
        out = np.zeros(p, dtype=np.float64)
        score = valid & (new > 0)
        out[score] = base[6][objective] / new[score]
        return out

    # ---- labels ---------------------------------------------------------------------
    def _labels(self, bits: np.ndarray):
        """Flat ``(P*n,)`` min-member labels + per-node group max member."""
        if self._jax_labels is not None:
            lf = self._jax_labels(bits)
        else:
            with self.phases.span("pop.labels.host"):
                lf = self._labels_np(bits)
        with self.phases.span("pop.maxmem"):
            return lf, self._maxmem(lf, bits.shape[0])

    def _labels_np(self, bits: np.ndarray) -> np.ndarray:
        t = self.t
        p, n = bits.shape[0], t.n
        rowbase, _ = t.grids(p)
        # run labeling over consecutive fused chain edges
        newrun = np.ones((p, n), dtype=bool)
        # unpackbits yields 0/1 uint8, so a bool view is free (no astype copy)
        newrun[:, t.chain_nodes + 1] = ~(bits.view(np.bool_)[:, t.chain_eids])
        lab = np.maximum.accumulate(np.where(newrun, t.ar_n, 0), axis=1)
        lf = lab.ravel()
        # fold non-adjacent fused edges in: hook to min, then pointer-jump
        if t.extra_eids.size:
            pi, j = np.nonzero(bits[:, t.extra_eids])
            if pi.size:
                base = pi.astype(_I64) * n
                iu = base + t.xu[j]
                iv = base + t.xv[j]
                while True:
                    a = lf.take(iu)
                    b = lf.take(iv)
                    if np.array_equal(a, b):
                        break
                    mn = np.minimum(a, b)
                    np.minimum.at(lf, base + a, mn)
                    np.minimum.at(lf, base + b, mn)
                    lf = lf.take(rowbase + lf)
        while True:                               # compress to fixpoint
            nxt = lf.take(rowbase + lf)
            if np.array_equal(nxt, lf):
                return lf
            lf = nxt

    def _maxmem(self, lf: np.ndarray, p: int) -> np.ndarray:
        """Per-node maximum member id of the node's group (flat (P*n,))."""
        t = self.t
        rowbase, ar_flat = t.grids(p)
        mf = np.empty(p * t.n, dtype=_I64)
        mf[rowbase + lf] = ar_flat                # ascending: last write = max
        return mf.take(rowbase + lf)

    # ---- group table ----------------------------------------------------------------
    def _rows_for_slots(self, lf, mx, gslots) -> np.ndarray:
        """Group-table row per slot, inserting flag-only rows for novel
        groups (their costs are deferred until a schedulable genome needs
        them).

        Lookup key: one exact int64 per group — ``(label << 53) | pattern``
        where ``pattern = gmask >> label`` is built by a single bincount of
        span-offset powers of two (exact in float64 while the group span is
        <= 52; wider groups are rare and fall back to an exact per-slot
        python path, as do graphs with > 1024 nodes where the label would
        not fit above bit 53)."""
        t = self.t
        n = t.n
        if n > 1024:
            return self._rows_python(lf, gslots)
        # every node contributes 2^(node - label) to its label's flat slot
        # (singletons land on unread slots); one full-width bincount, then
        # gather the multi-group slots
        rowbase, ar_flat = t.grids(lf.size // n)
        g = gslots.size
        pattern = np.bincount(rowbase + lf, weights=t.pow2.take(ar_flat - lf),
                              minlength=lf.size).take(gslots)
        mn = gslots % n
        wide = (mx.take(gslots) - mn) > 52
        wide_any = bool(wide.any())
        if wide_any:
            pattern = np.where(wide, 1.0, pattern)
        patt_i = pattern.astype(_I64)             # <= 53 bits: exact
        keys = (mn << np.int64(53)) | patt_i
        if wide_any:
            keys[wide] = -1                       # never in the sorted table
        if len(self._ikeys):
            posc = np.minimum(np.searchsorted(self._ikeys, keys),
                              len(self._ikeys) - 1)
            hit = self._ikeys[posc] == keys
            rows = np.where(hit, self._irows.take(posc), np.int64(-1))
        else:
            hit = np.zeros(g, dtype=bool)
            rows = np.full(g, -1, dtype=_I64)
        self.ev.group_hits += int(hit.sum())
        miss = np.nonzero(~hit)[0]
        if miss.size:
            gsl = gslots.take(miss).tolist()
            kl = keys.take(miss).tolist()
            pl = patt_i.take(miss).tolist()
            mnl = mn.take(miss).tolist()
            wl = wide.take(miss).tolist() if wide_any else None
            for jj, ii in enumerate(miss.tolist()):
                if wl is not None and wl[jj]:
                    gmask = self._slot_gmask(lf, gsl[jj])
                    skey = None                   # dict-only: no int64 key
                else:
                    gmask = pl[jj] << mnl[jj]
                    skey = kl[jj]
                r = self._key_dict.get(gmask)
                if r is None:
                    r = self._new_row(gmask, skey)
                else:
                    self.ev.group_hits += 1
                rows[ii] = r
            if self._pending:
                self._commit_rows()
        return rows

    def _rows_python(self, lf, gslots) -> np.ndarray:
        """Exact per-slot path for graphs too wide for int64 keys."""
        rows = np.empty(gslots.size, dtype=_I64)
        for ii, sl in enumerate(gslots.tolist()):
            gmask = self._slot_gmask(lf, sl)
            r = self._key_dict.get(gmask)
            if r is None:
                r = self._new_row(gmask, None)
            else:
                self.ev.group_hits += 1
            rows[ii] = r
        if self._pending:
            self._commit_rows()
        return rows

    def _slot_gmask(self, lf: np.ndarray, slot: int) -> int:
        """Reassemble one group's member bitmask from the flat labels."""
        n = self.t.n
        base = slot - slot % n
        members = np.nonzero(lf[base:base + n] == slot % n)[0]
        gmask = 0
        for u in members.tolist():
            gmask |= 1 << u
        return gmask

    def _new_row(self, gmask: int, skey: Optional[int]) -> int:
        """Insert a flag-only row for a never-seen group (no costing)."""
        low, sb = self.t.group_flags(gmask)
        r = len(self._gmasks) + len(self._pending)
        self._pending.append((skey, low, sb, gmask))
        return r

    def _grow(self, need: int) -> None:
        """Capacity-double the parallel arrays (rows beyond the live count
        stay zero/False until committed, so over-allocation is invisible to
        the ``take``-based readers)."""
        cap = self._tvalid.size
        if need <= cap:
            return
        newcap = max(64, 2 * cap)
        while newcap < need:
            newcap *= 2
        ct = np.zeros((newcap, 6))
        ct[:cap] = self._corr_tab
        self._corr_tab = ct
        for name in ("_tvalid", "_costed", "_lowsb"):
            a = getattr(self, name)
            b = np.zeros(newcap, dtype=a.dtype)
            b[:cap] = a
            setattr(self, name, b)

    def _commit_rows(self) -> None:
        """Append this batch's novel rows to the parallel arrays and merge
        their int64 keys into the sorted lookup arrays."""
        pend = self._pending
        self._pending = []
        self._grow(len(self._gmasks) + len(pend))
        newk = []
        newr = []
        for skey, low, sb, gmask in pend:
            r = len(self._gmasks)
            self._lowsb[r] = low + sb * 4294967296.0
            self._key_dict[gmask] = r
            self._gmasks.append(gmask)
            if skey is not None:
                newk.append(skey)
                newr.append(r)
        if newk:
            nk = np.array(newk, dtype=_I64)
            nr = np.array(newr, dtype=_I64)
            order = np.argsort(nk)
            nk = nk[order]
            pos = np.searchsorted(self._ikeys, nk)
            self._ikeys = np.insert(self._ikeys, pos, nk)
            self._irows = np.insert(self._irows, pos, nr[order])

    def _cost_rows(self, need: np.ndarray) -> None:
        """Run the cost model for not-yet-costed rows (once per group)."""
        ev = self.ev
        for r in sorted(set(need.tolist())):
            gmask = self._gmasks[r]
            d = ev._corr.get(gmask, _MISSING)
            if d is _MISSING:
                d = ev._compute_correction(gmask)
                ev._corr[gmask] = d
            else:
                ev.group_hits += 1
            if d is not None:
                self._corr_tab[r] = d
                self._tvalid[r] = True
            self._costed[r] = True

    # ---- exact residue check ----------------------------------------------------------
    def _sched_exact(self, ls: np.ndarray, ms: np.ndarray) -> np.ndarray:
        """Exact condensation-cycle check for suspect genomes whose groups
        are individually cycle-free: reconstruct reachability between multi
        groups from the static strict closure and close it by boolean matrix
        squaring; a cycle exists iff two groups reach each other (single-group
        cycles were already excluded by the ``self_bad`` flag)."""
        t = self.t
        s, n = ls.shape
        w = t.W
        skey = (ls + np.arange(s, dtype=_I64)[:, None] * n).ravel()
        inst = np.nonzero((ms > ls).ravel())[0]   # multi-member node instances
        node = inst % n
        order = np.argsort(skey.take(inst), kind="stable")
        snode = node.take(order)
        sslot = skey.take(inst).take(order)
        starts = np.nonzero(np.r_[True, sslot[1:] != sslot[:-1]])[0]
        uslot = sslot.take(starts)
        # per-group unions of (closure | members) via one reduceat
        stacked = np.concatenate([t.Cp, t.nodebit], axis=1)
        red = np.bitwise_or.reduceat(stacked[snode], starts, axis=0)
        r0, gm = red[:, :w], red[:, w:]
        g2 = len(uslot)
        usi = uslot // n
        cnt = np.bincount(usi, minlength=s)
        k = int(cnt.max())
        off = np.zeros(s, dtype=_I64)
        np.cumsum(cnt[:-1], out=off[1:])
        rank = np.arange(g2, dtype=_I64) - off.take(usi)
        r0p = np.zeros((s, k, w), dtype=_U64)
        gmp = np.zeros((s, k, w), dtype=_U64)
        r0p[usi, rank] = r0
        gmp[usi, rank] = gm
        h = ((r0p[:, :, None, :] & gmp[:, None, :, :]) != 0).any(-1)
        cyc = np.zeros(s, dtype=bool)
        if k > 1:
            for _ in range(max(1, int(np.ceil(np.log2(k))))):
                hf = h.astype(np.float32)
                nh = h | (np.matmul(hf, hf) > 0)
                if np.array_equal(nh, h):
                    break
                h = nh
            mut = h & h.swapaxes(1, 2)
            mut &= ~np.eye(k, dtype=bool)
            cyc = mut.any(axis=(1, 2))
        return cyc


def label_tables(t: StaticTables) -> tuple:
    """The graph arguments of :func:`label_kernel`, as int32 host arrays:
    node ids, chain-edge nodes and ids, extra-edge endpoints and ids."""
    return tuple(np.asarray(a, dtype=np.int32) for a in
                 (t.ar_n, t.chain_nodes, t.chain_eids, t.xu, t.xv,
                  t.extra_eids))


def _labels_jax(bits, ar, chain_nodes, chain_eids, xu, xv, extra_eids):
    """Label propagation for a ``(P, m)`` 0/1 genome matrix: chain-run
    labels, then a fixed number of hook-to-min / pointer-jump rounds over
    the extra edges, then a final jump.  Every shape, and so the compiled
    program, follows from the argument shapes alone.

    No step indexes by data.  A TPU runs a gather or scatter with data
    indices one element at a time (~20 ns each on a v5e), so every index
    here is an int32 compare against a one-hot mask built from the tables,
    and every pick or scatter a ``max``/``min``/``any`` over the selected
    entries, which the vector unit runs in parallel.  Labels are held
    node-major, ``(n, P)``, so the genomes lie along the vector lanes and
    the large reductions run over a leading axis:

    * column picks ``bits[:, eids]``: ``(k, m)`` masks, ``any`` over ``m``;
    * run breaks: node ``v`` starts a run unless it has a chain edge into
      it and every such edge is fused (a ``(C, n)`` mask);
    * endpoint labels ``lab[xu]``: ``(E, n)`` masks, ``max`` over ``n``;
    * hook: both scatter-mins of a round as one ``(E, n, P)`` select and
      ``min`` over the edges, exact because both scatter the same minimum;
    * pointer jump ``lab[lab[v, p], p]``: a ``(n, n, P)`` select and
      ``max`` over ``u``, in which exactly one ``u == lab[v, p]`` matches.

    Integers throughout, never a float matmul: the labels must come out
    exact.  The largest intermediate, ``n x n x P`` int32, is fused into
    its reduction by XLA and never stored."""
    import jax
    import jax.numpy as jnp

    n = ar.shape[0]
    rounds = int(np.ceil(np.log2(max(n, 2)))) + 2
    on = (bits != 0).T                                   # (m, P)
    col = jnp.arange(bits.shape[1], dtype=jnp.int32)
    node = ar[:, None]                                   # (n, 1)

    def pick(eids):                                      # bits[:, eids].T
        hit = eids[:, None] == col                       # (k, m)
        return jnp.any(hit[:, :, None] & on, axis=1)

    def at(lab, hit):                                    # lab[nodes]
        return jnp.max(jnp.where(hit[:, :, None], lab, -1), axis=1)

    def jump(lab):                                       # lab[lab[v, p], p]
        hit = lab == node[:, :, None]                    # (u, v, P)
        return jnp.max(jnp.where(hit, lab[:, None, :], -1), axis=0)

    into = (chain_nodes + 1)[:, None] == ar              # (C, n)
    broken = jnp.any(into[:, :, None] & ~pick(chain_eids)[:, None, :],
                     axis=0)
    newrun = broken | ~jnp.any(into, axis=0)[:, None]
    lab = jax.lax.cummax(jnp.where(newrun, node, 0), axis=0)
    if extra_eids.shape[0]:
        fused = pick(extra_eids)[:, None, :]             # (E, 1, P)
        hu = xu[:, None] == ar                           # (E, n); XLA does
        hv = xv[:, None] == ar                           # not hoist these
        big = jnp.iinfo(jnp.int32).max

        def body(lab, _):
            a = at(lab, hu)[:, None, :]
            b = at(lab, hv)[:, None, :]
            hit = fused & ((a == node) | (b == node))    # (E, n, P)
            hook = jnp.where(hit, jnp.minimum(a, b), big).min(axis=0)
            return jump(jnp.minimum(lab, hook)), None

        lab, _ = jax.lax.scan(body, lab, None, length=rounds)
    return jump(lab).T


@functools.cache
def label_kernel():
    """The jitted label kernel, ``(bits, *label_tables(t)) -> (P, n)``
    labels; built on first use so the numpy path never imports jax."""
    import jax
    return jax.jit(_labels_jax)


class _JaxLabels:
    """The jax engine's label pass for one graph: tables placed on the
    default device once, P padded to a multiple of 16 to bound recompiles.
    One kernel call per batch, with no gather or scatter inside: on a TPU
    v5e the kernel takes tens of microseconds, and the call's cost is
    mostly launch, transfer and sync.  Integer-only, so results are
    bit-identical to the numpy path; the fixed round count always reaches
    the fixpoint on connected hooks, and the host checks that it did
    (raising, not falling back, if not)."""

    def __init__(self, t: StaticTables, phases: Phases):
        try:
            import jax
        except ImportError as e:
            raise ImportError(
                "the jax population engine was requested "
                "(REPRO_POP_ENGINE=jax) but jax cannot be imported") from e
        enable_compile_cache()
        self.t = t
        self.phases = phases
        self._kernel = label_kernel()
        self._tables = jax.device_put(label_tables(t))
        self.device = None                        # where the labels lived

    def __call__(self, bits: np.ndarray) -> np.ndarray:
        import jax.numpy as jnp
        span = self.phases.span
        p = bits.shape[0]
        with span("pop.labels.launch"):
            pp = -(-p // 16) * 16                 # pad P: bound recompiles
            if pp != p:
                bits = np.concatenate(
                    [bits, np.zeros((pp - p, bits.shape[1]),
                                    dtype=bits.dtype)])
            out = self._kernel(jnp.asarray(bits), *self._tables)
        if self.device is None:
            self.device = next(iter(out.devices()))
        with span("pop.labels.wait"):
            host = np.asarray(out)
        with span("pop.labels.check"):
            lf = host[:p].astype(_I64).ravel()
            rowbase = self.t.grids(p)[0]
            if not np.array_equal(lf, lf.take(rowbase + lf)):
                raise RuntimeError(
                    "jax label kernel returned labels that are not a "
                    f"fixpoint (graph with n={self.t.n}, P={p})")
        return lf
