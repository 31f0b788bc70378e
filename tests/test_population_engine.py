"""Array-native population engine vs the scalar reference paths.

``repro.core.population.PopulationEvaluator`` must agree *bit-for-bit* with
the incremental engine on every surface it replaces:

* batched union-find group labels vs ``FusionState.group_masks()``,
* batched schedulability vs ``FusionState.is_schedulable()`` /
  ``ReferenceFusionState``,
* batched fitness vs the canonical scalar sum in ``Evaluator._fitness_fast``
  (same float operations in the same order — equality, not approx),

on random graphs and random populations (duplicates included), plus the
rare paths: the exact multi-group condensation-cycle residue
(:meth:`_sched_exact`), wide groups (span > 52 nodes), and the pure-python
group-table path for graphs too wide for int64 keys.  Finally, a fixed-seed
GA run must produce the identical best genome and fitness trajectory with
the engine on and off.
"""
import random

import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

np = pytest.importorskip("numpy")

from repro.core.fusion import FusionState
from repro.core.fusion_ref import ReferenceFusionState
from repro.core.graph import Layer, LayerGraph
from repro.core.population import MIN_BATCH
from repro.costmodel import SIMBA, Evaluator
from repro.workloads import mobilenet_v3_large

OBJECTIVES = ("edp", "energy", "cycles", "dram")


def _conv(name, c, hw, m, k=3):
    return Layer(name=name, kind="conv", c=c, h=hw, w=hw, m=m, p=hw, q=hw,
                 r=k, s=k, padding=(k // 2, k // 2))


def _expected_labels(state: FusionState, n: int):
    want = list(range(n))            # default: every node its own group
    for gm in state.group_masks():
        mn = (gm & -gm).bit_length() - 1
        mm = gm
        while mm:
            b = mm & -mm
            want[b.bit_length() - 1] = mn
            mm ^= b
    return want


def _check_population(graph, masks):
    """Engine vs scalar reference on one batch (labels, sched, fitness)."""
    cg = graph.compiled()
    states = [FusionState.from_mask(graph, mk) for mk in masks]
    ev = Evaluator(graph, SIMBA)
    pe = ev.population(backend="numpy")
    lab = pe.group_labels(masks)
    sch = pe.schedulable_masks(masks)
    scalar = Evaluator(graph, SIMBA)     # fresh: no shared cache effects
    fits = {obj: pe.fitness_masks(masks, obj) for obj in OBJECTIVES}
    for i, s in enumerate(states):
        assert lab[i].tolist() == _expected_labels(s, cg.n)
        assert bool(sch[i]) == s.is_schedulable()
        for obj in OBJECTIVES:
            # bit-identical to the canonical scalar sum; fitness() may
            # re-associate the same floats (~1 ulp), so only approx there
            assert fits[obj][i] == scalar._fitness_fast(s, obj)
            assert fits[obj][i] == pytest.approx(scalar.fitness(s, obj),
                                                 rel=1e-9)


@st.composite
def random_dag_population(draw):
    """A random layered conv DAG (chains + joins) and a random population
    with duplicate genomes."""
    n = draw(st.integers(min_value=4, max_value=9))
    hw, ch = 8, 4
    g = LayerGraph("rand")
    names = [g.add(Layer(name="in", kind="input", m=ch, p=hw, q=hw))]
    for i in range(n):
        k = draw(st.sampled_from([1, 3]))
        # parents: previous node, plus possibly one earlier (join -> add)
        prev = names[-1]
        extra = draw(st.integers(min_value=0, max_value=len(names) - 1))
        parents = [prev]
        if names[extra] != prev and draw(st.booleans()):
            parents.append(names[extra])
        cname = g.add(_conv(f"c{i}", ch, hw, ch, k), [prev])
        if len(parents) > 1:
            cname = g.add(Layer(name=f"a{i}", kind="add", c=ch, h=hw, w=hw,
                                m=ch, p=hw, q=hw), [cname, names[extra]])
        names.append(cname)
    m = g.compiled().m
    seed = draw(st.integers(min_value=0, max_value=2**31))
    rng = random.Random(seed)
    pop = [rng.getrandbits(m) for _ in range(24)]
    pop += pop[:8]                       # duplicates inside one batch
    return g, pop


@given(random_dag_population())
@settings(max_examples=25, deadline=None)
def test_engine_matches_scalar_on_random_graphs(gp):
    graph, masks = gp
    _check_population(graph, masks)


def test_engine_matches_scalar_on_mobilenet():
    graph = mobilenet_v3_large()
    m = graph.compiled().m
    rng = random.Random(11)
    masks = [rng.getrandbits(m) for _ in range(60)]
    masks += masks[:10]
    _check_population(graph, masks)


def test_reference_engine_agreement_small_graph():
    g = LayerGraph("chain")
    prev = g.add(Layer(name="in", kind="input", m=4, p=8, q=8))
    for i in range(5):
        prev = g.add(_conv(f"c{i}", 4, 8, 4), [prev])
    edges = g.edges
    m = g.compiled().m
    ev = Evaluator(g, SIMBA)
    pe = ev.population(backend="numpy")
    masks = list(range(1 << m))
    sch = pe.schedulable_masks(masks)
    for i, mk in enumerate(masks):
        fused = frozenset(e for j, e in enumerate(edges) if (mk >> j) & 1)
        ref = ReferenceFusionState(g, fused)
        assert bool(sch[i]) == ref.is_schedulable()


# ---- rare paths ---------------------------------------------------------------------
def _residue_graph():
    """Two fused groups, each individually cycle-free (no ``self_bad``),
    whose condensation still cycles: A={1,4} (fused 1->4), B={2,3,5}
    (fused 2->5, 3->5), unfused edges 1->3 (A->B) and 2->4 (B->A)."""
    g = LayerGraph("residue")
    l0 = g.add(Layer(name="n0", kind="input", m=4, p=8, q=8))
    l1 = g.add(_conv("n1", 4, 8, 4), [l0])
    l2 = g.add(_conv("n2", 4, 8, 4), [l0])
    l3 = g.add(_conv("n3", 4, 8, 4), [l1])
    g.add(Layer(name="n4", kind="add", c=4, h=8, w=8, m=4, p=8, q=8),
          [l1, l2])
    g.add(Layer(name="n5", kind="add", c=4, h=8, w=8, m=4, p=8, q=8),
          [l2, l3])
    return g


def test_residue_exact_cycle_check():
    g = _residue_graph()
    cg = g.compiled()
    eid = cg.edge_id
    fuse = lambda *edges: sum(1 << eid[e] for e in edges)
    cyc = fuse(("n1", "n4"), ("n2", "n5"), ("n3", "n5"))   # A + B: cycle
    ok = fuse(("n1", "n4"))                                # A alone: fine
    ev = Evaluator(g, SIMBA)
    pe = ev.population(backend="numpy")
    masks = [cyc, ok, 0, cyc]
    sch = pe.schedulable_masks(masks)
    states = [FusionState.from_mask(g, mk) for mk in masks]
    assert [bool(b) for b in sch] == [s.is_schedulable() for s in states]
    assert not sch[0] and sch[1] and sch[2]
    # the cyclic genome must have been caught by the exact residue check,
    # not the per-group flags (its groups are individually cycle-free)
    assert pe.stats()["residue_checks"] > 0
    for obj in OBJECTIVES:
        fits = pe.fitness_masks(masks, obj)
        scalar = Evaluator(g, SIMBA)
        for i, s in enumerate(states):
            assert fits[i] == scalar._fitness_fast(s, obj)


def test_wide_group_span_over_52():
    """A fully fused 60-conv chain has group span > 52 — the int64 key fast
    path must hand these to the exact python path."""
    g = LayerGraph("long")
    prev = g.add(Layer(name="in", kind="input", m=4, p=64, q=64))
    for i in range(60):
        prev = g.add(_conv(f"c{i}", 4, 64, 4, k=1), [prev])
    m = g.compiled().m
    rng = random.Random(3)
    masks = [(1 << m) - 1, 0, rng.getrandbits(m), (1 << m) - 1]
    _check_population(g, masks)


def test_python_rows_path_very_wide_graph():
    """Graphs beyond 1024 nodes cannot pack labels into int64 keys; the
    per-slot python table path must still agree with the scalar engine."""
    g = LayerGraph("huge")
    prev = g.add(Layer(name="in", kind="input", m=2, p=4, q=4))
    for i in range(1040):
        prev = g.add(_conv(f"c{i}", 2, 4, 2, k=1), [prev])
    m = g.compiled().m
    rng = random.Random(5)
    masks = [rng.getrandbits(m) for _ in range(3)]
    ev = Evaluator(g, SIMBA)
    pe = ev.population(backend="numpy")
    lab = pe.group_labels(masks)
    sch = pe.schedulable_masks(masks)
    for i, mk in enumerate(masks):
        s = FusionState.from_mask(g, mk)
        assert lab[i].tolist() == _expected_labels(s, g.compiled().n)
        assert bool(sch[i]) == s.is_schedulable()


# ---- engine selection + fixed-seed identity ----------------------------------------
def _ga_run(monkeypatch, mode, generations=10):
    from repro.search import SearchSession, SearchSpec
    monkeypatch.setenv("REPRO_POP_ENGINE", mode)
    spec = SearchSpec(workload="mobilenet_v3", accelerator="simba",
                      backend="ga", backend_config={"generations": generations},
                      seed=0)
    s = SearchSession(spec)
    s.run()
    return s


def test_fixed_seed_bit_identity_engine_on_vs_off(monkeypatch):
    off = _ga_run(monkeypatch, "off")
    on = _ga_run(monkeypatch, "numpy")
    assert off.evaluator.cache_stats()["pop_backend"] == "off"
    assert on.evaluator.cache_stats()["pop_backend"] == "numpy"
    assert on.result.best_state.mask == off.result.best_state.mask
    assert on.result.best_fitness == off.result.best_fitness
    assert on.result.history == off.result.history
    # pin the absolute values so a drift in BOTH engines is also caught
    assert hex(on.result.best_state.mask) == "0x10080410000c0004005c4a"
    assert on.result.best_fitness == 1.2808320767908055


def test_small_batches_use_scalar_path():
    graph = mobilenet_v3_large()
    ev = Evaluator(graph, SIMBA)
    states = [FusionState.from_mask(graph, 1 << i)
              for i in range(MIN_BATCH - 1)]
    fits = ev.fitness_batch(states, "edp")
    assert ev.cache_stats()["pop_batches"] == 0      # engine never engaged
    scalar = Evaluator(graph, SIMBA)
    assert fits == [scalar._fitness_fast(s, "edp") for s in states]


def test_engine_mode_off_env(monkeypatch):
    monkeypatch.setenv("REPRO_POP_ENGINE", "off")
    graph = mobilenet_v3_large()
    ev = Evaluator(graph, SIMBA)
    assert ev.cache_stats()["pop_backend"] == "off"
    monkeypatch.setenv("REPRO_POP_ENGINE", "bogus")
    from repro.core.population import engine_mode
    with pytest.raises(ValueError):
        engine_mode()


def test_jax_backend_labels_bit_identical():
    graph = mobilenet_v3_large()
    m = graph.compiled().m
    rng = random.Random(9)
    masks = [rng.getrandbits(m) for _ in range(40)]
    ev_np = Evaluator(graph, SIMBA)
    pe_np = ev_np.population(backend="numpy")
    ev_jx = Evaluator(graph, SIMBA)
    pe_jx = ev_jx.population(backend="jax")
    assert pe_jx.backend == "jax"
    assert np.array_equal(pe_jx.group_labels(masks), pe_np.group_labels(masks))
    for obj in OBJECTIVES:
        a = pe_jx.fitness_masks(masks, obj)
        b = pe_np.fitness_masks(masks, obj)
        assert np.array_equal(a, b)
    import jax
    dev = jax.devices()[0]
    st = pe_jx.stats()
    assert (st["device_platform"], st["device_kind"]) == \
        (dev.platform, dev.device_kind)
    assert pe_np.stats()["device_platform"] is None


def _skip_dag(n: int, seed: int) -> LayerGraph:
    """Seeded random DAG over ``n`` nodes in topological id order.  Chain
    edges ``i-1 -> i`` are rare (always ``0 -> 1``); every node takes two
    skip edges from the three nodes before its predecessor.  Groups then
    grow mostly through hooks over extra edges, in long chains: numpy's
    loop needs 5-7 hook rounds at n >= 127, against the kernel's fixed
    ``ceil(log2 n) + 2`` = 9-11."""
    rng = random.Random(seed)
    g = LayerGraph(f"dag{n}")
    names = []
    for i in range(n):
        ins = [names[i - 1]] if i == 1 or (i and rng.random() < 0.1) else []
        for _ in range(2 if i > 1 else 0):
            j = names[rng.randrange(max(0, i - 4), i - 1)]
            if j not in ins:
                ins.append(j)
        names.append(g.add(Layer(name=f"l{i}", kind="add", c=1, h=1, w=1,
                                 m=1, p=1, q=1), ins))
    return g


@pytest.mark.parametrize("fill", ["random", "all_fused", "none_fused"])
@pytest.mark.parametrize("p", [1, 17, 100])
@pytest.mark.parametrize("n", [2, 127, 128, 129, 300])
def test_jax_labels_match_numpy_on_random_dags(n, p, fill):
    """The jax label kernel (dense compares and reductions, a fixed round
    count) equals the numpy loop to its fixpoint, bit for bit, across the
    128-lane tile and at populations that are no multiple of 16."""
    graph = _skip_dag(n, seed=1000 + n)
    pe_np = Evaluator(graph, SIMBA).population(backend="numpy")
    pe_jx = Evaluator(graph, SIMBA).population(backend="jax")
    m = pe_np.t.m
    if fill == "random":
        rng = np.random.default_rng(n * 1000 + p)
        bits = (rng.random((p, m)) < rng.uniform(0.6, 1.0, (p, 1)))
    else:
        bits = np.full((p, m), fill == "all_fused")
    bits = bits.astype(np.uint8)
    want = pe_np._labels_np(bits)
    assert np.array_equal(pe_jx._jax_labels(bits), want)
    if fill == "none_fused":
        assert np.array_equal(want, np.tile(np.arange(n), p))
    if fill == "all_fused":
        assert not want.any()


# ---- the jax engine never falls back to numpy -------------------------------------
def test_jax_engine_without_jax_raises(monkeypatch):
    import sys
    monkeypatch.setitem(sys.modules, "jax", None)     # import jax -> error
    ev = Evaluator(mobilenet_v3_large(), SIMBA)
    with pytest.raises(ImportError, match="REPRO_POP_ENGINE=jax"):
        ev.population(backend="jax")


def test_jax_idempotence_guard_miss_raises():
    import jax.numpy as jnp
    graph = mobilenet_v3_large()
    n, m = graph.compiled().n, graph.compiled().m
    pe = Evaluator(graph, SIMBA).population(backend="jax")

    def shifted(bits, *tables):       # node j -> j-1: not a fixpoint
        lab = jnp.maximum(jnp.arange(n) - 1, 0)
        return jnp.broadcast_to(lab, (bits.shape[0], n))

    pe._jax_labels._kernel = shifted
    rng = random.Random(2)
    with pytest.raises(RuntimeError, match="not a fixpoint"):
        pe.fitness_masks([rng.getrandbits(m) for _ in range(MIN_BATCH)],
                         "edp")


# ---- one process per device ---------------------------------------------------------
def _no_fork(*_a, **_k):
    raise AssertionError("a fork context was requested under the jax engine")


def test_island_jax_engine_runs_in_process_and_matches_numpy(monkeypatch):
    import repro.search.island as island
    from repro.search import search
    cfg = {"preset": "fast", "generations": 6, "islands": 2,
           "migrate_every": 3}
    monkeypatch.setenv("REPRO_POP_ENGINE", "numpy")
    ref = search("vgg16", "simba", backend="island", seed=3,
                 backend_config=cfg)
    monkeypatch.setenv("REPRO_POP_ENGINE", "jax")
    monkeypatch.setattr(island, "_fork_context", _no_fork)
    got = search("vgg16", "simba", backend="island", seed=3,
                 backend_config=cfg)
    assert got.backend_stats["pop_backend"] == "jax"
    assert ref.backend_stats["pop_backend"] == "numpy"
    assert got.genome_mask == ref.genome_mask
    assert got.best_fitness == ref.best_fitness
    assert got.history == ref.history


def test_batch_scheduler_jax_engine_runs_misses_in_process(monkeypatch,
                                                            tmp_path):
    import multiprocessing
    from repro.search import SearchSpec
    from repro.serve import ArtifactStore, BatchScheduler
    monkeypatch.setenv("REPRO_POP_ENGINE", "jax")
    monkeypatch.setattr(multiprocessing, "get_context", _no_fork)
    sched = BatchScheduler(ArtifactStore(str(tmp_path)), workers=2)
    for wl in ("vgg16", "unet"):
        sched.submit(SearchSpec(workload=wl, backend="ga", backend_config={
            "preset": "fast", "generations": 2}))
    out = sched.run()
    assert out.stats["searched"] == 2 and out.stats["failed"] == 0
    for job in out.jobs:
        assert job.artifact.backend_stats["pop_backend"] == "jax"
