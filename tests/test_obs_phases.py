"""Phase spans (``repro.obs.phase``): the counters they keep, their place on
the JAX profiler's host plane, and that timing a search changes nothing
it returns."""
from __future__ import annotations

import glob
import json
import os
import subprocess
import sys

import pytest

from repro.obs import Phases, merge_phases
from repro.obs import phase as phase_mod
from repro.search import SearchSession, SearchSpec
from repro.search.artifact import ScheduleArtifact

#: every span a jax-engine ga search opens, by owner
POP_CHILDREN = ("pop.unpack", "pop.labels.launch", "pop.labels.wait",
                "pop.labels.check", "pop.maxmem", "pop.rows", "pop.sched",
                "pop.cost", "pop.gather")
SPANS = (("pop.build", "pop.batch") + POP_CHILDREN
         + ("ga.generation", "ga.mutate", "ga.ahead", "ga.score",
            "ga.select", "ga.observe", "session.build", "session.finish"))

GENERATIONS = 5


def fast_spec(seed: int = 3, telemetry: bool = False) -> SearchSpec:
    return SearchSpec(workload="mobilenet_v3", accelerator="simba",
                      seed=seed, telemetry=telemetry,
                      backend_config={"preset": "fast",
                                      "generations": GENERATIONS})


class FakeAnnotation:
    """Stands in for ``jax.profiler.TraceAnnotation``; logs enter/exit."""

    enabled = False
    log: list = []

    def __init__(self, name):
        self.name = name

    @classmethod
    def is_enabled(cls):
        return cls.enabled

    def __enter__(self):
        self.log.append(("enter", self.name))

    def __exit__(self, *exc):
        self.log.append(("exit", self.name))


@pytest.fixture
def fake_annotation(monkeypatch):
    FakeAnnotation.log = []
    FakeAnnotation.enabled = False
    monkeypatch.setattr(phase_mod, "_annotation", FakeAnnotation)
    return FakeAnnotation


# ---- the primitive ----------------------------------------------------------------

def test_phases_accumulate_and_nest(fake_annotation):
    ph = Phases()
    for _ in range(3):
        with ph.span("outer"):
            with ph.span("inner"):
                sum(range(1000))
    assert ph.calls("outer") == 3 and ph.calls("inner") == 3
    assert ph.seconds("outer") >= ph.seconds("inner") > 0.0
    assert ph.calls("never") == 0 and ph.seconds("never") == 0.0
    snap = ph.snapshot()
    assert list(snap) == ["inner", "outer"]
    assert snap["outer"] == {"calls": 3, "seconds": ph.seconds("outer")}
    # the profiler is off: no annotation was entered
    assert fake_annotation.log == []


def test_phases_annotate_while_recording(fake_annotation):
    fake_annotation.enabled = True
    ph = Phases()
    with ph.span("outer"):
        with ph.span("inner"):
            pass
    assert fake_annotation.log == [("enter", "outer"), ("enter", "inner"),
                                   ("exit", "inner"), ("exit", "outer")]


def test_span_counts_when_the_block_raises(fake_annotation):
    fake_annotation.enabled = True
    ph = Phases()
    with pytest.raises(KeyError):
        with ph.span("boom"):
            raise KeyError("x")
    assert ph.calls("boom") == 1
    assert fake_annotation.log[-1] == ("exit", "boom")


def test_a_phase_does_not_nest_inside_itself():
    ph = Phases()
    with ph.span("x"):
        with pytest.raises(RuntimeError, match="already open"):
            with ph.span("x"):
                pass
    with ph.span("x"):                          # closed again: reusable
        pass
    assert ph.calls("x") == 2


def test_merge_phases_sums_by_name():
    a = {"x": {"calls": 1, "seconds": 0.5}}
    b = {"x": {"calls": 2, "seconds": 0.25}, "y": {"calls": 1,
                                                  "seconds": 1.0}}
    assert merge_phases(a, b, {}) == {"x": {"calls": 3, "seconds": 0.75},
                                      "y": {"calls": 1, "seconds": 1.0}}
    assert a == {"x": {"calls": 1, "seconds": 0.5}}     # inputs untouched


def test_obs_and_numpy_search_never_import_jax():
    code = ("import sys\n"
            "from repro.search import search\n"
            "a = search('vgg16', backend_config={'preset': 'fast', "
            "'generations': 2})\n"
            "assert a.backend_stats['phases']['pop.batch']['calls'] > 0\n"
            "assert 'jax' not in sys.modules, 'jax imported'\n")
    env = dict(os.environ, REPRO_POP_ENGINE="numpy",
               PYTHONPATH=os.pathsep.join(
                   [os.path.join(os.path.dirname(__file__), "..", "src"),
                    os.environ.get("PYTHONPATH", "")]))
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr


# ---- spans of a real search -------------------------------------------------------

def test_numpy_engine_spans_and_batch_time_are_one_clock(monkeypatch):
    monkeypatch.setenv("REPRO_POP_ENGINE", "numpy")
    s = SearchSession(fast_spec())
    art = s.run()
    st = s.evaluator.population().stats()
    ph = st["phases"]
    assert st["batch_time_s"] == ph["pop.batch"]["seconds"]
    assert st["batches"] == ph["pop.batch"]["calls"] > 0
    assert "pop.labels.host" in ph and "pop.labels.wait" not in ph
    stats = art.backend_stats
    assert stats["batch_time_s"] == stats["phases"]["pop.batch"]["seconds"]
    assert stats["phases"]["ga.generation"]["calls"] == GENERATIONS
    assert stats["phases"]["session.build"]["calls"] == 1
    assert stats["phases"]["session.finish"]["calls"] == 1


def test_island_threads_merge_their_phases(monkeypatch):
    monkeypatch.setenv("REPRO_POP_ENGINE", "numpy")
    spec = SearchSpec(workload="vgg16", seed=1, backend="island",
                      backend_config={"preset": "fast", "generations": 4,
                                      "islands": 2, "migrate_every": 2,
                                      "workers": "thread"})
    art = SearchSession(spec).run()
    assert art.backend_stats["phases"]["ga.generation"]["calls"] == 2 * 4


@pytest.fixture
def jax_engine(monkeypatch):
    monkeypatch.setenv("REPRO_POP_ENGINE", "jax")
    SearchSession(fast_spec(seed=99)).run()        # compile outside traces


def _traced(tmp_path, spec):
    """Run one search inside a profiler session; (session, artifact, host
    events by line)."""
    import jax
    from jax.profiler import ProfileData
    jax.profiler.start_trace(str(tmp_path))
    try:
        s = SearchSession(spec)
        art = s.run()
    finally:
        jax.profiler.stop_trace()
    path = sorted(glob.glob(str(tmp_path / "**" / "*.xplane.pb"),
                            recursive=True))[-1]
    lines = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for ln in plane.lines:
                lines.append([(e.name, e.start_ns, e.start_ns + e.duration_ns)
                              for e in ln.events])
    return s, art, lines


def test_spans_land_on_the_profilers_host_plane(jax_engine, tmp_path):
    s, art, lines = _traced(tmp_path, fast_spec())
    names = {n for evs in lines for n, _, _ in evs}
    assert set(SPANS) <= names
    batches = []
    for evs in lines:
        bs = sorted((a, b) for n, a, b in evs if n == "pop.batch")
        batches.extend(bs)
        for n, a, b in evs:
            if n in POP_CHILDREN:
                assert any(a0 <= a and b <= b1 for a0, b1 in bs), n
    assert len(batches) == s.evaluator.population().stats()["batches"]
    assert len(batches) == art.backend_stats["phases"]["pop.batch"]["calls"]


def _outcome(art):
    return (art.genome_mask, art.best_fitness, list(art.history))


def test_results_identical_with_profiler_and_telemetry_on_or_off(
        jax_engine, tmp_path):
    base = _outcome(SearchSession(fast_spec()).run())
    with_tel = _outcome(SearchSession(fast_spec(telemetry=True)).run())
    _, art, _ = _traced(tmp_path / "a", fast_spec())
    _, art_tel, _ = _traced(tmp_path / "b", fast_spec(telemetry=True))
    assert with_tel == base
    assert _outcome(art) == base
    assert _outcome(art_tel) == base


def test_artifact_phases_round_trip(monkeypatch):
    monkeypatch.setenv("REPRO_POP_ENGINE", "numpy")
    art = SearchSession(fast_spec()).run()
    ph = art.backend_stats["phases"]
    assert set(ph) >= {"pop.batch", "ga.generation", "session.build"}
    back = ScheduleArtifact.from_dict(json.loads(json.dumps(art.to_dict())))
    assert back.backend_stats["phases"] == ph


# ---- the kernel's name ------------------------------------------------------------

def test_label_kernel_module_name_is_pinned():
    """The benchmark finds the label kernel's device time by this module
    name (``chipbench/harness.py`` ``LABEL_KERNEL``): a rename must fail
    here rather than silence its metrics."""
    import numpy as np

    from repro.core.population import (StaticTables, label_kernel,
                                       label_tables)
    from repro.workloads import mobilenet_v3_large
    t = StaticTables(mobilenet_v3_large().compiled())
    bits = np.zeros((16, t.m), dtype=np.uint8)
    lowered = label_kernel().lower(bits, *label_tables(t))
    assert "module @jit__labels_jax" in lowered.as_text()
    assert lowered.compile().as_text().startswith("HloModule jit__labels_jax")


@pytest.mark.parametrize("workload", ["mobilenet_v3", "resnet50", "unet",
                                      "vgg16"])
def test_label_kernel_has_no_gather_or_scatter(workload):
    """A TPU runs a gather or scatter with data indices one element at a
    time; the label kernel does every pick, hook and pointer jump as dense
    compares and reductions instead, and must stay that way."""
    import numpy as np

    from repro.core.population import (StaticTables, label_kernel,
                                       label_tables)
    from repro.search.registry import build_workload
    t = StaticTables(build_workload(workload).compiled())
    bits = np.zeros((16, t.m), dtype=np.uint8)
    text = label_kernel().lower(bits, *label_tables(t)).as_text()
    assert "module @jit__labels_jax" in text
    assert "stablehlo.scatter" not in text
    assert "stablehlo.gather" not in text
