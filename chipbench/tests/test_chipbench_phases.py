"""The harness's phases end to end on the CPU at a tiny budget, with the
device check skipped: a sound run is correct; the control and each fault
the cells can have come out not correct."""
from __future__ import annotations

import json

import numpy as np
import pytest

from chipbench import check, harness, run

CPU = {"platform": "cpu", "kind": "cpu", "count": 1}
BENCH = json.loads((harness.ROOT / "BENCHMARK.json").read_text())
#: the cells whose files the harness holds, in or out of BENCHMARK.json
CELLS = {"mobilenet_v3-simba.search": ("mobilenet_v3-simba", "search"),
         "resnet50-eyeriss.daemon": ("resnet50-eyeriss", "daemon")}
#: end-to-end metrics each driver reports
E2E = {"session": ["search_evals_per_s", "setup_s"],
       "daemon": ["job_p50_s", "job_p95_s", "setup_s"]}


def tiny(cell: str) -> dict:
    config, traffic = CELLS[cell]
    ctx = harness.load_parts({"name": cell, "config": config,
                              "traffic": traffic, "chips": 1}, BENCH)
    mix = ctx["mix"]
    ctx["end_to_end"] = [{"name": n, "unit": "s"} for n in E2E[mix["driver"]]]
    if mix["driver"] == "session":
        mix["backend_config"] = {"preset": "fast", "generations": 12}
        mix["sample_share"] = 0.5
    else:
        mix.update(rate_per_s=3.0, drain_s=30, warmup_jobs=1,
                   backend_config={"preset": "fast", "generations": 4})
    return ctx


@pytest.fixture
def jax_engine(monkeypatch):
    monkeypatch.setenv("REPRO_POP_ENGINE", "jax")
    harness.enable_cache()


def run_tiny(cell: str, seed: int = 3, seconds: float = 1.5) -> tuple:
    ctx = tiny(cell)
    res = run.run_cell(ctx, seed, seconds, False, CPU, harness.CompileLog())
    return ctx, res


@pytest.mark.parametrize("cell", ["mobilenet_v3-simba.search",
                                  "resnet50-eyeriss.daemon"])
def test_sound_run_is_correct(jax_engine, cell):
    _, res = run_tiny(cell)
    assert res["correct"] is True
    assert res["attempted"] > 0 and res["failed"] == 0
    assert list(res)[-1] == "compared"
    for k, v in res["compared"].items():
        assert v["value"] <= v["limit"], k
    names = set(res["metrics"])
    assert "setup_s" in names and len(names) >= 2
    for m in res["metrics"].values():
        assert m["value"] > 0


def _window(cell: str, seed: int = 4, seconds: float = 1.5) -> tuple:
    ctx = tiny(cell)
    driver = harness.DRIVERS[ctx["mix"]["driver"]](ctx)
    try:
        driver.setup()
        w = driver.window(seed, seconds, harness.Tracer(False, 0, 0),
                          harness.CompileLog())
    finally:
        driver.close()
    return ctx, w


@pytest.mark.parametrize("cell", ["mobilenet_v3-simba.search",
                                  "resnet50-eyeriss.daemon"])
def test_control_fails(jax_engine, cell):
    ctx, w = _window(cell)
    sound = check.check(w, ctx)
    assert check.verdict(sound, ctx["limits"], w["attempted"])
    ctl = check.check(check.control_answers(w, ctx["config"]), ctx)
    assert ctl.rel_gap > ctx["limits"]["rel_gap"]
    assert not check.verdict(ctl, ctx["limits"], w["attempted"])


def _perturb_scores(monkeypatch, fn):
    from repro.core import population
    inner = population.PopulationEvaluator.fitness_masks

    def broken(self, masks, objective="edp"):
        return fn(np.asarray(inner(self, masks, objective)).copy())

    monkeypatch.setattr(population.PopulationEvaluator, "fitness_masks",
                        broken)


def test_altered_score_fails(jax_engine, monkeypatch):
    """A score altered where it is produced."""
    def alter(f):
        f[0] *= 1.0 + 1e-6
        return f
    _perturb_scores(monkeypatch, alter)
    _, res = run_tiny("mobilenet_v3-simba.search")
    assert res["correct"] is False


def test_half_batch_left_out_fails(jax_engine, monkeypatch):
    """Half of each batch left out, the mean of the rest put in its place."""
    def half(f):
        k = len(f) // 2
        f[k:] = f[:k].mean() if k else 0.0
        return f
    _perturb_scores(monkeypatch, half)
    _, res = run_tiny("mobilenet_v3-simba.search")
    assert res["correct"] is False


def test_search_returning_its_start_fails(jax_engine, monkeypatch):
    """A search that returns its starting genome unchanged."""
    from repro.core import ga
    inner = ga.run_ga_problem

    def unchanged(problem, *a, **kw):
        out = inner(problem, *a, **kw)
        start = problem.initial()
        out.best_state = start
        out.best_fitness = problem.fitness(start)
        return out

    monkeypatch.setattr(ga, "run_ga_problem", unchanged)
    from repro.search import backends
    monkeypatch.setattr(backends, "run_ga_problem", unchanged,
                        raising=False)
    _, res = run_tiny("mobilenet_v3-simba.search")
    assert res["correct"] is False


def test_altered_cost_fails(jax_engine, monkeypatch):
    """An artifact's cost altered where the cost model produces it."""
    from repro.costmodel import default
    inner = default.DefaultCostModel.cost_group

    def altered(self, key):
        bd = inner(self, key)
        if bd is not None:
            import dataclasses
            bd = dataclasses.replace(bd, energy_pj=bd.energy_pj * (1 + 1e-6))
        return bd

    monkeypatch.setattr(default.DefaultCostModel, "cost_group", altered)
    _, res = run_tiny("resnet50-eyeriss.daemon")
    assert res["correct"] is False


def test_store_serving_another_artifact_fails(jax_engine, monkeypatch):
    """The store answers a key with the artifact stored under another."""
    from repro.serve import store as store_mod
    inner = store_mod.ArtifactStore.load_key
    first = {}

    def wrong(self, key):
        art = inner(self, key)
        if art is not None:
            first.setdefault("art", art)
            return first["art"]
        return art

    monkeypatch.setattr(store_mod.ArtifactStore, "load_key", wrong)
    _, res = run_tiny("resnet50-eyeriss.daemon", seed=5)
    assert res["correct"] is False


def test_unanswered_job_fails(jax_engine, monkeypatch):
    """A job that never resolves counts as unanswered."""
    from repro.serve import daemon
    inner = daemon.ScheduleDaemon._run_job
    calls = {"n": 0}

    def stall(self, job):
        calls["n"] += 1
        if calls["n"] == 2:            # the first traffic job after warm-up
            raise RuntimeError("planted failure")
        return inner(self, job)

    monkeypatch.setattr(daemon.ScheduleDaemon, "_run_job", stall)
    ctx = tiny("resnet50-eyeriss.daemon")
    ctx["mix"]["drain_s"] = 3
    res = run.run_cell(ctx, 6, 1.5, False, CPU, harness.CompileLog())
    assert res["correct"] is False
    assert res["compared"]["unanswered"]["value"] >= 1
