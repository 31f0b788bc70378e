"""The harness's phases end to end on the CPU at a tiny budget, with the
device check skipped: a sound run is correct; the control and each fault
the cells can have come out not correct, for every configuration, each
held to the plain reference it names."""
from __future__ import annotations

import dataclasses
import json

import numpy as np
import pytest

from chipbench import check, harness, run

CPU = {"platform": "cpu", "kind": "cpu", "count": 1}
BENCH = json.loads((harness.ROOT / "BENCHMARK.json").read_text())
#: the cells whose files the harness holds, in or out of BENCHMARK.json
CELLS = {"mobilenet_v3-simba.search": ("mobilenet_v3-simba", "search"),
         "resnet50-eyeriss.daemon": ("resnet50-eyeriss", "daemon")}
#: every configuration under configs/, and the cell whose limits hold it
CONFIGS = sorted(p.stem for p in (harness.HERE / "configs").glob("*.json"))
HOME = {config: cell for cell, (config, _) in CELLS.items()}
#: every configuration under every mix
EVERY_CELL = [f"{c}.{t}" for c in CONFIGS for t in ("search", "daemon")]
#: end-to-end metrics each driver reports
E2E = {"session": ["search_evals_per_s", "setup_s"],
       "daemon": ["job_p50_s", "job_p95_s", "setup_s"]}


def config_of(name: str) -> dict:
    return json.loads((harness.HERE / "configs" / f"{name}.json")
                      .read_text())


def tiny(cell: str, config: dict = None) -> dict:
    """``<config>.<mix>`` at a tiny budget, held to the limits of the
    configuration's own cell; ``config`` stands in for its file."""
    name, traffic = cell.rsplit(".", 1)
    ctx = harness.load_parts({"name": HOME[name], "config": name,
                              "traffic": traffic, "chips": 1}, BENCH)
    ctx["cell"]["name"] = cell
    if config is not None:
        ctx["config"] = config
    mix = ctx["mix"]
    ctx["end_to_end"] = [{"name": n, "unit": "s"} for n in E2E[mix["driver"]]]
    if mix["driver"] == "session":
        mix["backend_config"] = {"preset": "fast", "generations": 12}
        mix["sample_share"] = 0.5
    else:
        mix.update(sweeps_per_s=1.0, drain_s=30, warmup_jobs=1,
                   backend_config={"preset": "fast", "generations": 4})
        ctx["config"] = dict(ctx["config"],
                             accelerators=ctx["config"]["accelerators"][::6])
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


def window(ctx: dict, seed: int = 4, seconds: float = 1.5) -> dict:
    driver = harness.DRIVERS[ctx["mix"]["driver"]](ctx)
    try:
        driver.setup()
        return driver.window(seed, seconds, harness.Tracer(False, 0, 0),
                             harness.CompileLog())
    finally:
        driver.close()


@pytest.mark.parametrize("cell", EVERY_CELL)
def test_control_fails(jax_engine, cell):
    """The float32 control, by the reference the configuration names."""
    ctx = tiny(cell)
    w = window(ctx)
    sound = check.check(w, ctx)
    assert check.verdict(sound, ctx["limits"], w["attempted"])
    ctl = check.check(check.control_answers(w, ctx["config"]), ctx)
    assert ctl.rel_gap > ctx["limits"]["rel_gap"]
    assert not check.verdict(ctl, ctx["limits"], w["attempted"])


# ---- planted faults -------------------------------------------------------
def _perturb_scores(monkeypatch, fn):
    from repro.core import population
    inner = population.PopulationEvaluator.fitness_masks

    def broken(self, masks, objective="edp"):
        return fn(np.asarray(inner(self, masks, objective)).copy())

    monkeypatch.setattr(population.PopulationEvaluator, "fitness_masks",
                        broken)


def altered_score(monkeypatch):
    """A score altered where it is produced."""
    def alter(f):
        f[0] *= 1.0 + 1e-6
        return f
    _perturb_scores(monkeypatch, alter)


def half_batch_left_out(monkeypatch):
    """Half of each batch left out, the mean of the rest put in its place."""
    def half(f):
        k = len(f) // 2
        f[k:] = f[:k].mean() if k else 0.0
        return f
    _perturb_scores(monkeypatch, half)


def search_returning_its_start(monkeypatch):
    """A search that returns its starting genome unchanged."""
    from repro.core import ga
    from repro.search import backends
    inner = ga.run_ga_problem

    def unchanged(problem, *a, **kw):
        out = inner(problem, *a, **kw)
        start = problem.initial()
        out.best_state = start
        out.best_fitness = problem.fitness(start)
        return out

    monkeypatch.setattr(ga, "run_ga_problem", unchanged)
    monkeypatch.setattr(backends, "run_ga_problem", unchanged,
                        raising=False)


def altered_cost(monkeypatch):
    """An artifact's cost altered where the cost model produces it."""
    from repro.costmodel import default
    inner = default.DefaultCostModel.cost_group

    def altered(self, key):
        bd = inner(self, key)
        if bd is not None:
            bd = dataclasses.replace(bd, energy_pj=bd.energy_pj * (1 + 1e-6))
        return bd

    monkeypatch.setattr(default.DefaultCostModel, "cost_group", altered)


def store_serving_another_artifact(monkeypatch):
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


def unanswered_job(monkeypatch):
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


#: fault -> (mix it shows in, seed, drain seconds or None)
FAULTS = {altered_score: ("search", 3, None),
          half_batch_left_out: ("search", 3, None),
          search_returning_its_start: ("search", 3, None),
          altered_cost: ("daemon", 3, None),
          store_serving_another_artifact: ("daemon", 5, None),
          unanswered_job: ("daemon", 6, 3)}


def planted_run(monkeypatch, fault, config: str) -> dict:
    """A tiny run of ``config`` under the fault's mix, the fault planted."""
    traffic, seed, drain = FAULTS[fault]
    ctx = tiny(f"{config}.{traffic}")
    if drain is not None:
        ctx["mix"]["drain_s"] = drain
    fault(monkeypatch)
    return run.run_cell(ctx, seed, 1.5, False, CPU, harness.CompileLog())


def test_altered_score_fails(jax_engine, monkeypatch):
    res = planted_run(monkeypatch, altered_score, "mobilenet_v3-simba")
    assert res["correct"] is False


def test_half_batch_left_out_fails(jax_engine, monkeypatch):
    res = planted_run(monkeypatch, half_batch_left_out, "mobilenet_v3-simba")
    assert res["correct"] is False


def test_search_returning_its_start_fails(jax_engine, monkeypatch):
    res = planted_run(monkeypatch, search_returning_its_start,
                      "mobilenet_v3-simba")
    assert res["correct"] is False


def test_altered_cost_fails(jax_engine, monkeypatch):
    res = planted_run(monkeypatch, altered_cost, "resnet50-eyeriss")
    assert res["correct"] is False


def test_store_serving_another_artifact_fails(jax_engine, monkeypatch):
    res = planted_run(monkeypatch, store_serving_another_artifact,
                      "resnet50-eyeriss")
    assert res["correct"] is False


def test_unanswered_job_fails(jax_engine, monkeypatch):
    res = planted_run(monkeypatch, unanswered_job, "resnet50-eyeriss")
    assert res["correct"] is False
    assert res["compared"]["unanswered"]["value"] >= 1


#: each fault in the configurations the tests above do not plant it in
ELSEWHERE = [(c, f) for f, (traffic, _, _) in FAULTS.items()
             for c in CONFIGS if CELLS.get(f"{c}.{traffic}") is None]


@pytest.mark.filterwarnings(
    "ignore::pytest.PytestUnhandledThreadExceptionWarning")
@pytest.mark.parametrize("config, fault", ELSEWHERE,
                         ids=[f"{c}-{f.__name__}" for c, f in ELSEWHERE])
def test_planted_fault_fails_in_every_configuration(jax_engine, monkeypatch,
                                                    config, fault):
    assert planted_run(monkeypatch, fault, config)["correct"] is False
