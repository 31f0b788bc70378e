"""The benchmark's parts on their own: discovery by name, the traffic
generator, the percentile arithmetic, the kernel byte function, the peaks
table, the trace reduction, the plain reference and the command's refusal
to run without a chip."""
from __future__ import annotations

import json
import os
import random
import shutil
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

from chipbench import harness, kernels, peaks, references, stats, trace, \
    traffic

ROOT = Path(__file__).resolve().parents[2]
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
DATA = Path(__file__).resolve().parent / "data"


# ---- discovery ------------------------------------------------------------
@pytest.mark.parametrize("cell", [w["name"] for w in BENCH["workloads"]])
def test_cell_parts_found_by_name(cell):
    ctx = harness.load_cell(cell)
    w = ctx["cell"]
    assert cell == f"{w['config']}.{w['traffic']}"
    assert ctx["config"]["name"] == w["config"]
    assert ctx["mix"]["driver"] in harness.DRIVERS
    assert set(ctx["limits"]) == {"rel_gap", "mismatches", "unanswered"}
    assert {m["name"] for m in ctx["end_to_end"]} >= {"setup_s"}
    assert len(ctx["end_to_end"]) >= 2 and ctx["per_layer"]


READERS = sorted(p.stem for p in (ROOT / "chipbench" / "metrics").glob("*.py"))


def test_every_metric_has_a_reader_file():
    assert {m["name"] for m in BENCH["per_layer"]} <= set(READERS)


@pytest.mark.parametrize("name", READERS)
def test_reader_reads_nothing_from_an_empty_run(name):
    read = harness.metric_reader(name)
    assert read({"window": {"counters": None, "jobs": [], "outcomes": {},
                            "attempted": 0, "generations": 0,
                            "window_s": 1.0, "compiles_in_window": 0},
                 "config": {}, "mix": {}}) in (None, 0)


def test_discovery_takes_a_new_cell_from_files_alone(tmp_path, monkeypatch):
    bench = json.loads(json.dumps(BENCH))
    bench["workloads"].append({"name": "mobilenet_v3-simba.daemon",
                               "config": "mobilenet_v3-simba",
                               "traffic": "daemon", "chips": 1, "why": "x"})
    path = tmp_path / "BENCHMARK.json"
    path.write_text(json.dumps(bench))
    limits = harness.HERE / "limits" / "mobilenet_v3-simba.daemon.json"
    shutil.copy(harness.HERE / "limits" / "resnet50-eyeriss.daemon.json",
                limits)
    try:
        ctx = harness.load_cell("mobilenet_v3-simba.daemon", path)
    finally:
        limits.unlink()
    assert ctx["config"]["workload"] == "mobilenet_v3"
    assert ctx["mix"]["driver"] == "daemon"


def test_benchmark_names_and_keys():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    names = [x["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in BENCH[k]]
    assert len(names) == len(set(names))
    layers = {m["layer"] for m in BENCH["per_layer"]}
    e2e = {m["name"] for m in BENCH["end_to_end"]}
    assert all(m["moves"] in e2e for m in BENCH["per_layer"])
    assert all(not any("mfu" in n for n in names) for _ in [0])
    assert layers
    for c in BENCH["configs"]:
        cfg = json.loads((ROOT / c["file"]).read_text())
        assert cfg["reduced"] == c["reduced"] == []
        assert cfg["source"] and cfg["assumed"]


# ---- traffic --------------------------------------------------------------
MIX = {"sweeps_per_s": 0.25}
ACCS = [f"eyeriss@act{d:+d}" for d in range(-64, 129, 16)]


def test_open_loop_is_a_function_of_the_seed():
    a = traffic.open_loop_jobs(MIX, ACCS, 2**31 + 11, 51)
    b = traffic.open_loop_jobs(MIX, ACCS, 2**31 + 11, 51)
    c = traffic.open_loop_jobs(MIX, ACCS, 7, 51)
    assert a == b and a != c


def test_every_seed_gets_the_same_work():
    runs = [traffic.open_loop_jobs(MIX, ACCS, s, 51)
            for s in (1, 2, 3**20, 2**40 + 3)]
    for r in runs[1:]:
        assert [(j["t"], j["accelerator"]) for j in r] == \
            [(j["t"], j["accelerator"]) for j in runs[0]]
    assert len({tuple(j["seed"] for j in r) for r in runs}) == len(runs)
    for r in runs:
        assert 0 < r[0]["t"] and r[-1]["t"] < 51
        assert all(x["t"] <= y["t"] for x, y in zip(r, r[1:]))
        assert len({(j["seed"], j["accelerator"]) for j in r}) == len(r)


def test_a_sweep_is_every_machine_once_under_one_seed():
    jobs = traffic.open_loop_jobs(MIX, ACCS, 2**31 + 77, 51)
    assert len(jobs) == round(0.25 * 51) * len(ACCS)
    sweeps = [jobs[i:i + len(ACCS)] for i in range(0, len(jobs), len(ACCS))]
    for sw in sweeps:
        assert [j["accelerator"] for j in sw] == ACCS
        assert len({j["t"] for j in sw}) == len({j["seed"] for j in sw}) == 1
        assert 0 <= sw[0]["seed"] < traffic.SEARCH_SEEDS
    assert len({sw[0]["seed"] for sw in sweeps}) == len(sweeps)


def test_closed_loop_seeds():
    seeds = traffic.closed_loop_seeds(2**31 + 5)
    assert [next(seeds) for _ in range(3)] == [2**31 + 5, 2**31 + 6,
                                               2**31 + 7]


# ---- arithmetic -----------------------------------------------------------
@pytest.mark.parametrize("q, want", [(0.5, 50), (0.95, 95), (1.0, 100),
                                     (0.01, 1)])
def test_nearest_rank(q, want):
    assert stats.nearest_rank(list(range(100, 0, -1)), q) == want


def test_nearest_rank_refuses_bad_input():
    with pytest.raises(ValueError):
        stats.nearest_rank([], 0.5)
    with pytest.raises(ValueError):
        stats.nearest_rank([1.0], 0.0)


def test_beyond_and_spread():
    xs = list(range(1, 201))
    assert stats.beyond(xs, 0.95) == 10
    assert stats.spread([1, 2, 3, 4, 5]) == pytest.approx(
        (4.5 - 1.5) / 3)


def test_kernel_bytes_from_shapes():
    # 112 genomes over 109 edges (1 byte each) -> 92 int32 labels each
    b = kernels.label_kernel_bytes(112, 92, 109, 90, 19)
    assert b == 112 * 109 + (92 + 2 * 90 + 3 * 19) * 4 + 112 * 92 * 4
    assert kernels.label_kernel_bytes(0, 92, 109, 90, 19) == \
        kernels.table_bytes(92, 90, 19)


@pytest.mark.parametrize("cfg, counts", [("mobilenet_v3-simba", (92, 109)),
                                         ("resnet50-eyeriss", (73, 88))])
def test_graph_edge_counts(cfg, counts):
    g = json.loads((harness.HERE / "configs" / f"{cfg}.json").read_text())
    n, m, chain, extra = kernels.graph_edge_counts(g["graph"]["nodes"],
                                                   g["graph"]["fields"])
    assert (n, m) == counts and chain + extra == m and extra > 0


def test_peaks_table():
    v5e = peaks.peaks("TPU v5 lite")
    assert v5e["hbm_bytes_per_s"] == 819e9 and v5e["hbm_bytes"] == 16e9
    with pytest.raises(peaks.UnknownDevice):
        peaks.peaks("TPU v9 imaginary")


# ---- trace reduction ------------------------------------------------------
def test_reduce_synthetic_events():
    dev = [{"XLA Ops": [("a", 0, 10), ("b", 5, 10), ("a", 40, 20)],
            "XLA Modules": [("jit__labels_jax(7)", 0, 15),
                            ("jit_other(3)", 40, 20)]}]
    host = [("outer", 0, 100), ("inner", 20, 10), ("late", 61, 5)]
    out = trace.reduce_events(dev, host, ["jit__labels_jax"])
    assert out["busy_s"] == pytest.approx(35e-9)
    k = out["kernels"]["jit__labels_jax"]
    assert k["calls"] == 1 and k["seconds"] == pytest.approx(15e-9)
    assert out["device_ops"][0] == ["a", pytest.approx(30e-9)]
    assert out["idle_gaps"] == [["inner", pytest.approx(25e-9)]]
    assert trace.reduce_events([{"XLA Ops": []}], host, []) is None


def test_reduce_recorded_trace():
    path = DATA / "label_kernel.xplane.pb"
    meta = json.loads((DATA / "label_kernel.json").read_text())
    out = trace.reduce_trace(str(path), ["jit__labels_jax"])
    k = out["kernels"]["jit__labels_jax"]
    assert k["calls"] == meta["kernel_calls"]
    assert k["seconds"] == pytest.approx(meta["kernel_seconds"])
    assert out["busy_s"] == pytest.approx(meta["busy_s"])
    assert 0 < out["busy_s"]
    assert out["device_ops"] and out["idle_gaps"]


# ---- the plain reference --------------------------------------------------
def _first_and_last_machines():
    for p in sorted((ROOT / "chipbench" / "configs").glob("*.json")):
        accs = json.loads(p.read_text())["accelerators"]
        for acc in dict.fromkeys((accs[0], accs[-1])):
            yield p.stem, acc


@pytest.mark.parametrize("cfg, acc", list(_first_and_last_machines()))
def test_reference_agrees_with_program(cfg, acc):
    from repro.core.fusion import FusionState
    from repro.costmodel.evaluator import Evaluator
    from repro.search.registry import build_accelerator, build_workload
    c = json.loads((harness.HERE / "configs" / f"{cfg}.json").read_text())
    graph = build_workload(c["workload"])
    ev = Evaluator(graph, build_accelerator(acc))
    reference = references.load(c)
    ref = reference.Reference(reference.Graph(c["graph"]),
                              reference.machine_for(acc, c["machines"],
                                                    c["energy_pj"]))
    rng = random.Random(9)
    m = len(ref.g.edges)
    assert m == graph.compiled().m
    legal = 0
    for _ in range(120):
        p = rng.random() * 0.4
        mask = sum(1 << i for i in range(m) if rng.random() < p)
        got = ev.evaluate(FusionState.from_mask(graph, mask))
        want = ref.schedule(mask)
        assert (got is None) == (want is None)
        if got is None:
            continue
        legal += 1
        assert got.energy_pj == pytest.approx(want["energy_pj"], rel=1e-13)
        assert got.cycles == pytest.approx(want["cycles"], rel=1e-13)
        for k in ("dram_read_words", "dram_write_words", "act_write_events",
                  "macs", "n_groups"):
            assert getattr(got, k) == want[k]
    assert legal > 5


def test_float32_reference_is_off_by_rounding():
    c = json.loads((harness.HERE / "configs" / "mobilenet_v3-simba.json")
                   .read_text())
    reference = references.load(c)
    g = reference.Graph(c["graph"])
    r64 = reference.Reference(g, reference.machine_for(
        "simba", c["machines"], c["energy_pj"]))
    r32 = reference.Reference(g, reference.machine_for(
        "simba", c["machines"], c["energy_pj"], np.float32))
    a, b = r64.baseline(), r32.baseline()
    gap = abs(float(b["energy_pj"]) - a["energy_pj"]) / a["energy_pj"]
    assert 1e-9 < gap < 1e-5
    assert isinstance(b["energy_pj"], np.float32)


def test_repartition_keeps_capacity_and_refuses_empty_buffers():
    c = json.loads((harness.HERE / "configs" / "resnet50-eyeriss.json")
                   .read_text())
    reference = references.load(c)
    m = reference.machine_for("eyeriss@act+64", c["machines"], c["energy_pj"])
    assert (m.act_buf_kib, m.weight_buf_kib) == (192, 448)
    with pytest.raises(ValueError):
        reference.machine_for("eyeriss@act-128", c["machines"],
                              c["energy_pj"])


# ---- the command ----------------------------------------------------------
def _run_cmd(cwd: Path, env_extra: dict) -> subprocess.CompletedProcess:
    env = dict(os.environ, JAX_PLATFORMS="cpu", **env_extra)
    return subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload",
         "mobilenet_v3-simba.search", "--seed", "3", "--seconds", "1",
         "--trace", "0"], cwd=cwd, env=env, capture_output=True, text=True,
        timeout=300)


def _no_result(proc) -> bool:
    lines = proc.stdout.strip().splitlines()
    return proc.returncode != 0 and not (lines and lines[-1].startswith("{"))


def test_command_without_a_chip_prints_no_result():
    proc = _run_cmd(ROOT, {})
    assert _no_result(proc), proc.stdout[-2000:]
    assert "no TPU" in proc.stderr


def test_command_without_the_program_prints_no_result(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(ROOT / "chipbench", tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    proc = _run_cmd(tmp_path, {"PYTHONPATH": ""})
    assert _no_result(proc), proc.stdout[-2000:]
