"""`repro.obs` units: metric instruments and registry, the JSONL tracer
(schema, ambient span stack, null objects), the trace-file aggregator
(`repro trace`), the collector's window math, and the artifact-summary
renderer.  Search-level integration (bit-identity, observer ordering,
span counts against real runs) lives in tests/test_obs_search.py."""
import io
import json
import math
import os

import pytest

from repro.obs import (NULL_REGISTRY, NULL_TRACER, SCHEMA_VERSION,
                       MetricRegistry, TelemetryCollector, Tracer, clock,
                       trace_path_from_env, validate_event)
from repro.obs.collect import TRACE_ENV
from repro.obs.metrics import Counter, Gauge, Histogram, series_name
from repro.obs.report import render_telemetry
from repro.obs.traceview import read_trace


# ---- instruments ------------------------------------------------------------------

def test_counter_gauge_histogram_basics():
    c = Counter()
    c.inc()
    c.inc(4)
    assert c.snapshot() == 5
    g = Gauge()
    g.set(2)
    g.set(0.25)
    assert g.snapshot() == 0.25
    h = Histogram()
    for v in (1.0, 3.0, 0.5):
        h.observe(v)
    s = h.snapshot()
    assert s["count"] == 3 and s["total"] == 4.5
    assert s["min"] == 0.5 and s["max"] == 3.0
    assert s["mean"] == pytest.approx(1.5)


def test_histogram_buckets_are_power_of_two_magnitudes():
    h = Histogram()
    # frexp exponents: 1.0 -> 1, 2.0..3.99 -> 2, 0.5 -> 0; v <= 0 -> 0
    h.observe(1.0)
    h.observe(2.0)
    h.observe(3.0)
    h.observe(0.0)
    s = h.snapshot()
    assert s["buckets"] == {"0": 1, "1": 1, "2": 2}
    # string keys so the snapshot JSON-serializes with sort_keys
    json.dumps(s, sort_keys=True)


def test_empty_histogram_snapshot_has_no_infinities():
    s = Histogram().snapshot()
    assert s == {"count": 0, "total": 0.0, "min": 0.0, "max": 0.0,
                 "mean": 0.0, "buckets": {}}
    assert math.isfinite(s["min"]) and math.isfinite(s["max"])


def test_registry_get_or_create_and_labels():
    reg = MetricRegistry()
    assert reg.counter("a") is reg.counter("a")
    # distinct label sets are distinct series; label order is canonical
    assert reg.counter("a", x="1") is not reg.counter("a", x="2")
    assert reg.counter("b", x="1", y="2") is reg.counter("b", y="2", x="1")
    assert len(reg) == 4


def test_registry_rejects_type_conflict_on_one_series():
    reg = MetricRegistry()
    reg.counter("n")
    with pytest.raises(TypeError, match="one series, one instrument type"):
        reg.gauge("n")


def test_registry_snapshot_shape_and_series_names():
    reg = MetricRegistry()
    reg.counter("evals", engine="jax").inc(3)
    reg.gauge("rate").set(0.5)
    reg.histogram("lat").observe(2.0)
    snap = reg.snapshot()
    assert set(snap) == {"counters", "gauges", "histograms"}
    assert snap["counters"] == {"evals{engine=jax}": 3}
    assert snap["gauges"] == {"rate": 0.5}
    assert snap["histograms"]["lat"]["count"] == 1
    assert series_name("x", ()) == "x"
    assert series_name("x", (("a", "1"), ("b", "2"))) == "x{a=1,b=2}"


def test_null_registry_is_inert():
    i = NULL_REGISTRY.counter("x", any_label="y")
    i.inc()
    i.set(3.0)
    i.observe(1.0)
    assert len(NULL_REGISTRY) == 0
    assert NULL_REGISTRY.snapshot() == {"counters": {}, "gauges": {},
                                        "histograms": {}}


# ---- tracer -----------------------------------------------------------------------

def events(buf: io.StringIO):
    return [json.loads(line) for line in buf.getvalue().splitlines()]


def test_span_context_manager_nests_and_validates():
    buf = io.StringIO()
    tr = Tracer(stream=buf)
    with tr.span("outer", {"k": 1}):
        with tr.span("inner"):
            tr.point("tick", attrs={"n": 2})
    evs = events(buf)
    assert [e["name"] for e in evs] == ["tick", "inner", "outer"]
    for e in evs:
        assert validate_event(e) == []
    point, inner, outer = evs
    assert point["parent"] == inner["id"]
    assert inner["parent"] == outer["id"]
    assert outer["parent"] is None
    assert outer["attrs"] == {"k": 1}
    assert all(e["pid"] == os.getpid() for e in evs)


def test_retroactive_emit_with_preallocated_id():
    # the SearchSession generation-window pattern: allocate + push an id so
    # children nest under it while open, close it retroactively later
    buf = io.StringIO()
    tr = Tracer(stream=buf)
    gen = tr.alloc_id()
    tr.push(gen)
    tr.emit_span("child", t0=1.0, dur_s=0.5)
    tr.pop()
    tr.emit_span("gen", t0=0.0, dur_s=2.0, span_id=gen, parent=None)
    child, gen_ev = events(buf)
    assert child["parent"] == gen and gen_ev["id"] == gen
    assert validate_event(child) == [] and validate_event(gen_ev) == []


def test_tracer_pop_on_empty_stack_is_none():
    tr = Tracer(stream=io.StringIO())
    assert tr.current() is None and tr.pop() is None


def test_tracer_does_not_close_borrowed_stream():
    buf = io.StringIO()
    Tracer(stream=buf).close()
    assert not buf.closed
    with pytest.raises(ValueError, match="path or a stream"):
        Tracer()


def test_tracer_file_lines_append_and_validate(tmp_path):
    p = tmp_path / "t.jsonl"
    t1 = Tracer(str(p))
    t1.emit_span("a", t0=0.0, dur_s=0.1)
    t1.close()
    t2 = Tracer(str(p))            # append mode: earlier events survive
    t2.point("b")
    t2.close()
    lines = p.read_text().splitlines()
    assert len(lines) == 2
    assert [validate_event(json.loads(ln)) for ln in lines] == [[], []]


def test_validate_event_rejects_schema_drift():
    good = {"v": SCHEMA_VERSION, "pid": 1, "ev": "span", "name": "x",
            "id": 3, "parent": None, "t0": 0.0, "dur_s": 0.1, "attrs": {}}
    assert validate_event(good) == []
    assert validate_event("nope") == ["event is not a JSON object"]
    assert any("v=" in e for e in validate_event({**good, "v": 99}))
    assert any("unknown keys" in e
               for e in validate_event({**good, "rogue": 1}))
    assert any("ev=" in e for e in validate_event({**good, "ev": "blip"}))
    assert validate_event({**good, "dur_s": -1.0})
    assert validate_event({**good, "pid": True})
    assert validate_event({**good, "parent": 0})
    point = {"v": SCHEMA_VERSION, "pid": 1, "ev": "point", "name": "p",
             "parent": None, "ts": 1.0, "attrs": {}}
    assert validate_event(point) == []
    assert any("unknown keys" in e
               for e in validate_event({**point, "dur_s": 0.1}))


def test_null_tracer_is_inert():
    assert NULL_TRACER.enabled is False
    with NULL_TRACER.span("x") as sid:
        assert sid is None
    assert NULL_TRACER.emit_span("x") == 0
    assert NULL_TRACER.alloc_id() == 0
    NULL_TRACER.point("x")
    NULL_TRACER.push(1)
    assert NULL_TRACER.pop() is None and NULL_TRACER.current() is None
    NULL_TRACER.close()


def test_clock_seam_surface():
    assert isinstance(clock.unix_time(), int)
    a = clock.perf_counter()
    assert clock.perf_counter() >= a
    assert clock.now() > 1_600_000_000.0   # wall clock, seconds since epoch


# ---- trace aggregation (repro trace) ----------------------------------------------

def test_read_trace_tree_slowest_and_metrics(tmp_path):
    p = tmp_path / "t.jsonl"
    tr = Tracer(str(p))
    with tr.span("search"):
        for dur in (0.2, 0.4):
            with tr.span("generation"):
                tr.emit_span("batch_eval", t0=0.0, dur_s=dur)
        snap = {"counters": {"eval.states": 10}, "gauges": {},
                "histograms": {"eval.batch_s": Histogram().snapshot()}}
        tr.point("metrics.snapshot", attrs=snap)
    tr.close()
    rep = read_trace(str(p), top=2)
    assert rep.valid and rep.n_events == 6
    assert rep.span_counts == {"search": 1, "generation": 2, "batch_eval": 2}
    paths = {row["path"]: row for row in rep.tree}
    assert paths["search/generation/batch_eval"]["count"] == 2
    assert paths["search/generation/batch_eval"]["max_s"] == 0.4
    assert len(rep.slowest) == 2
    assert rep.slowest[0]["dur_s"] == pytest.approx(0.4)
    assert rep.point_counts == {"metrics.snapshot": 1}
    assert rep.metrics["counters"] == {"eval.states": 10}
    # the JSON the CLI --json mode prints round-trips
    d = json.loads(json.dumps(rep.to_dict()))
    assert d["valid"] and d["span_counts"]["generation"] == 2


def test_read_trace_merges_snapshots_across_processes(tmp_path):
    # forked island workers each emit their own metrics.snapshot point;
    # counters sum, gauges last-wins, histograms combine
    p = tmp_path / "t.jsonl"
    tr = Tracer(str(p))
    h1, h2 = Histogram(), Histogram()
    h1.observe(1.0)
    h2.observe(4.0)
    tr.point("metrics.snapshot", attrs={
        "counters": {"eval.states": 3}, "gauges": {"g": 1.0},
        "histograms": {"h": h1.snapshot()}})
    tr.point("metrics.snapshot", attrs={
        "counters": {"eval.states": 5}, "gauges": {"g": 2.0},
        "histograms": {"h": h2.snapshot()}})
    tr.close()
    rep = read_trace(str(p))
    assert rep.metrics["counters"]["eval.states"] == 8
    assert rep.metrics["gauges"]["g"] == 2.0
    h = rep.metrics["histograms"]["h"]
    assert h["count"] == 2 and h["min"] == 1.0 and h["max"] == 4.0
    assert h["mean"] == pytest.approx(2.5)


def test_read_trace_invalid_lines_fail_validity_but_still_aggregate(tmp_path):
    p = tmp_path / "t.jsonl"
    tr = Tracer(str(p))
    tr.emit_span("ok", t0=0.0, dur_s=0.1)
    tr.close()
    with open(p, "a") as f:
        f.write("not json at all\n")
        f.write(json.dumps({"v": 99, "pid": 1, "ev": "span"}) + "\n")
        f.write("\n")                       # blank lines are skipped
    rep = read_trace(str(p))
    assert not rep.valid and len(rep.errors) == 2
    assert rep.n_events == 1 and rep.span_counts == {"ok": 1}
    assert "INVALID" in rep.describe()


def test_read_trace_orphan_parent_roots_at_own_name(tmp_path):
    # a forked worker's child span can outlive a parent window that is
    # discarded unemitted — it must root at its own name, not crash
    p = tmp_path / "t.jsonl"
    tr = Tracer(str(p))
    tr.emit_span("batch_eval", t0=0.0, dur_s=0.1, parent=12345)
    tr.close()
    rep = read_trace(str(p))
    assert rep.valid
    assert rep.tree[0]["path"] == "batch_eval"


# ---- collector --------------------------------------------------------------------

class FakeEvaluator:
    group_hits = 0
    group_misses = 0


def test_collector_window_math_and_generation_records():
    col = TelemetryCollector()                      # metrics only, no tracer
    ev = FakeEvaluator()
    col.bind_evaluator(ev)
    col.begin_search({"workload": "w"})
    col.record_batch(4, 3, [2.0, 0.0, 1.0, 1.0], "numpy", 0.0, 0.01, 2)
    ev.group_hits, ev.group_misses = 6, 2
    col.on_step(0, best=2.0, evals=3, offspring=4)
    assert len(col.generations) == 1
    rec = col.generations[0]
    assert rec["batch_states"] == 4 and rec["batch_unique"] == 3
    assert rec["rejection_rate"] == pytest.approx(0.25)
    assert rec["mean"] == pytest.approx(1.0)
    assert rec["std"] == pytest.approx(math.sqrt(0.5))
    assert rec["group_hit_rate"] == pytest.approx(6 / 8)
    assert rec["novel_groups"] == 2
    # the window drained: an empty next tick records zeros, not stale sums
    col.on_step(1, best=2.0, evals=3, offspring=4)
    assert col.generations[1]["batch_states"] == 0
    assert col.generations[1]["mean"] == 0.0
    snap = col.registry.snapshot()
    assert snap["counters"]["eval.states"] == 4
    assert snap["counters"]["eval.invalid"] == 1
    assert snap["counters"]["eval.batches_by_engine{engine=numpy}"] == 1
    s = col.summary({"group_hit_rate": 0.75})
    assert s["schema"] == 1 and s["steps"] == 2
    assert s["best"] == [2.0, 2.0]
    assert s["rejection_rate"] == [0.25, 0.0]
    assert s["cache"]["group_hit_rate"] == 0.75
    json.dumps(s, sort_keys=True)                   # artifact-embeddable


def test_collector_span_scaffolding_counts_generations():
    buf = io.StringIO()
    col = TelemetryCollector(tracer=Tracer(stream=buf))
    col.bind_evaluator(FakeEvaluator())
    col.begin_search({"workload": "w", "seed": 0})
    col.note_group_costed(0.25)
    col.record_batch(2, 2, [1.0, 1.5], "scalar", 0.0, 0.01, 1)
    col.on_step(0, best=1.5, evals=2, offspring=2)
    col.record_batch(2, 1, [1.5], "scalar", 0.0, 0.01, 0)
    col.on_step(1, best=1.5, evals=3, offspring=4)
    col.end_search({"unique_groups": 3})
    evs = events(buf)
    assert all(validate_event(e) == [] for e in evs)
    by_name = {}
    for e in evs:
        by_name.setdefault(e["name"], []).append(e)
    # exactly one generation span per tick; the dangling post-final window
    # is discarded unemitted
    assert len(by_name["generation"]) == 2
    search = by_name["search"][0]
    assert search["attrs"]["steps"] == 2
    assert search["attrs"]["cache"] == {"unique_groups": 3}
    assert all(g["parent"] == search["id"] for g in by_name["generation"])
    gen_ids = {g["id"] for g in by_name["generation"]}
    assert all(b["parent"] in gen_ids for b in by_name["batch_eval"])
    # novel-group costing time is an attribute of its batch span, drained
    # per batch (no span of its own: its start was never known)
    assert "costmodel" not in by_name
    assert [b["attrs"]["cost_s"] for b in by_name["batch_eval"]] == [0.25,
                                                                   0.0]
    assert by_name["metrics.snapshot"][0]["parent"] == search["id"]


def test_collector_from_env(monkeypatch, tmp_path):
    monkeypatch.delenv(TRACE_ENV, raising=False)
    assert trace_path_from_env() is None
    assert TelemetryCollector.from_env() is None
    monkeypatch.setenv(TRACE_ENV, "")               # empty means unset
    assert TelemetryCollector.from_env() is None
    p = tmp_path / "env.jsonl"
    monkeypatch.setenv(TRACE_ENV, str(p))
    col = TelemetryCollector.from_env()
    assert col is not None and col.tracer.enabled
    col.tracer.point("hello")
    col.close()
    assert validate_event(json.loads(p.read_text())) == []


def test_collector_migration_and_certificate_hooks():
    buf = io.StringIO()
    col = TelemetryCollector(tracer=Tracer(stream=buf))
    col.record_migration(2, best=1.2, islands=4, migration=False)
    col.record_migration(3, best=1.3, islands=4, migration=True)
    snap = col.registry.snapshot()
    assert snap["counters"]["island.barriers"] == 2
    assert snap["counters"]["island.migrations"] == 1

    class Cert:
        traffic_words = 100
        schedule_lb_words = 80
        graph_lb_words = 60
        gap_vs_schedule = 0.25
        gap_vs_graph = 0.666667

    col.record_certificate("sha256:ab", Cert(), ok=True)
    snap = col.registry.snapshot()
    assert snap["counters"]["verify.artifacts{ok=true}"] == 1
    evs = events(buf)
    names = [e["name"] for e in evs]
    assert names.count("island.migration") == 1     # barriers are not points
    cert_ev = [e for e in evs if e["name"] == "verify.certificate"][0]
    assert cert_ev["attrs"]["gap_vs_schedule"] == 0.25
    assert all(validate_event(e) == [] for e in evs)


# ---- renderer ---------------------------------------------------------------------

def make_summary(n=6):
    return {
        "schema": 1, "steps": n,
        "best": [1.0 + 0.1 * i for i in range(n)],
        "mean": [0.8 + 0.1 * i for i in range(n)],
        "std": [0.1] * n,
        "rejection_rate": [0.5 / (i + 1) for i in range(n)],
        "group_hit_rate": [i / n for i in range(n)],
        "unique_states": [10 * (i + 1) for i in range(n)],
        "offspring": [12 * (i + 1) for i in range(n)],
        "cache": {"group_hit_rate": 0.9, "unique_groups": 42,
                  "pop_backend": "numpy", "batch_evals_per_sec": 5000.0},
        "metrics": {"counters": {"eval.states": 60, "eval.invalid": 9}},
    }


def test_render_telemetry_curve_cache_and_rejection_lines():
    out = render_telemetry(make_summary())
    assert "6 steps, best 1.0000 -> 1.5000" in out
    assert "60 unique states" in out
    assert "unique_groups 42" in out and "engine numpy" in out
    assert "9 of 60 scored states were unschedulable (15.0%)" in out
    assert out.count("|#") == 6                     # one bar row per step


def test_render_telemetry_downsamples_long_runs_keeping_endpoints():
    out = render_telemetry(make_summary(n=200))
    rows = [ln for ln in out.splitlines() if "|" in ln]
    assert len(rows) == 20
    assert "     0  " in rows[0] and "   199  " in rows[-1]


def test_render_telemetry_empty_summary():
    out = render_telemetry({"schema": 1, "steps": 0, "best": []})
    assert "no per-generation records" in out
