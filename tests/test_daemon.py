"""`repro.serve.daemon`: persistent queue (priorities, journal replay,
dedup), daemon lifecycle over a real socket (submit/poll/cancel,
restart-replays-journal, zero-eval store hits), warm-start pins, store GC.
"""
import json
import os
import time
import urllib.error
import urllib.request

import pytest

from repro.search import ScheduleArtifact, SearchSession, SearchSpec
from repro.serve import (ArtifactStore, ScheduleDaemon, artifact_key,
                         collect_garbage, find_warm_start)
from repro.serve.queue import JobQueue
from repro.serve.warmstart import adapt_mask, workload_family

FAST = {"preset": "fast", "generations": 4}


def fast_spec(workload="vgg16", seed=0, generations=4, **kw):
    return SearchSpec(workload=workload, seed=seed,
                      backend_config={"preset": "fast",
                                      "generations": generations}, **kw)


# ---- JobQueue ---------------------------------------------------------------------

def spec_dict(seed=0, workload="vgg16"):
    return fast_spec(workload=workload, seed=seed).to_dict()


def test_queue_priority_order(tmp_path):
    q = JobQueue(str(tmp_path))
    a = q.submit(spec_dict(seed=0), priority=0, key="ka")
    b = q.submit(spec_dict(seed=1), priority=5, key="kb")
    c = q.submit(spec_dict(seed=2), priority=1, key="kc")
    order = [q.next_job().id for _ in range(3)]
    assert order == [b.id, c.id, a.id]
    q.close()


def test_queue_ties_run_in_submission_order(tmp_path):
    q = JobQueue(str(tmp_path))
    ids = [q.submit(spec_dict(seed=i), key=f"k{i}").id for i in range(4)]
    assert [q.next_job().id for _ in range(4)] == ids
    q.close()


def test_queue_journal_replay_requeues_running_and_queued(tmp_path):
    q = JobQueue(str(tmp_path))
    a = q.submit(spec_dict(seed=0), priority=2, key="ka")
    b = q.submit(spec_dict(seed=1), priority=0, key="kb")
    started = q.next_job()
    assert started.id == a.id            # higher priority first
    q.close()                            # "crash": a was running, b queued

    q2 = JobQueue(str(tmp_path))
    assert q2.replay.jobs == 2
    assert q2.replay.requeued == 2       # running job re-runs from scratch
    assert {j.state for j in q2.list_jobs()} == {"queued"}
    # ids continue past the replayed ones
    c = q2.submit(spec_dict(seed=2), key="kc")
    assert c.id == b.id + 1
    q2.close()


def test_queue_replay_keeps_terminal_states(tmp_path):
    q = JobQueue(str(tmp_path))
    a = q.submit(spec_dict(seed=0), key="ka")
    assert q.next_job().id == a.id
    q.resolve_done(a.id, "searched", "ka")
    b = q.submit(spec_dict(seed=1), key="kb")
    assert q.cancel(b.id) == "cancelled"
    q.close()

    q2 = JobQueue(str(tmp_path))
    assert q2.get(a.id).state == "done"
    assert q2.get(a.id).outcome == "searched"
    assert q2.get(b.id).state == "cancelled"
    assert q2.replay.requeued == 0
    q2.close()


def test_queue_dedup_attaches_and_resolves_with_primary(tmp_path):
    q = JobQueue(str(tmp_path))
    a = q.submit(spec_dict(seed=0), key="same")
    b = q.submit(spec_dict(seed=0), key="same")
    assert b.attached_to == a.id
    assert q.next_job().id == a.id
    assert q.next_job(timeout=0.05) is None   # b never enters the heap
    q.resolve_done(a.id, "searched", "same")
    assert q.get(b.id).state == "done"
    assert q.get(b.id).outcome == "cache_hit"
    q.close()


def test_queue_dedup_failure_propagates(tmp_path):
    q = JobQueue(str(tmp_path))
    a = q.submit(spec_dict(seed=0), key="same")
    b = q.submit(spec_dict(seed=0), key="same")
    q.next_job()
    q.resolve_failed(a.id, "boom")
    assert q.get(b.id).state == "failed"
    assert q.get(b.id).error == "boom"
    q.close()


def test_queue_cancelled_primary_requeues_attached(tmp_path):
    q = JobQueue(str(tmp_path))
    a = q.submit(spec_dict(seed=0), key="same")
    b = q.submit(spec_dict(seed=0), key="same")
    assert q.next_job().id == a.id
    q.resolve_cancelled(a.id)
    nxt = q.next_job(timeout=1.0)
    assert nxt is not None and nxt.id == b.id  # request still stands
    q.close()


def test_queue_tolerates_torn_trailing_line(tmp_path):
    q = JobQueue(str(tmp_path))
    q.submit(spec_dict(seed=0), key="ka")
    q.close()
    with open(tmp_path / "queue.jsonl", "a") as f:
        f.write('{"v":1,"event":"sub')      # torn mid-crash write
    q2 = JobQueue(str(tmp_path))
    assert q2.replay.jobs == 1
    assert len(q2.replay.warnings) == 1
    q2.close()


def test_queue_live_keys_cover_non_terminal_jobs(tmp_path):
    q = JobQueue(str(tmp_path))
    a = q.submit(spec_dict(seed=0), key="ka")
    q.submit(spec_dict(seed=1), key="kb")
    q.next_job()
    q.resolve_done(a.id, "searched", "ka")
    assert q.live_keys() == {"kb"}
    q.close()


# ---- daemon over a real socket ----------------------------------------------------

def _get(base, path):
    with urllib.request.urlopen(base + path, timeout=30) as r:
        return json.load(r)


def _post(base, path, payload):
    req = urllib.request.Request(base + path,
                                 data=json.dumps(payload).encode())
    with urllib.request.urlopen(req, timeout=30) as r:
        return json.load(r)


def _delete(base, path):
    req = urllib.request.Request(base + path, method="DELETE")
    with urllib.request.urlopen(req, timeout=30) as r:
        return json.load(r)


def _wait(base, jid, timeout=120.0):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        j = _get(base, f"/jobs/{jid}")
        if j["state"] in ("done", "failed", "cancelled"):
            return j
        time.sleep(0.05)
    raise AssertionError(f"job {jid} did not resolve: {j}")


@pytest.fixture()
def daemon(tmp_path):
    svc = ScheduleDaemon(str(tmp_path / "store"), workers=1)
    svc.start()
    try:
        yield svc, f"http://127.0.0.1:{svc.port}"
    finally:
        svc.stop()


def test_daemon_submit_poll_artifact_metrics(daemon):
    svc, base = daemon
    assert _get(base, "/healthz") == {"ok": True}
    job = _post(base, "/jobs", {"spec": fast_spec().to_dict()})
    assert job["state"] in ("queued", "running", "done")
    done = _wait(base, job["id"])
    assert done["outcome"] == "searched"
    assert done["key"]
    # live per-generation convergence records were served
    assert len(done["progress"]) == 4
    assert done["progress"][0]["step"] == 0
    assert done["summary"]["edp_x"] > 0
    art = _get(base, f"/artifacts/{done['key']}")
    assert art["genome_mask"] is not None
    m = _get(base, "/metrics")
    assert m["jobs"]["done"] == 1
    assert m["daemon"]["searches_run"] == 1
    assert m["metrics"]["counters"]["daemon.jobs{outcome=searched}"] == 1
    assert m["metrics"]["counters"]["eval.states"] > 0


def test_daemon_store_hit_serves_with_zero_new_evaluations(daemon):
    svc, base = daemon
    first = _wait(base, _post(base, "/jobs",
                              {"spec": fast_spec().to_dict()})["id"])
    evals_before = _get(base, "/metrics")["metrics"]["counters"]["eval.states"]
    dup = _post(base, "/jobs", {"spec": fast_spec().to_dict()})
    # resolved AT submission: no queueing, no search, no evaluator
    assert dup["state"] == "done"
    assert dup["outcome"] == "cache_hit"
    assert dup["key"] == first["key"]
    m = _get(base, "/metrics")
    assert m["metrics"]["counters"]["eval.states"] == evals_before
    assert svc.searches_run == 1
    assert svc.store_hits == 1


def test_daemon_reports_queue_wait_and_run_time(daemon):
    """One worker: the second job waits for the first one's run; the
    views and the /metrics counters read the same perf_counter stamps."""
    svc, base = daemon
    ids = [_post(base, "/jobs", {"spec": fast_spec(seed=s).to_dict()})["id"]
           for s in (0, 1)]
    first, second = [_wait(base, i) for i in ids]
    for j in (first, second):
        assert j["outcome"] == "searched"
        assert j["queue_wait_s"] >= 0.0 and j["run_s"] > 0.0
    assert second["queue_wait_s"] > 0.0
    hit = _post(base, "/jobs", {"spec": fast_spec(seed=0).to_dict()})
    assert hit["outcome"] == "cache_hit"
    assert hit["queue_wait_s"] is None and hit["run_s"] is None
    m = _get(base, "/metrics")["metrics"]
    assert m["counters"]["daemon.queue_waits"] == 2
    assert m["counters"]["daemon.queue_wait_s"] == pytest.approx(
        first["queue_wait_s"] + second["queue_wait_s"])
    assert "daemon.job_wall_s" not in m["histograms"]


def test_daemon_404s(daemon):
    svc, base = daemon
    for path in ("/jobs/999", "/artifacts/" + "0" * 64, "/nope"):
        with pytest.raises(urllib.error.HTTPError) as ei:
            _get(base, path)
        assert ei.value.code == 404


def test_daemon_bad_spec_is_400(daemon):
    svc, base = daemon
    for payload in ({}, {"spec": {"workload": "no_such_net"}},
                    {"spec": {"workload": "vgg16", "bogus_field": 1}}):
        with pytest.raises(urllib.error.HTTPError) as ei:
            _post(base, "/jobs", payload)
        assert ei.value.code == 400


def test_daemon_cancel_running_job_cooperatively(daemon):
    svc, base = daemon
    # enough generations that the cancel lands mid-search
    job = _post(base, "/jobs", {"spec": fast_spec(
        workload="unet", generations=100000).to_dict()})
    deadline = time.monotonic() + 60
    while _get(base, f"/jobs/{job['id']}")["state"] != "running":
        assert time.monotonic() < deadline, "job never started"
        time.sleep(0.02)
    out = _delete(base, f"/jobs/{job['id']}")
    assert out["state"] in ("cancelling", "cancelled")
    final = _wait(base, job["id"])
    assert final["state"] == "cancelled"
    # a repeat DELETE reports the job as already resolved (409)
    with pytest.raises(urllib.error.HTTPError) as ei:
        _delete(base, f"/jobs/{job['id']}")
    assert ei.value.code == 409


def test_daemon_cancel_queued_job(tmp_path):
    svc = ScheduleDaemon(str(tmp_path / "store"), workers=0)
    svc.start()
    base = f"http://127.0.0.1:{svc.port}"
    try:
        job = _post(base, "/jobs", {"spec": fast_spec().to_dict()})
        assert job["state"] == "queued"
        assert _delete(base, f"/jobs/{job['id']}")["state"] == "cancelled"
        assert _get(base, f"/jobs/{job['id']}")["state"] == "cancelled"
    finally:
        svc.stop()


def test_daemon_restart_replays_journal(tmp_path):
    store_dir = str(tmp_path / "store")
    svc = ScheduleDaemon(store_dir, workers=0)   # nothing drains
    svc.start()
    base = f"http://127.0.0.1:{svc.port}"
    j0 = _post(base, "/jobs", {"spec": fast_spec(seed=0).to_dict(),
                               "priority": 1})
    j1 = _post(base, "/jobs", {"spec": fast_spec(seed=1).to_dict(),
                               "priority": 5})
    svc.stop()                                   # jobs still queued

    svc2 = ScheduleDaemon(store_dir, workers=1)
    assert svc2.queue.replay.requeued == 2
    svc2.start()
    base2 = f"http://127.0.0.1:{svc2.port}"
    try:
        done1 = _wait(base2, j1["id"])
        done0 = _wait(base2, j0["id"])
        assert done0["outcome"] == "searched"
        assert done1["outcome"] == "searched"
        assert svc2.searches_run == 2
    finally:
        svc2.stop()


def test_daemon_inflight_dedup_one_search_serves_both(tmp_path):
    store_dir = str(tmp_path / "store")
    svc = ScheduleDaemon(store_dir, workers=0)   # hold both in the queue
    svc.start()
    base = f"http://127.0.0.1:{svc.port}"
    ja = _post(base, "/jobs", {"spec": fast_spec().to_dict()})
    jb = _post(base, "/jobs", {"spec": fast_spec().to_dict()})
    assert not ja["deduped"]
    assert jb["deduped"]                          # attached in-flight
    svc.stop()

    svc2 = ScheduleDaemon(store_dir, workers=1)
    svc2.start()
    base2 = f"http://127.0.0.1:{svc2.port}"
    try:
        da = _wait(base2, ja["id"])
        db = _wait(base2, jb["id"])
        assert da["key"] == db["key"]
        assert svc2.searches_run == 1             # exactly one search
        assert {da["outcome"], db["outcome"]} == {"searched", "cache_hit"}
    finally:
        svc2.stop()


# ---- warm-start pins --------------------------------------------------------------

def test_daemon_default_results_bit_identical_to_direct_session(tmp_path):
    spec = fast_spec()
    direct = SearchSession(spec).run()

    svc = ScheduleDaemon(str(tmp_path / "store"), workers=1)
    svc.start()
    base = f"http://127.0.0.1:{svc.port}"
    try:
        done = _wait(base, _post(base, "/jobs",
                                 {"spec": spec.to_dict()})["id"])
        via_daemon = svc.store.load_key(done["key"])
    finally:
        svc.stop()
    # same fixed-seed trajectory, same store key, byte-identical payload
    # minus wall-clock provenance (wall_s, created_unix, and the timing
    # rates and phase seconds inside backend_stats are the only fields a
    # clock feeds); each phase ran as many times on both paths
    assert done["key"] == artifact_key(direct.graph_fingerprint, spec)
    a, b = direct.to_dict(), via_daemon.to_dict()
    for d in (a, b):
        d.pop("wall_s"), d.pop("created_unix")
        for k in ("batch_time_s", "batch_evals_per_sec"):
            d["backend_stats"].pop(k, None)
        d["backend_stats"]["phases"] = {
            name: v["calls"]
            for name, v in d["backend_stats"]["phases"].items()}
    assert a["backend_stats"]["phases"]["session.build"] == 1
    assert a == b


def test_warm_start_seeds_first_generation_at_or_above_cold(tmp_path):
    donor_spec = fast_spec(seed=0, generations=12)
    cold_spec = fast_spec(seed=7)
    cold = SearchSession(cold_spec).run()

    svc = ScheduleDaemon(str(tmp_path / "store"), workers=1)
    svc.start()
    base = f"http://127.0.0.1:{svc.port}"
    try:
        donor = _wait(base, _post(base, "/jobs",
                                  {"spec": donor_spec.to_dict()})["id"])
        warm_job = _wait(base, _post(
            base, "/jobs",
            {"spec": cold_spec.to_dict(), "warm_start": True})["id"])
        warm = svc.store.load_key(warm_job["key"])
        donor_art = svc.store.load_key(donor["key"])
    finally:
        svc.stop()
    assert warm_job["outcome"] == "searched"
    # the donor's converged winner joins the initial pool, so the warm
    # run's first generation can never be worse than it — and must be at
    # least as good as the cold run's first generation
    assert warm.history[0] >= donor_art.best_fitness - 1e-9
    assert warm.history[0] >= cold.history[0] - 1e-9
    # warm-starting never changes the request's identity
    assert warm_job["key"] == artifact_key(cold.graph_fingerprint, cold_spec)


def test_warm_start_ranking_prefers_same_fingerprint(tmp_path):
    store = ArtifactStore(str(tmp_path))
    exact = SearchSession(fast_spec(seed=0)).run()
    other = SearchSession(fast_spec(workload="unet", seed=0)).run()
    store.put(exact)
    store.put(other)
    seed = find_warm_start(store, exact.graph_fingerprint, fast_spec(seed=3))
    assert seed is not None and seed.exact
    assert seed.mask == exact.genome_mask
    # family match: same workload name, different params -> inexact donor
    fam = find_warm_start(store, "sha256:elsewhere",
                          fast_spec(workload="vgg16@hw=160", seed=0))
    assert fam is not None and not fam.exact
    assert workload_family("vgg16@hw=160") == "vgg16"
    # no donor at all for an unknown family
    assert find_warm_start(store, "sha256:x",
                           fast_spec(workload="resnet50")) is None


def test_adapt_mask_clips_to_edge_range():
    assert adapt_mask(0b1011, 2) == 0b11
    assert adapt_mask(0b1011, 8) == 0b1011
    assert adapt_mask(0b1011, 0) == 0


def test_seed_genomes_default_empty_keeps_ga_identical():
    # belt and braces on top of the byte-identity test above: the seeding
    # hook's empty default must leave run_ga_problem's draws untouched
    from repro.core.ga import GAConfig, run_ga_problem
    from repro.core.problem import FusionProblem, SearchProblem
    from repro.search.registry import build_accelerator, build_workload
    from repro.costmodel.evaluator import Evaluator

    assert SearchProblem.seed_genomes == ()
    graph = build_workload("vgg16")
    cfg = GAConfig.fast(generations=3)
    r1 = run_ga_problem(FusionProblem(
        graph, Evaluator(graph, build_accelerator("simba"))), cfg)
    p2 = FusionProblem(graph, Evaluator(graph, build_accelerator("simba")))
    p2.seed_genomes = ()                 # explicit empty == absent
    r2 = run_ga_problem(p2, cfg)
    assert r1.history == r2.history
    assert r1.best_state.mask == r2.best_state.mask
    assert r1.evaluations == r2.evaluations


# ---- store GC ---------------------------------------------------------------------

def _store_with_artifacts(root, n=4):
    store = ArtifactStore(str(root))
    keys = []
    for seed in range(n):
        art = SearchSession(fast_spec(seed=seed, generations=1)).run()
        keys.append(store.put(art))
    return store, keys


def test_gc_evicts_least_recently_used_first(tmp_path):
    store, keys = _store_with_artifacts(tmp_path, n=4)
    now = time.time()
    for i, key in enumerate(keys):       # keys[0] oldest access
        os.utime(store.path_for(key), (now - 1000 + i, now - 1000 + i))
    res = collect_garbage(store, max_objects=2, live=frozenset())
    assert res.evicted == keys[:2]
    assert sorted(store.keys()) == sorted(keys[2:])


def test_gc_never_evicts_live_keys(tmp_path):
    store, keys = _store_with_artifacts(tmp_path, n=3)
    now = time.time()
    for i, key in enumerate(keys):
        os.utime(store.path_for(key), (now - 1000 + i, now - 1000 + i))
    res = collect_garbage(store, max_objects=1, live={keys[0]})
    assert keys[0] not in res.evicted
    assert keys[0] in res.kept_live
    assert os.path.isfile(store.path_for(keys[0]))


def test_gc_respects_max_bytes(tmp_path):
    store, keys = _store_with_artifacts(tmp_path, n=3)
    sizes = {k: os.path.getsize(store.path_for(k)) for k in keys}
    budget = sizes[keys[1]] + sizes[keys[2]]
    res = collect_garbage(store, max_bytes=budget, live=frozenset())
    remaining = sum(os.path.getsize(store.path_for(k))
                    for k in store.keys())
    assert remaining <= budget
    assert res.evicted_bytes > 0


def test_gc_reports_corrupt_objects_without_deleting(tmp_path):
    store, keys = _store_with_artifacts(tmp_path, n=2)
    bad = store.path_for(keys[0])
    with open(bad, "w") as f:
        f.write("{not json")
    res = collect_garbage(store, max_objects=0, live=frozenset())
    assert keys[0] in res.corrupt
    assert os.path.isfile(bad)           # reported, not deleted
    assert keys[1] in res.evicted        # the healthy object still evicts


def test_gc_dry_run_deletes_nothing(tmp_path):
    store, keys = _store_with_artifacts(tmp_path, n=2)
    res = collect_garbage(store, max_objects=0, live=frozenset(),
                          dry_run=True)
    assert len(res.evicted) == 2
    assert sorted(store.keys()) == sorted(keys)


def test_gc_pins_keys_from_queue_journal(tmp_path):
    store, keys = _store_with_artifacts(tmp_path, n=2)
    q = JobQueue(str(tmp_path))          # journal in the store dir
    q.submit(spec_dict(seed=0), key=keys[0])
    q.close()
    res = collect_garbage(store, max_objects=0)
    assert keys[0] in res.kept_live
    assert keys[1] in res.evicted


def test_store_hit_refreshes_lru_clock(tmp_path):
    store, keys = _store_with_artifacts(tmp_path, n=1)
    art = store.load_key(keys[0])
    path = store.path_for(keys[0])
    os.utime(path, (1000.0, 1000.0))
    store.get(art.graph_fingerprint, art.spec)
    assert os.path.getmtime(path) > 1000.0
