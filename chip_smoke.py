"""Run the schedule-search service once on one TPU chip and check its output.

    python chip_smoke.py

Everything runs in this one process, because a chip belongs to one process
at a time.  The population engine is the jax one (``REPRO_POP_ENGINE=jax``,
set here before ``repro`` is imported), so the GA's population scoring runs
its label kernel on the chip.  Phases:

1. **device**: JAX's first device must be a TPU.  Anything else exits
   non-zero; there is no CPU branch.
2. **search**: ``mobilenet_v3`` on ``simba``, backend ``ga`` at the paper's
   budget (P=100, G=500), seed 0, through ``SearchSession``.  The engine
   must be jax with its labels computed on the chip; the winner mask, best
   fitness and history must be bit-identical to the same spec run with the
   numpy engine; and ``repro.analysis.verify`` must accept the artifact.
3. **service**: a ``ScheduleDaemon`` (threads, two workers) on a loopback
   port answers four jobs over HTTP: mobilenet_v3/simba (``fast``),
   resnet50/simba2x2, a duplicate of the first, and a 2-island job.  All
   must end ``done``, the duplicate as a cache hit, every stored artifact
   must verify, and no batch may have been scored by the numpy engine.

Any failed check raises, and the script exits non-zero without printing
the last line.  On success the last line of standard output is::

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

The compile cache follows ``JAX_COMPILATION_CACHE_DIR`` when it is set,
else ``<repo>/.jax_cache``; each process reports its compiles and cache
hits.
"""
from __future__ import annotations

import json
import os
import sys
import tempfile
import time
import urllib.request
from pathlib import Path

HERE = Path(__file__).resolve().parent
SRC = HERE / "src"

#: the service phase's job mix: (workload, accelerator, backend, extra config)
SERVICE_JOBS = (
    ("mobilenet_v3", "simba", "ga", {}),
    ("resnet50", "simba2x2", "ga", {}),
    ("mobilenet_v3", "simba", "ga", {}),          # duplicate of job 0
    ("unet", "simba", "island", {"islands": 2}),
)
_BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
_CACHE_HIT = "/jax/compilation_cache/cache_hits"


class SmokeError(AssertionError):
    """A check of the smoke run failed."""


def _check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeError(what)


class CompileLog:
    """Counts this process's XLA compiles and persistent-cache hits through
    ``jax.monitoring`` (a cache hit also reports a compile duration)."""

    def __init__(self):
        import jax.monitoring as mon
        self.durations = []
        self.cache_hits = 0
        mon.register_event_duration_secs_listener(self._on_duration)
        mon.register_event_listener(self._on_event)

    def _on_duration(self, event, secs, **_):
        if event == _BACKEND_COMPILE:
            self.durations.append(secs)

    def _on_event(self, event, **_):
        if event == _CACHE_HIT:
            self.cache_hits += 1

    def summary(self) -> dict:
        return {"requests": len(self.durations),
                "cache_hits": self.cache_hits,
                "compiled": len(self.durations) - self.cache_hits,
                "seconds": [round(s, 4) for s in self.durations]}


# ---- phases --------------------------------------------------------------------
def device_phase():
    """JAX's first device, which must be a TPU."""
    import jax
    devs = jax.devices()
    dev = devs[0]
    print(f"device: platform={dev.platform} kind={dev.device_kind} "
          f"count={len(devs)}", flush=True)
    _check(dev.platform == "tpu",
           f"JAX found no TPU (first device: {dev.platform}); this check "
           f"runs on the chip only")
    return dev


def search_phase(device, backend_config=None) -> dict:
    """One ga search on the jax engine, checked against the numpy engine
    and the independent verifier.  ``device`` is where the engine's labels
    must have been computed."""
    from repro.analysis.verify import verify_artifact
    from repro.search import SearchSession, SearchSpec

    spec = SearchSpec(workload="mobilenet_v3", accelerator="simba",
                      backend="ga", seed=0,
                      backend_config=backend_config or {"preset": "paper"})
    jx = SearchSession(spec)
    t0 = time.perf_counter()
    artifact = jx.run()
    wall_s = time.perf_counter() - t0
    stats = jx.evaluator.population().stats()
    _check(stats["backend"] == "jax", f"engine is {stats['backend']!r}")
    _check(stats["batches"] > 0, "the jax engine scored no batch")
    _check((stats["device_platform"], stats["device_kind"]) ==
           (device.platform, device.device_kind),
           f"labels lived on {stats['device_platform']} "
           f"{stats['device_kind']}, not {device.platform} "
           f"{device.device_kind}")

    ref = SearchSession(spec)
    ref.evaluator.population(backend="numpy")
    t0 = time.perf_counter()
    ref.run()
    numpy_wall_s = time.perf_counter() - t0
    _check(ref.evaluator.population().stats()["backend"] == "numpy",
           "reference run did not use the numpy engine")
    a, b = jx.result, ref.result
    _check(a.best_state.mask == b.best_state.mask,
           f"winner {a.best_state.mask:#x} != numpy {b.best_state.mask:#x}")
    _check(a.best_fitness == b.best_fitness,
           f"best fitness {a.best_fitness!r} != numpy {b.best_fitness!r}")
    _check(a.history == b.history, "fitness history differs from numpy")

    report = verify_artifact(artifact)
    _check(report.ok, f"artifact rejected: {report.describe()}")
    return {"wall_s": wall_s, "numpy_wall_s": numpy_wall_s,
            "generations": len(a.history), "best_fitness": a.best_fitness,
            "winner_mask": f"{a.best_state.mask:#x}", "engine": stats}


def _http(base: str, path: str, body=None) -> dict:
    data = None if body is None else json.dumps(body).encode()
    req = urllib.request.Request(base + path, data=data,
                                 headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=30) as resp:
        return json.load(resp)


def service_phase(generations=None, timeout_s: float = 900.0) -> dict:
    """Four jobs through an in-process daemon over loopback HTTP."""
    from repro.analysis.verify import verify_store
    from repro.serve import ScheduleDaemon

    cfg = {"preset": "fast"}
    if generations is not None:
        cfg["generations"] = generations
    with tempfile.TemporaryDirectory(prefix="chip-smoke-") as store:
        svc = ScheduleDaemon(store, port=0, workers=2)
        svc.start()
        try:
            base = f"http://{svc.host}:{svc.port}"
            ids = []
            for workload, accel, backend, extra in SERVICE_JOBS:
                spec = {"workload": workload, "accelerator": accel,
                        "backend": backend, "seed": 0,
                        "backend_config": {**cfg, **extra}}
                ids.append(_http(base, "/jobs", {"spec": spec})["id"])
            t0 = time.perf_counter()
            while True:
                jobs = [_http(base, f"/jobs/{i}") for i in ids]
                if all(j["state"] in ("done", "failed", "cancelled")
                       for j in jobs):
                    break
                _check(time.perf_counter() - t0 < timeout_s,
                       f"jobs not finished after {timeout_s} s: "
                       f"{[j['state'] for j in jobs]}")
                time.sleep(0.2)
            wall_s = time.perf_counter() - t0
            metrics = _http(base, "/metrics")
        finally:
            svc.stop()
        reports = verify_store(store)

    for j in jobs:
        _check(j["state"] == "done",
               f"job {j['id']} ended {j['state']}: {j.get('error')}")
    _check(jobs[2]["outcome"] == "cache_hit",
           f"duplicate job resolved as {jobs[2]['outcome']!r}")
    _check(len(reports) == len({j["key"] for j in jobs}),
           f"{len(reports)} stored artifacts for "
           f"{len({j['key'] for j in jobs})} distinct jobs")
    for key, rep in reports:
        _check(rep.ok, f"stored artifact {key[:12]} rejected: "
                       f"{rep.describe()}")
    counters = metrics["metrics"]["counters"]
    by_engine = {k: v for k, v in counters.items()
                 if k.startswith("eval.batches_by_engine")}
    _check(by_engine.get("eval.batches_by_engine{engine=jax}", 0) > 0,
           f"no batch scored by the jax engine: {by_engine}")
    _check(by_engine.get("eval.batches_by_engine{engine=numpy}", 0) == 0,
           f"batches scored by the numpy engine: {by_engine}")
    return {"wall_s": wall_s,
            "jobs": [(j["id"], j["spec"]["workload"], j["spec"]["backend"],
                      j["state"], j["outcome"], j["deduped"]) for j in jobs],
            "verified": len(reports), "batches_by_engine": by_engine}


# ---- driver --------------------------------------------------------------------
def main() -> int:
    t_start = time.perf_counter()
    os.environ["REPRO_POP_ENGINE"] = "jax"
    sys.path.insert(0, str(SRC))
    import repro
    _check(Path(repro.__file__).resolve().is_relative_to(SRC),
           f"repro was imported from {repro.__file__}, not from {SRC}")
    from repro.core.population import enable_compile_cache
    enable_compile_cache()
    import jax
    print(f"compile cache: {jax.config.jax_compilation_cache_dir}",
          flush=True)
    compiles = CompileLog()

    dev = device_phase()
    count = len(jax.devices())
    found = search_phase(dev)
    print(f"search: mobilenet_v3/simba ga paper seed 0: "
          f"{found['generations']} generations, jax wall "
          f"{found['wall_s']} s, numpy wall {found['numpy_wall_s']} s",
          flush=True)
    print(f"search: best fitness {found['best_fitness']!r} winner "
          f"{found['winner_mask']} identical to numpy; artifact verified",
          flush=True)
    print(f"search: engine {json.dumps(found['engine'], sort_keys=True)}",
          flush=True)
    print(f"search: compiles {json.dumps(compiles.summary())}", flush=True)

    served = service_phase()
    print(f"service: wall {served['wall_s']} s, "
          f"{served['verified']} stored artifacts verified", flush=True)
    for job in served["jobs"]:
        print(f"service: job {json.dumps(job)}", flush=True)
    print(f"service: {json.dumps(served['batches_by_engine'], sort_keys=True)}",
          flush=True)
    print(f"process: compiles {json.dumps(compiles.summary())}, wall "
          f"{time.perf_counter() - t_start} s", flush=True)
    print(json.dumps({"ok": True, "device": {"platform": dev.platform,
                                             "kind": dev.device_kind,
                                             "count": count}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
