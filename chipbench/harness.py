"""The benchmark harness: one run of one cell.

A cell is ``<config>.<mix>`` in ``BENCHMARK.json``.  Its parts are found by
name: ``configs/<config>.json`` (the deployment and its layer table),
``mixes/<mix>.json`` (the traffic's parameters and the driver that offers
it), ``limits/<cell>.json`` (the limits of the numbers compared),
``metrics/<metric>.py`` (one reader per per-layer metric) and the plain
reference the configuration names (``references/__init__.py``).

A run is: device check, set-up (start the system, warm up every shape the
traffic uses through the cell's own entry), the measured window, the drain
of answers due in it, then the comparison with the plain reference
(``check.py``).  The program is reached only through its entries:
``SearchSession``/``SearchSpec``, ``ScheduleDaemon`` over HTTP and
``Evaluator.population().stats()``.
"""
from __future__ import annotations

import http.client
import importlib.util
import json
import os
import random
import shutil
import subprocess
import sys
import tempfile
import time
from pathlib import Path
from typing import Callable, Dict, List, Optional

from chipbench import references
from chipbench.traffic import closed_loop_seeds, open_loop_jobs

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent
#: JAX's persistent compilation cache: a fixed directory of the checkout
CACHE_DIR = ROOT / ".jax_cache"
#: the label kernel's compiled module, as the profiler names it
LABEL_KERNEL = "jit__labels_jax"
#: warm-up searches use seeds the traffic never draws
WARMUP_SEED = 1 << 62

_BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
_CACHE_HIT = "/jax/compilation_cache/cache_hits"


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def now() -> float:
    return time.monotonic()


# ---- cell discovery -------------------------------------------------------
def load_cell(name: str, bench_path: Path = ROOT / "BENCHMARK.json") -> Dict:
    """The cell's entry, configuration, mix, limits and metrics, found by
    name in ``BENCHMARK.json``."""
    bench = json.loads(Path(bench_path).read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r}; known: {sorted(cells)}")
    return load_parts(cells[name], bench)


def load_parts(cell: Dict, bench: Optional[Dict] = None) -> Dict:
    """A cell's files, found by the names in its entry; its metrics are
    those of ``bench`` that name it (none without ``bench``).  A reference
    the configuration names but nothing provides fails here, before
    set-up."""
    name = cell["name"]
    config = json.loads((HERE / "configs" / f"{cell['config']}.json")
                        .read_text())
    references.load(config)
    mix = json.loads((HERE / "mixes" / f"{cell['traffic']}.json").read_text())
    limits = json.loads((HERE / "limits" / f"{name}.json").read_text())
    bench = bench or {"per_layer": [], "end_to_end": []}
    metrics = [m for m in bench["per_layer"]
               if name in m.get("workloads", [name])]
    e2e = [m for m in bench["end_to_end"]
           if name in m.get("workloads", [name])]
    return {"cell": cell, "config": config, "mix": mix, "limits": limits,
            "per_layer": metrics, "end_to_end": e2e}


def metric_reader(name: str) -> Callable[[Dict], Optional[float]]:
    """``read(record)`` of ``metrics/<name>.py``."""
    path = HERE / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        f"chipbench_metric_{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


# ---- process set-up -------------------------------------------------------
def prepare_program() -> None:
    """Ask for the chip's scoring path and put the program on the path.
    Must run before ``repro`` is imported."""
    os.environ["REPRO_POP_ENGINE"] = "jax"
    src = str(ROOT / "src")
    if src not in sys.path:
        sys.path.insert(0, src)


def enable_cache() -> None:
    import jax
    CACHE_DIR.mkdir(exist_ok=True)      # jax writes no entry into a missing
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)


class CompileLog:
    """This process's XLA compile requests and persistent-cache hits, from
    ``jax.monitoring`` (a cache hit also reports a compile duration)."""

    def __init__(self):
        import jax.monitoring as mon
        self.requests = 0
        self.cache_hits = 0
        self.seconds = 0.0
        mon.register_event_duration_secs_listener(self._on_duration)
        mon.register_event_listener(self._on_event)

    def _on_duration(self, event, secs, **_):
        if event == _BACKEND_COMPILE:
            self.requests += 1
            self.seconds += secs

    def _on_event(self, event, **_):
        if event == _CACHE_HIT:
            self.cache_hits += 1

    def summary(self) -> Dict:
        return {"requests": self.requests, "cache_hits": self.cache_hits,
                "compiled": self.requests - self.cache_hits,
                "seconds": self.seconds}


def check_device(chips: int, platform: str = "tpu") -> Dict:
    """JAX's devices: the first must be a TPU and there must be enough."""
    import jax
    devs = jax.devices()
    dev = devs[0]
    log(f"device: platform={dev.platform} kind={dev.device_kind} "
        f"count={len(devs)}")
    if dev.platform != platform:
        raise NoChip(f"JAX found no TPU (first device: {dev.platform})")
    if len(devs) < chips:
        raise NoChip(f"the cell asks for {chips} chips; JAX found "
                     f"{len(devs)}")
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": chips}


def memory_peak() -> Optional[int]:
    import jax
    peaks = []
    for d in jax.devices():
        stats = d.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            peaks.append(int(stats["peak_bytes_in_use"]))
    return max(peaks) if peaks else None


class Tracer:
    """The profiler over a sub-window of the measured window."""

    def __init__(self, on: bool, offset: float, length: float):
        self.on = on
        self.offset, self.length = offset, length
        self.start_at = self.stop_at = float("inf")
        self.dir = tempfile.mkdtemp(prefix="chipbench-trace-") if on else None
        self.state = "idle"
        self.t_start = self.t_stop = None
        self.at_start = self.at_stop = None
        self.overhead_s = 0.0           # inside start_trace / stop_trace

    def arm(self, t0: float) -> None:
        """Place the sub-window: ``offset`` seconds after the window opens
        at ``t0``, for ``length`` seconds."""
        self.start_at = t0 + self.offset
        self.stop_at = self.start_at + self.length

    def tick(self, t: float, snapshot: Callable[[], Dict]) -> None:
        """Start or stop the profiler when ``t`` has passed its time;
        ``snapshot`` reads the engine counters at that instant."""
        if not self.on:
            return
        import jax
        if self.state == "idle" and t >= self.start_at:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            self.at_start = snapshot()
            jax.profiler.start_trace(self.dir, profiler_options=opts)
            self.t_start = now()
            self.overhead_s += self.t_start - t
            self.state = "tracing"
        elif self.state == "tracing" and t >= self.stop_at:
            self.t_stop = now()
            self.at_stop = snapshot()
            jax.profiler.stop_trace()
            self.overhead_s += now() - t
            self.state = "done"

    def finish(self) -> None:
        if self.state == "tracing":
            import jax
            self.t_stop = now()
            jax.profiler.stop_trace()
            self.state = "done"

    def reduce(self) -> Optional[Dict]:
        if self.state != "done":
            return None
        from chipbench.trace import find_xplane, reduce_trace
        out = reduce_trace(find_xplane(self.dir), [LABEL_KERNEL])
        if out is not None:
            out["window_s"] = self.t_stop - self.t_start
            out["counters_start"] = self.at_start
            out["counters_stop"] = self.at_stop
        return out

    def close(self) -> None:
        if self.dir:
            shutil.rmtree(self.dir, ignore_errors=True)


def job_spec(config: Dict, mix: Dict, accelerator: str, seed: int) -> Dict:
    """The ``SearchSpec`` fields of one request of a cell."""
    return {"workload": config["workload"], "accelerator": accelerator,
            "objective": config["objective"], "backend": config["backend"],
            "costmodel": config["costmodel"],
            "backend_config": dict(mix["backend_config"]), "seed": seed}


# ---- closed loop over search sessions -------------------------------------
class WindowClosed(Exception):
    """Raised from the progress hook once the window has closed."""


class BatchSampler:
    """Keeps a seed-drawn sample of the batches a search scores: the
    genomes (as the problem encodes them) and the fitness returned.

    Wraps the problem's batch scorer, the call the GA loop makes; with
    probability ``share`` a batch is kept."""

    def __init__(self, problem, share: float, rng: random.Random):
        self.name = ("fitness_batch_unique"
                     if hasattr(problem, "fitness_batch_unique")
                     else "fitness_batch")
        self.inner = getattr(problem, self.name)
        self.encode = problem.encode_genome
        self.share = share
        self.rng = rng
        self.kept: List[tuple] = []
        setattr(problem, self.name, self)

    def __call__(self, states, *args, **kwargs):
        out = self.inner(states, *args, **kwargs)
        if self.rng.random() < self.share:
            self.kept.append((states, out))
        return out

    def samples(self) -> List[tuple]:
        return [([self.encode(s) for s in states], [float(f) for f in fits])
                for states, fits in self.kept]


class SessionDriver:
    """Back-to-back searches, one client: each search is a fresh
    ``SearchSession``, as each daemon miss is."""

    def __init__(self, ctx: Dict):
        self.ctx = ctx
        self.config, self.mix = ctx["config"], ctx["mix"]
        self.accelerator = self.config["accelerators"][0]

    def _session(self, spec: Dict):
        from repro.search import SearchSession, SearchSpec
        return SearchSession(SearchSpec.from_dict(spec))

    def close(self) -> None:
        """Nothing outlives a search session."""

    def setup(self) -> None:
        for i in range(self.mix["warmup_searches"]):
            self._session(job_spec(self.config, self.mix, self.accelerator,
                                   WARMUP_SEED + i)).run()

    def window(self, seed: int, seconds: float, tracer: Tracer,
               compiles: CompileLog) -> Dict:
        done_stats = {"batches": 0, "batch_time_s": 0.0, "states_scored": 0}
        live = {"session": None}

        def counters() -> Dict:
            out = dict(done_stats)
            if live["session"] is not None:
                st = live["session"].evaluator.population().stats()
                for k in out:
                    out[k] += st[k]
            return out

        searches, offspring, generations, engines = [], 0, 0, {}
        sampler_rng = random.Random(f"batch-sample:{seed}")
        seeds = closed_loop_seeds(seed)
        c0 = compiles.requests
        t0 = now()
        end = t0 + seconds
        tracer.arm(t0)
        while now() < end:
            rec = {"spec": job_spec(self.config, self.mix, self.accelerator,
                                    next(seeds)),
                   "artifact": None, "error": None,
                   "offspring": 0, "cut": False}
            searches.append(rec)
            try:
                sess = self._session(rec["spec"])
            except Exception as e:           # noqa: BLE001 — counted failed
                rec["error"] = f"{type(e).__name__}: {e}"
                continue
            live["session"] = sess
            rec["sampler"] = BatchSampler(sess.problem,
                                          self.mix["sample_share"],
                                          sampler_rng)

            def progress(p, rec=rec):
                t = now()
                if t >= end:
                    raise WindowClosed()
                rec["offspring"] = p.offspring_evaluated
                rec["generations"] = p.step + 1
                tracer.tick(t, counters)

            try:
                rec["artifact"] = sess.run(progress=progress)
            except WindowClosed:
                rec["cut"] = True
            except Exception as e:           # noqa: BLE001 — counted failed
                rec["error"] = f"{type(e).__name__}: {e}"
            st = sess.evaluator.population().stats()
            engines[st["backend"]] = engines.get(st["backend"], 0) \
                + st["batches"]
            for key in done_stats:
                done_stats[key] += st[key]
            live["session"] = None
            offspring += rec["offspring"]
            generations += rec.get("generations", 0)
        t1 = now()
        tracer.finish()
        for rec in searches:               # answers, read after the window
            if rec["artifact"] is not None:
                rec["artifact"] = rec["artifact"].to_dict()
            sampler = rec.pop("sampler", None)
            rec["samples"] = sampler.samples() if sampler else []
        return {"t0": t0, "window_s": seconds, "elapsed_s": t1 - t0,
                "trace_overhead_s": tracer.overhead_s,
                "searches": searches, "offspring": offspring,
                "generations": generations, "counters": done_stats,
                "engines": engines,
                "compiles_in_window": compiles.requests - c0,
                "attempted": len(searches)}


# ---- open loop over the daemon --------------------------------------------
def _http(base_host: str, port: int, method: str, path: str,
          body: Optional[Dict] = None, timeout: float = 60.0) -> Dict:
    conn = http.client.HTTPConnection(base_host, port, timeout=timeout)
    try:
        data = None if body is None else json.dumps(body).encode()
        conn.request(method, path, body=data,
                     headers={"Content-Type": "application/json"})
        resp = conn.getresponse()
        return json.loads(resp.read())
    finally:
        conn.close()


def _labelled(counters: Dict[str, float], prefix: str) -> Dict[str, float]:
    """The counters named ``prefix<label>}``, by label."""
    return {k[len(prefix):-1]: v for k, v in counters.items()
            if k.startswith(prefix)}


class DaemonDriver:
    """An in-process ``ScheduleDaemon`` on a loopback port, fed by a load
    generator in a child process that never imports jax."""

    TERMINAL = ("done", "failed", "cancelled")

    def __init__(self, ctx: Dict):
        self.ctx = ctx
        self.config, self.mix = ctx["config"], ctx["mix"]
        self.store = tempfile.mkdtemp(prefix="chipbench-store-")
        self.svc = None
        self.child = None

    def get(self, path: str) -> Dict:
        return _http(self.svc.host, self.svc.port, "GET", path)

    def _counters(self) -> Dict[str, float]:
        return self.get("/metrics")["metrics"]["counters"]

    def setup(self) -> None:
        from repro.serve import ScheduleDaemon
        self.svc = ScheduleDaemon(self.store, port=0,
                                  workers=self.mix["workers"])
        self.svc.start()
        accs = self.config["accelerators"]
        ids = []
        for i in range(self.mix["warmup_jobs"]):
            spec = job_spec(self.config, self.mix, accs[i * 5 % len(accs)],
                         WARMUP_SEED + i)
            ids.append(_http(self.svc.host, self.svc.port, "POST", "/jobs",
                             {"spec": spec})["id"])
        deadline = now() + 600.0
        while True:
            states = [self.get(f"/jobs/{i}")["state"] for i in ids]
            if all(s in self.TERMINAL for s in states):
                break
            if now() > deadline:
                raise RuntimeError(f"warm-up jobs unfinished: {states}")
            time.sleep(0.05)
        if any(s != "done" for s in states):
            raise RuntimeError(f"warm-up jobs ended {states}")
        self.child = subprocess.Popen(
            [sys.executable, str(HERE / "loadgen.py"), self.svc.host,
             str(self.svc.port)],
            stdin=subprocess.PIPE, stdout=subprocess.PIPE, text=True)
        ready = self.child.stdout.readline().strip()
        if ready != "ready":
            raise RuntimeError(f"load generator did not start: {ready!r}")

    def window(self, seed: int, seconds: float, tracer: Tracer,
               compiles: CompileLog) -> Dict:
        jobs = open_loop_jobs(self.mix, self.config["accelerators"], seed,
                              seconds)
        for j in jobs:
            j["spec"] = job_spec(self.config, self.mix, j["accelerator"],
                              j["seed"])
        before = self._counters()
        t0 = now() + 0.05
        self.child.stdin.write(json.dumps({
            "t0": t0, "jobs": [{"t": j["t"], "spec": j["spec"]}
                               for j in jobs],
            "poll_s": self.mix["poll_s"],
            "deadline": t0 + seconds + self.mix["drain_s"]}) + "\n")
        self.child.stdin.close()
        while now() < t0:
            time.sleep(0.001)
        c0 = compiles.requests
        end = t0 + seconds
        tracer.arm(t0)
        while True:
            t = now()
            if t >= end:
                break
            tracer.tick(t, lambda: {})
            time.sleep(min(0.01, max(end - t, 0.0)))
        c1 = compiles.requests
        tracer.finish()
        out = self.child.stdout.read()      # the child ends by its deadline
        self.child.wait(timeout=60)
        self.child = None
        result = json.loads(out.strip().splitlines()[-1])
        after = self._counters()
        delta = {k: v - before.get(k, 0) for k, v in after.items()}
        keys = sorted({r["key"] for r in result["jobs"]
                       if r.get("state") == "done" and r.get("key")})
        artifacts = {k: self.get(f"/artifacts/{k}") for k in keys}
        for j, r in zip(jobs, result["jobs"]):
            r["spec"] = j["spec"]
        return {"t0": t0, "window_s": seconds, "jobs": result["jobs"],
                "lateness": result["lateness"],
                "outcomes": _labelled(delta, "daemon.jobs{outcome="),
                "engines": _labelled(delta, "eval.batches_by_engine{engine="),
                "queue_wait": {"seconds": delta.get("daemon.queue_wait_s", 0),
                               "waits": delta.get("daemon.queue_waits", 0)},
                "artifacts": artifacts,
                "compiles_in_window": c1 - c0, "attempted": len(jobs)}

    def close(self) -> None:
        if self.child is not None:
            self.child.kill()
            self.child.wait()
            self.child = None
        if self.svc is not None:
            self.svc.stop()
            self.svc = None
        shutil.rmtree(self.store, ignore_errors=True)


DRIVERS = {"session": SessionDriver, "daemon": DaemonDriver}
