"""The engine's single wall-clock seam.

Every instrumented module (``search.session``, ``search.artifact``,
``serve.scheduler``, ``costmodel.evaluator``, ``core.population``, and
``repro.obs`` itself) reads time through these three callables instead of
calling ``time.*`` directly.  The determinism linter's ``clock-seam`` rule
(``[tool.repro.lint.clock_seam]`` in pyproject.toml) enforces the routing,
so the wall-clock allowlist names exactly one file — this one — and every
wall-time read in the engine is auditable from a single seam.

Wall time here is *metadata only* (trace timestamps, artifact provenance);
it never feeds fingerprints, store keys, costs, or RNG.
"""
from __future__ import annotations

import time as _time


def unix_time() -> int:
    """Whole-second wall time (artifact ``created_unix``, report stamps)."""
    return int(_time.time())


def now() -> float:
    """Float wall time, for trace event timestamps."""
    return _time.time()


#: Monotonic high-resolution timer, for span durations and throughput: the
#: C function itself, with no Python frame around it (phase spans read it
#: twice each on the hot path).
perf_counter = _time.perf_counter
