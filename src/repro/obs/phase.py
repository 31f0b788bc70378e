"""Named phase spans for the hot path, on two clocks at once.

A :class:`Phases` keeps, per span name, how many times the span ran
(``calls``) and the seconds it held (``seconds``, read through
:mod:`repro.obs.clock`).  While a JAX profiler session is recording, each
span also enters ``jax.profiler.TraceAnnotation(name)``, so it lands on the
profiler's host plane on the same clock as the device's events and names
the idle gaps between kernel calls.

    ph = Phases()
    with ph.span("pop.batch"):
        ...
    ph.snapshot()        # {"pop.batch": {"calls": 1, "seconds": ...}}

Spans open at batch and generation boundaries only, never per offspring:
with the profiler off one costs two clock reads, one ``is_enabled()`` and a
context manager.  jax is resolved only once something else has imported
it, so this package stays stdlib-only and the numpy engine never loads jax.

A :class:`Phases` is not locked: each owner updates its own from one
thread at a time (the population engine under its batch lock, each GA run
and each session from the thread that drives it), and
:func:`merge_phases` adds owners' snapshots together.
"""
from __future__ import annotations

import sys
from typing import Any, Dict, List, Mapping, Optional

from repro.obs import clock

#: ``{name: {"calls": int, "seconds": float}}``
PhaseSnapshot = Dict[str, Dict[str, Any]]

_annotation: Optional[Any] = None       # jax.profiler.TraceAnnotation


def _trace_annotation() -> Optional[Any]:
    """The profiler's annotation class once jax is loaded, else None."""
    global _annotation
    if _annotation is None and "jax" in sys.modules:
        from jax.profiler import TraceAnnotation
        _annotation = TraceAnnotation
    return _annotation


class _Span:
    """Context manager behind :meth:`Phases.span`: one per name, reused,
    so entering a span allocates nothing.  A span does not nest inside
    itself (no phase contains itself)."""

    __slots__ = ("_name", "acc", "_ta", "_t0")

    def __init__(self, name: str):
        self._name = name
        self.acc: List[Any] = [0, 0.0]          # calls, seconds
        self._ta: Optional[Any] = None
        self._t0 = -1.0                         # < 0: not open

    def __enter__(self) -> None:
        if self._t0 >= 0.0:
            raise RuntimeError(f"phase {self._name!r} is already open")
        ta = _annotation if _annotation is not None else _trace_annotation()
        if ta is not None and ta.is_enabled():
            self._ta = ta(self._name)
            self._ta.__enter__()
        self._t0 = clock.perf_counter()

    def __exit__(self, *exc: Any) -> bool:
        acc = self.acc
        acc[1] += clock.perf_counter() - self._t0
        acc[0] += 1
        self._t0 = -1.0
        if self._ta is not None:
            self._ta.__exit__(None, None, None)
            self._ta = None
        return False


class Phases:
    """Calls and seconds per span name (see the module docstring)."""

    __slots__ = ("_spans",)

    def __init__(self) -> None:
        self._spans: Dict[str, _Span] = {}

    def span(self, name: str) -> _Span:
        """``with phases.span(name):`` times the block under ``name``."""
        sp = self._spans.get(name)
        if sp is None:
            sp = self._spans[name] = _Span(name)
        return sp

    def calls(self, name: str) -> int:
        sp = self._spans.get(name)
        return sp.acc[0] if sp else 0

    def seconds(self, name: str) -> float:
        sp = self._spans.get(name)
        return sp.acc[1] if sp else 0.0

    def snapshot(self) -> PhaseSnapshot:
        return {k: {"calls": sp.acc[0], "seconds": sp.acc[1]}
                for k, sp in sorted(list(self._spans.items()))}


def merge_phases(*snapshots: Mapping[str, Mapping[str, Any]]
                 ) -> PhaseSnapshot:
    """Sum phase snapshots name by name."""
    out: PhaseSnapshot = {}
    for snap in snapshots:
        for name, v in snap.items():
            o = out.setdefault(name, {"calls": 0, "seconds": 0.0})
            o["calls"] += v["calls"]
            o["seconds"] += v["seconds"]
    return {k: out[k] for k in sorted(out)}
