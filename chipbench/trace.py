"""Reduce a JAX profiler trace (``.xplane.pb``) to the benchmark's numbers.

Device planes are the TPU planes (``/device:TPU:<i>``).  On each, the
``XLA Ops`` line holds one event per operation run on the core and the
``XLA Modules`` line one event per compiled program run.  From them:

* ``busy_s``: the union of the operation intervals, averaged over the
  device planes;
* per kernel: the number of module events whose name starts with the
  kernel's module name, and their summed durations;
* ``device_ops``: the operations that took most time, summed by name (the
  head of their HLO text);
* ``idle_gaps``: the gaps between busy intervals, each named by the
  innermost host event that covers its midpoint, summed by name.
"""
from __future__ import annotations

import glob
import os
import re
from collections import defaultdict
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
TOP = 10
#: an operation's name is its HLO text, cut to this many characters
OP_NAME = 100
#: gaps named by host activity (the longest ones); the rest are summed
NAMED_GAPS = 500
_SUFFIX = re.compile(r"\(\d+\)$")


def find_xplane(root: str) -> str:
    """The newest ``.xplane.pb`` under a profiler log directory."""
    found = sorted(glob.glob(os.path.join(root, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {root}")
    return found[-1]


def _union(iv: np.ndarray) -> np.ndarray:
    """Merge ``(k, 2)`` [start, end) intervals into disjoint sorted ones."""
    if len(iv) == 0:
        return iv.reshape(0, 2)
    iv = iv[np.argsort(iv[:, 0], kind="stable")]
    ends = np.maximum.accumulate(iv[:, 1])
    new = np.ones(len(iv), dtype=bool)
    new[1:] = iv[1:, 0] > ends[:-1]
    starts = iv[new, 0]
    last = np.r_[np.nonzero(new)[0][1:] - 1, len(iv) - 1]
    return np.stack([starts, ends[last]], axis=1)


def module_name(name: str) -> str:
    """A module event's name without its trailing ``(<program id>)``."""
    return _SUFFIX.sub("", name)


def _events(line) -> List[Tuple[str, float, float]]:
    return [(e.name, e.start_ns, e.duration_ns) for e in line.events]


def read_planes(path: str) -> Tuple[List[Dict[str, list]], list]:
    """(device planes as {line name: events}, host events) of a trace."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    devices, host = [], []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            devices.append({ln.name: _events(ln) for ln in plane.lines})
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                host.extend(_events(ln))
    return devices, host


def reduce_events(devices: Sequence[Dict[str, list]], host: list,
                  kernels: Sequence[str]) -> Optional[Dict]:
    """The reduction of already-read planes (see the module docstring);
    None when no device plane ran an operation."""
    busy, op_time = [], defaultdict(float)
    per_kernel = {k: {"calls": 0, "seconds": 0.0} for k in kernels}
    gaps = []
    for lines in devices:
        ops = lines.get(OPS_LINE, [])
        if not ops:
            continue
        iv = np.array([(s, s + d) for _, s, d in ops], dtype=np.float64)
        u = _union(iv)
        busy.append(float((u[:, 1] - u[:, 0]).sum()) * 1e-9)
        for name, _, d in ops:
            op_time[name[:OP_NAME]] += d * 1e-9
        for name, _, d in lines.get(MODULES_LINE, []):
            base = module_name(name)
            for k in kernels:
                if base.startswith(k):
                    per_kernel[k]["calls"] += 1
                    per_kernel[k]["seconds"] += d * 1e-9
        if len(u) > 1:
            gaps.append(np.stack([u[:-1, 1], u[1:, 0]], axis=1))
    if not busy:
        return None
    gap_iv = np.concatenate(gaps) if gaps else np.zeros((0, 2))
    return {
        "busy_s": sum(busy) / len(busy),
        "device_planes": len(busy),
        "kernels": per_kernel,
        "device_ops": sorted(([k, v] for k, v in op_time.items()),
                             key=lambda kv: -kv[1])[:TOP],
        "idle_gaps": name_gaps(gap_iv, host, len(busy)),
    }


def name_gaps(gaps: np.ndarray, host: list, planes: int) -> list:
    """Idle gaps summed by the innermost host event covering each gap's
    midpoint, longest first (seconds averaged over device planes)."""
    if len(gaps) == 0:
        return []
    length = (gaps[:, 1] - gaps[:, 0]) * 1e-9 / planes
    order = np.argsort(-length, kind="stable")
    named, rest = order[:NAMED_GAPS], order[NAMED_GAPS:]
    out = defaultdict(float)
    if host:
        hs = np.array([s for _, s, _ in host], dtype=np.float64)
        he = hs + np.array([d for _, _, d in host], dtype=np.float64)
        hd = he - hs
    for i in named:
        mid = 0.5 * (gaps[i, 0] + gaps[i, 1])
        name = "(no host event)"
        if host:
            cover = np.nonzero((hs <= mid) & (he > mid))[0]
            if len(cover):
                name = host[int(cover[np.argmin(hd[cover])])][0]
        out[name] += float(length[i])
    if len(rest):
        out["(shorter gaps)"] += float(length[rest].sum())
    return sorted(([k, v] for k, v in out.items()),
                  key=lambda kv: -kv[1])[:TOP]


def reduce_trace(path: str, kernels: Sequence[str]) -> Optional[Dict]:
    devices, host = read_planes(path)
    return reduce_events(devices, host, kernels)
