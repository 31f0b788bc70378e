"""The traffic generator: turns a mix's parameters and ``--seed`` into the
requests of one run.

Every seed gets the same amount of work in another order, so runs with
different seeds measure the same thing:

* closed loop: searches with seeds ``seed``, ``seed + 1``, ... back to back;
* open loop: ``round(rate * seconds)`` arrivals whose gaps are the
  stratified quantiles of an exponential at ``rate`` (a Poisson process's
  gaps, each quantile once), shuffled by the seed and scaled to end inside
  the window;
* keys of an open-loop job: (search seed, machine) pairs taken at the
  stratified quantiles of Zipf(``zipf_s``) over ``seed_values`` search
  seeds times a uniform choice of machine, so every seed repeats the same
  number of keys; which search seeds and machines hold which popularity
  rank, and the order of the jobs, come from the seed.
"""
from __future__ import annotations

import bisect
import itertools
import math
import random
from typing import Dict, Iterator, List, Sequence


def closed_loop_seeds(seed: int) -> Iterator[int]:
    """Search seeds of the closed loop, in order: ``seed``, ``seed + 1``..."""
    return itertools.count(seed)


def arrival_times(rate: float, seconds: float, rng: random.Random
                  ) -> List[float]:
    """Send times in [0, seconds) of a stratified Poisson process."""
    n = max(int(round(rate * seconds)), 1)
    gaps = [-math.log(1.0 - (i + 0.5) / n) / rate for i in range(n)]
    rng.shuffle(gaps)
    scale = seconds * (n - 0.5) / n / sum(gaps)
    out, t = [], 0.0
    for g in gaps:
        t += g * scale
        out.append(t)
    out[-1] = min(out[-1], seconds * (n - 0.5) / n)
    return out


def zipf_keys(n: int, s: float, values: int, machines: Sequence[str],
              rng: random.Random) -> List[tuple]:
    """``n`` (search seed, machine) keys at the stratified quantiles of
    Zipf(s) over ``values`` seeds times a uniform machine, in random order."""
    weights = [k ** -s for k in range(1, values + 1)]
    total = sum(weights)
    cum, acc = [], 0.0
    for w in weights:                  # pairs ordered by rank, then machine
        for _ in machines:
            acc += w / total / len(machines)
            cum.append(acc)
    picks = [min(bisect.bisect_left(cum, (i + 0.5) / n), len(cum) - 1)
             for i in range(n)]
    seed_of = list(range(values))
    rng.shuffle(seed_of)
    machine_of = [list(machines) for _ in range(values)]
    for row in machine_of:
        rng.shuffle(row)
    keys = [(seed_of[p // len(machines)],
             machine_of[p // len(machines)][p % len(machines)])
            for p in picks]
    rng.shuffle(keys)
    return keys


def open_loop_jobs(mix: Dict, accelerators: Sequence[str], seed: int,
                   seconds: float) -> List[Dict]:
    """The open-loop schedule of one run: send time, search seed, machine."""
    rng = random.Random(f"open-loop:{seed}")
    times = arrival_times(mix["rate_per_s"], seconds, rng)
    keys = zipf_keys(len(times), mix["zipf_s"], mix["seed_values"],
                     accelerators, rng)
    return [{"t": t, "seed": k[0], "accelerator": k[1]}
            for t, k in zip(times, keys)]


def repeat_share(jobs: Sequence[Dict]) -> float:
    """Share of jobs whose key an earlier job of the schedule already had."""
    seen, rep = set(), 0
    for j in jobs:
        k = (j["seed"], j["accelerator"])
        rep += k in seen
        seen.add(k)
    return rep / len(jobs) if jobs else 0.0
