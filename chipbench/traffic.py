"""The traffic generator: turns a mix's parameters and ``--seed`` into the
requests of one run.

Every seed gets the same amount of work, so runs with different seeds
measure the same thing:

* closed loop: searches with seeds ``seed``, ``seed + 1``, ... back to back;
* open loop: design sweeps, as the paper's Fig. 11 runs them (and the
  repository's ``benchmarks/fig11_repartition.py``): one search of the same
  seed on each machine of the configuration, posted back to back.
  ``round(sweeps_per_s * seconds)`` sweeps arrive at times whose gaps are
  the stratified quantiles of an exponential at ``sweeps_per_s`` (a
  Poisson process's gaps, each quantile once), in one fixed shuffled order
  (``FIXED``) and scaled to end inside the window.  Each sweep's search
  seed is drawn from ``--seed``, distinct from the others', so no job
  repeats another's spec.

So every seed sends the same number of searches on the same machines at
the same times; the seed chooses only the searches' seeds.
"""
from __future__ import annotations

import itertools
import math
import random
from typing import Dict, Iterator, List, Sequence

#: draws the arrival gaps' order of every open-loop run
FIXED = "open-loop"
#: search seeds of the open loop are drawn below this
SEARCH_SEEDS = 1 << 31


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


def open_loop_jobs(mix: Dict, accelerators: Sequence[str], seed: int,
                   seconds: float) -> List[Dict]:
    """The open-loop schedule of one run: send time, search seed, machine;
    a sweep's jobs share its send time and go out in machine order."""
    times = arrival_times(mix["sweeps_per_s"], seconds, random.Random(FIXED))
    seeds = random.Random(f"open-loop:{seed}").sample(range(SEARCH_SEEDS),
                                                      len(times))
    return [{"t": t, "seed": s, "accelerator": acc}
            for t, s in zip(times, seeds) for acc in accelerators]
