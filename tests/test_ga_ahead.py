"""The GA loop's one-call order against a verbatim copy of the two-call
loop it replaced: the same winners, histories, counts, observer ticks and
RNG draws on every seed, one batch-scorer call a generation after the
first, and the old order wherever a ``migrate`` hook is given."""
from __future__ import annotations

import functools
import random
from collections import Counter
from typing import Dict, Hashable, List, Optional, Tuple

import pytest

from repro.analysis import build_spacemap
from repro.configs import get_config
from repro.configs.base import SHAPES
from repro.core.ga import (GAConfig, GAMigrate, GAObserver, GAResult,
                           run_ga_problem, select_pool)
from repro.core.problem import FusionProblem, SearchProblem
from repro.costmodel import SIMBA, Evaluator
from repro.obs import Phases
from repro.search import register_objective
from repro.search.registry import OBJECTIVES
from repro.search.session import _CustomObjectiveProblem
from repro.search.tpu import TpuScheduleProblem
from repro.workloads import build_workload

SEEDS = range(20)
GENERATIONS = 12


# ---- the oracle: the two-call loop, verbatim --------------------------------------

def two_call_loop(problem: SearchProblem, config: GAConfig = GAConfig(),
                  observer: Optional[GAObserver] = None,
                  migrate: Optional[GAMigrate] = None) -> GAResult:
    """The loop as it stood with two batch-scorer calls a generation:
    the offspring, then the top-up (kept verbatim as the oracle)."""
    rng = random.Random(config.seed)
    # bound locals for the per-offspring hot path; getrandbits drives an
    # inlined _randbelow identical to CPython's (same draws as rng.randrange)
    getrandbits = rng.getrandbits
    pkey = problem.key
    pmut = problem.mutate
    pbatch_unique = getattr(problem, "fitness_batch_unique", None)
    fit_cache: Dict[Hashable, float] = {}
    offspring_evaluated = 0
    phases = Phases()
    span = phases.span

    def score(states: List) -> List[float]:
        """Fitness per genome, via the run-level cache; novel genomes are
        scored in one batch so the evaluator can dedupe shared structure.
        The fresh list is unique by construction, so problems exposing
        ``fitness_batch_unique`` skip their own dedup pass."""
        with span("ga.score"):
            keys = [pkey(s) for s in states]
            fresh: Dict[Hashable, object] = {}
            for k, s in zip(keys, states):
                if k not in fit_cache and k not in fresh:
                    fresh[k] = s
            vals = list(fresh.values())
        if vals:
            fits = (pbatch_unique(vals) if pbatch_unique is not None
                    else problem.fitness_batch(vals))
        with span("ga.score"):
            if vals:
                fit_cache.update(zip(fresh, fits))
            return [fit_cache[k] for k in keys]

    # warm-start seeding (repro.serve.warmstart): extra genomes scored into
    # the initial pool alongside the canonical start.  With no seeds (the
    # default) the pool is exactly ``[initial]`` and every subsequent RNG
    # draw is bit-identical to the unseeded loop; seeds widen the first
    # generation's parent-index range, which is why seeding is opt-in.
    init = problem.initial()
    starters: List = [init]
    seen_keys = {pkey(init)}
    for seed_genome in getattr(problem, "seed_genomes", ()) or ():
        k = pkey(seed_genome)
        if k not in seen_keys:
            seen_keys.add(k)
            starters.append(seed_genome)
    pool: List[Tuple[float, object]] = list(zip(score(starters), starters))
    history: List[float] = []

    for gen in range(config.generations):
        with span("ga.generation"):
            with span("ga.mutate"):
                offspring: List = []
                npool = len(pool)
                kbits = npool.bit_length()
                for _ in range(config.mutations_per_gen):
                    r = getrandbits(kbits)
                    while r >= npool:
                        r = getrandbits(kbits)
                    parent = pool[r][1]
                    if config.crossover_rate \
                            and rng.random() < config.crossover_rate \
                            and len(pool) > 1:
                        other = pool[rng.randrange(len(pool))][1]
                        parent = problem.crossover(parent, other, rng)
                    offspring.append(pmut(parent, rng))
            fits = score(offspring)
            offspring_evaluated += len(offspring)

            with span("ga.select"):
                pool = select_pool(pool + list(zip(fits, offspring)),
                                   config.top_n, config.random_survivors,
                                   rng, key=problem.key)
            # keep the pool topped up to the paper's full P with fresh
            # mutants of survivors (duplicates allowed; next generation
            # dedupes); parents are picked by size-2 tournament over the
            # rank-sorted survivor list, which balances intensification
            # around the elite against survivor diversity
            if len(pool) < config.population:
                with span("ga.mutate"):
                    need = config.population - len(pool)
                    n_surv = len(pool)
                    sbits = n_surv.bit_length()
                    topup = []
                    for _ in range(need):
                        i = getrandbits(sbits)
                        while i >= n_surv:
                            i = getrandbits(sbits)
                        j = getrandbits(sbits)
                        while j >= n_surv:
                            j = getrandbits(sbits)
                        topup.append(pmut(pool[i if i < j else j][1], rng))
                tfits = score(topup)
                offspring_evaluated += len(topup)
                pool.extend(zip(tfits, topup))
            if migrate is not None:
                migrated = migrate(gen, pool)
                if migrated is not None:
                    pool = migrated
            history.append(max(f for f, _ in pool))
            if observer is not None:
                with span("ga.observe"):
                    stop = observer(gen, history[-1], len(fit_cache),
                                    offspring_evaluated)
                if stop:
                    break

    best_f, best_s = max(pool, key=lambda fs: fs[0])
    # batch scoring may re-associate float sums (~1 ulp); report the winner's
    # exact single-state fitness so results are comparable across engines
    best_f = problem.fitness(best_s)
    return GAResult(best_state=best_s, best_fitness=best_f,
                    history=history, evaluations=len(fit_cache),
                    offspring_evaluated=offspring_evaluated,
                    phases=phases.snapshot())


# ---- a problem that records what the loop asks of it ------------------------------

class Recording(SearchProblem):
    """Wraps a problem: keeps the key of every child ``mutate`` makes (so
    the RNG draws, in order), the loop's RNG, and the generation in which
    each batch-scorer call falls."""

    def __init__(self, inner: SearchProblem):
        self.inner = inner
        self.seed_genomes = inner.seed_genomes
        self.children: List[Hashable] = []
        self.calls: List[int] = []
        self.rng: Optional[random.Random] = None
        self.gen = 0                       # observer ticks so far

    def initial(self):
        return self.inner.initial()

    def key(self, genome):
        return self.inner.key(genome)

    def fitness(self, genome):
        return self.inner.fitness(genome)

    def crossover(self, a, b, rng):
        return self.inner.crossover(a, b, rng)

    def mutate(self, genome, rng):
        self.rng = rng
        child = self.inner.mutate(genome, rng)
        self.children.append(self.inner.key(child))
        return child

    def fitness_batch(self, genomes):
        self.calls.append(self.gen)
        unique = getattr(self.inner, "fitness_batch_unique", None)
        return (unique(genomes) if unique is not None
                else self.inner.fitness_batch(genomes))


def run(loop, make_problem, config: GAConfig, stop_at: Optional[int] = None,
        migrate: Optional[GAMigrate] = None) -> Tuple[GAResult, Recording,
                                                       list]:
    """One search; (result, its recording, the observer's argument tuples)."""
    problem = Recording(make_problem())
    ticks: list = []

    def observer(gen, best, evals, offspring):
        ticks.append((gen, best, evals, offspring))
        problem.gen = gen + 1
        return gen == stop_at

    return loop(problem, config, observer, migrate), problem, ticks


def outcome(res: GAResult, problem: Recording, ticks: list) -> tuple:
    return (problem.key(res.best_state), res.best_fitness, res.history,
            res.evaluations, res.offspring_evaluated, ticks)


# ---- problems ---------------------------------------------------------------------

@functools.lru_cache(maxsize=None)
def evaluator(workload: str) -> Evaluator:
    """One evaluator per graph for the whole module: its caches change no
    score, so both loops may share it."""
    return Evaluator(build_workload(workload), SIMBA)


def fusion(workload: str = "mobilenet_v3", spacemap: bool = False,
           seeds: int = 0, seed: int = 0):
    def make():
        ev = evaluator(workload)
        sm = build_spacemap(ev.graph, "default", "simba") if spacemap \
            else None
        problem = FusionProblem(ev.graph, ev, "edp", spacemap=sm)
        if seeds:
            draw = random.Random(1000 + seed)
            problem.seed_genomes = tuple(problem.random_genome(draw)
                                         for _ in range(seeds))
        return problem
    return make


CUSTOM = "test_ga_ahead_energy_squared"


def custom(workload: str = "vgg16"):
    if CUSTOM not in OBJECTIVES:
        register_objective(CUSTOM, lambda c: c.energy_pj ** 2)

    def make():
        ev = evaluator(workload)
        return _CustomObjectiveProblem(ev.graph, ev, CUSTOM)
    return make


def tpu():
    cfg = get_config("dbrx-132b")
    return lambda: TpuScheduleProblem(cfg, SHAPES["train_4k"])


def no_topup(seed: int) -> GAConfig:
    """Selection keeps more than the pool holds: no generation tops up."""
    return GAConfig(population=10, top_n=8, random_survivors=6,
                    mutations_per_gen=20, generations=GENERATIONS, seed=seed)


#: name -> (problem factory of the seed, GAConfig of the seed, calls a
#: generation after the first: "one"; "at_most_one" where a generation may
#: find every genome cached (a small space); "oracle" where no generation
#: tops up, so the order is the two-call loop's)
VARIANTS = {
    "paper": (lambda s: fusion(),
              lambda s: GAConfig.paper(generations=GENERATIONS, seed=s),
              "one"),
    "fast": (lambda s: fusion(),
             lambda s: GAConfig.fast(generations=GENERATIONS, seed=s), "one"),
    "crossover": (lambda s: fusion(),
                  lambda s: GAConfig.fast(generations=GENERATIONS, seed=s,
                                          crossover_rate=0.3), "one"),
    "spacemap": (lambda s: fusion("resnet50", spacemap=True),
                 lambda s: GAConfig.fast(generations=GENERATIONS, seed=s),
                 "one"),
    "seed_genomes": (lambda s: fusion(seeds=5, seed=s),
                     lambda s: GAConfig.paper(generations=GENERATIONS,
                                              seed=s, crossover_rate=0.1),
                     "one"),
    "custom_objective": (lambda s: custom(),
                         lambda s: GAConfig.fast(generations=6, seed=s),
                         "one"),
    "tpu_schedule": (lambda s: tpu(),
                     lambda s: GAConfig.fast(generations=GENERATIONS,
                                             seed=s), "at_most_one"),
    "no_topup": (lambda s: fusion(), no_topup, "oracle"),
}


def calls_per_generation(problem: Recording) -> Counter:
    return Counter(problem.calls)


# ---- equivalence ------------------------------------------------------------------

@pytest.mark.parametrize("variant", sorted(VARIANTS))
def test_one_call_order_matches_the_two_call_loop(variant):
    """Full runs: every result, every observer tick, every child in order
    and the RNG's final state as the two-call loop gives them; after the
    first generation, one batch-scorer call a generation."""
    factory, make_config, calls = VARIANTS[variant]
    for seed in SEEDS:
        config = make_config(seed)
        old, p_old, t_old = run(two_call_loop, factory(seed), config)
        new, p_new, t_new = run(run_ga_problem, factory(seed), config)
        assert outcome(new, p_new, t_new) == outcome(old, p_old, t_old), seed
        assert len(t_new) == config.generations
        assert p_new.children == p_old.children, seed
        assert p_new.rng.getstate() == p_old.rng.getstate(), seed
        per_new = calls_per_generation(p_new)
        if calls == "oracle":
            assert p_new.calls == p_old.calls, seed
            assert "ga.ahead" not in new.phases, seed
            continue
        for g in range(1, config.generations):
            assert per_new[g] == 1 or (calls == "at_most_one"
                                       and per_new[g] == 0), (seed, g)
        assert new.phases["ga.ahead"]["calls"] == config.generations - 1


def test_paper_search_makes_one_call_a_generation_after_the_first():
    """The paper preset on MobileNet-v3: the first generation scores the
    start, its offspring and then its top-up with the next offspring; every
    later generation makes exactly one call, where the two-call loop made
    two."""
    for seed in SEEDS:
        config = GAConfig.paper(generations=GENERATIONS, seed=seed)
        _, p_old, _ = run(two_call_loop, fusion(), config)
        new, p_new, _ = run(run_ga_problem, fusion(), config)
        per_old, per_new = (calls_per_generation(p_old),
                            calls_per_generation(p_new))
        assert per_new[0] == per_old[0] == 3, seed
        for g in range(1, config.generations):
            assert per_old[g] == 2 and per_new[g] == 1, (seed, g)
        assert new.phases["ga.ahead"]["calls"] == config.generations - 1
        assert new.phases["ga.generation"]["calls"] == config.generations


@pytest.mark.parametrize("variant", ["paper", "fast", "crossover",
                                     "seed_genomes"])
def test_observer_stop_matches_the_two_call_loop(variant):
    """An observer that stops the search at a chosen generation sees the
    same ticks and leaves the same results; the one-call loop has drawn
    the next generation's offspring by then, and only those."""
    factory, make_config, _ = VARIANTS[variant]
    for seed in SEEDS:
        config = make_config(seed)
        stop_at = seed % config.generations
        old, p_old, t_old = run(two_call_loop, factory(seed), config,
                                stop_at=stop_at)
        new, p_new, t_new = run(run_ga_problem, factory(seed), config,
                                stop_at=stop_at)
        assert outcome(new, p_new, t_new) == outcome(old, p_old, t_old), seed
        assert len(t_new) == stop_at + 1
        last = stop_at == config.generations - 1
        drawn_ahead = 0 if last else config.mutations_per_gen
        assert len(p_new.children) == len(p_old.children) + drawn_ahead
        assert p_new.children[:len(p_old.children)] == p_old.children
        if last:
            assert p_new.rng.getstate() == p_old.rng.getstate(), seed


def _elite_swap(gen, pool):
    """A migration hook that replaces the pool every other generation,
    reversing it (no RNG)."""
    return list(reversed(pool)) if gen % 2 else None


@pytest.mark.parametrize("migrate", [lambda gen, pool: None, _elite_swap],
                         ids=["keeps", "replaces"])
def test_migrate_keeps_the_two_call_order(migrate):
    """With a ``migrate`` hook the loop scores as the two-call loop did:
    the same calls in the same generations, no ``ga.ahead``, and the same
    results."""
    for seed in SEEDS:
        config = GAConfig.fast(generations=GENERATIONS, seed=seed)
        old, p_old, t_old = run(two_call_loop, fusion(), config,
                                migrate=migrate)
        new, p_new, t_new = run(run_ga_problem, fusion(), config,
                                migrate=migrate)
        assert outcome(new, p_new, t_new) == outcome(old, p_old, t_old), seed
        assert p_new.calls == p_old.calls, seed
        assert p_new.children == p_old.children, seed
        assert p_new.rng.getstate() == p_old.rng.getstate(), seed
        assert "ga.ahead" not in new.phases
