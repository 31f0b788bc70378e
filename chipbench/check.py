"""The comparison that decides ``correct``.

Every answer due in the window is held against the plain reference
(``reference.py``), built from the configuration's own data:

* each artifact: the spec it answers, the winner's legality, its costs,
  per-group breakdowns and fitness (``best_fitness`` and the engine's last
  ``history`` entry, which is the batch score of the winner);
* each batch the window kept a sample of (search cells): the fitness the
  scoring engine returned for every genome, and whether the winner of a
  finished search is at least as good as every genome it scored there.

Three numbers come out, each with a limit in ``limits/<cell>.json``:

* ``rel_gap``: the widest relative gap between a float the program returned
  and the reference's;
* ``mismatches``: exact disagreements (integers, group members, legality,
  which spec an artifact answers);
* ``unanswered``: answers due that never came (a search that raised, a job
  that failed, was cancelled or had not resolved by the drain limit).

The control puts the reference computed in float32 in the program's place
(:func:`control_answers`).
"""
from __future__ import annotations

import copy
from typing import Dict, Iterable, List, Optional

import numpy as np

from chipbench.reference import Graph, Reference, machine_for

#: floats of a cost record compared by relative gap
_COST_FLOATS = ("energy_pj", "cycles")
_COST_INTS = ("dram_read_words", "dram_write_words", "act_write_events",
              "macs", "n_groups")
_GROUP_FLOATS = ("energy_pj", "compute_cycles", "dram_cycles")
_GROUP_INTS = ("dram_read_words", "dram_write_words", "act_write_events",
               "macs", "tile_rows", "weight_passes")
_SPEC_KEYS = ("workload", "accelerator", "objective", "backend", "costmodel",
              "backend_config", "seed")


class Tally:
    """The numbers compared, with the first few faults named."""

    def __init__(self):
        self.rel_gap = 0.0
        self.mismatches = 0
        self.unanswered = 0
        self.compared = 0
        self.faults: List[str] = []

    def _note(self, what: str) -> None:
        if len(self.faults) < 8:
            self.faults.append(what)

    def gap(self, got, want, what: str) -> None:
        self.compared += 1
        got, want = float(got), float(want)
        g = abs(got - want) / abs(want) if want else abs(got)
        if not g <= self.rel_gap:          # NaN counts as the widest gap
            self.rel_gap = float("inf") if g != g else g
            if g > 1e-12 or g != g:
                self._note(f"{what}: {got!r} vs reference {want!r}")

    def same(self, got, want, what: str) -> None:
        self.compared += 1
        if got != want:
            self.mismatches += 1
            self._note(f"{what}: {got!r} vs reference {want!r}")

    def missing(self, what: str) -> None:
        self.unanswered += 1
        self._note(f"no answer: {what}")

    def numbers(self) -> Dict[str, float]:
        return {"rel_gap": self.rel_gap, "mismatches": self.mismatches,
                "unanswered": self.unanswered}


class References:
    """One reference per machine of a configuration."""

    def __init__(self, config: Dict, dtype=float):
        self.config = config
        self.graph = Graph(config["graph"])
        self.dtype = dtype
        self._refs: Dict[str, Reference] = {}

    def __call__(self, accelerator: str) -> Reference:
        ref = self._refs.get(accelerator)
        if ref is None:
            ref = self._refs[accelerator] = Reference(
                self.graph, machine_for(accelerator, self.config["machines"],
                                        self.config["energy_pj"],
                                        self.dtype))
        return ref


def check_artifact(a: Dict, want_spec: Dict, refs: References, t: Tally,
                   tag: str) -> Optional[float]:
    """Hold one artifact against the reference; the winner's reference
    fitness, or None when the winner is not a legal schedule."""
    spec = a.get("spec", {})
    for k in _SPEC_KEYS:
        t.same(spec.get(k), want_spec[k], f"{tag} spec.{k}")
    ref = refs(want_spec["accelerator"])
    t.same(a.get("n_edges"), len(ref.g.edges), f"{tag} n_edges")
    mask = int(a["genome_mask"], 16)
    best = ref.schedule(mask)
    t.same(best is not None, True, f"{tag} winner {a['genome_mask']} legal")
    if best is None:
        return None
    for name, got, want in (("best", a["best"], best),
                            ("baseline", a["baseline"], ref.baseline())):
        for k in _COST_FLOATS:
            t.gap(got[k], want[k], f"{tag} {name}.{k}")
        for k in _COST_INTS:
            t.same(got[k], want[k], f"{tag} {name}.{k}")
    fit = ref.fitness(mask)
    t.gap(a["best_fitness"], fit, f"{tag} best_fitness")
    if a.get("history"):
        t.gap(a["history"][-1], fit, f"{tag} history[-1]")
    want_groups = {tuple(g["members"]): g for g in best["groups"]}
    got_groups = {tuple(sorted(g["members"])): g
                  for g in a.get("group_breakdowns", [])}
    t.same(sorted(got_groups), sorted(want_groups), f"{tag} groups")
    for members, g in got_groups.items():
        w = want_groups.get(members)
        if w is None:
            continue
        for k in _GROUP_FLOATS:
            t.gap(g[k], w[k], f"{tag} group {members[0]}.{k}")
        for k in _GROUP_INTS:
            t.same(g[k], w[k], f"{tag} group {members[0]}.{k}")
    return fit


def check_samples(samples: Iterable, ref: Reference, t: Tally,
                  tag: str) -> float:
    """Hold sampled batch scores against the reference; the best
    reference fitness among the sampled genomes."""
    top = 0.0
    for masks, fits in samples:
        for mask, got in zip(masks, fits):
            want = ref.fitness(mask)
            t.same(got > 0, want > 0, f"{tag} genome {mask:#x} legal")
            if got > 0 and want > 0:
                t.gap(got, want, f"{tag} genome {mask:#x} fitness")
            top = max(top, float(want))
    return top


def check_searches(window: Dict, refs: References) -> Tally:
    """The search cell: every finished search's artifact and every batch
    sample; a search that raised is unanswered."""
    t = Tally()
    for s in window["searches"]:
        spec = s["spec"]
        tag = f"search seed {spec['seed']}"
        if s.get("error"):
            t.missing(f"{tag}: {s['error']}")
            continue
        top = check_samples(s.get("samples", ()), refs(spec["accelerator"]),
                            t, tag)
        if s.get("artifact") is None:
            continue                      # cut by the window's end
        m0 = t.mismatches
        fit = check_artifact(s["artifact"], spec, refs, t, tag)
        s["bad"] = t.mismatches > m0
        if fit is not None and top > fit:
            # the GA keeps its best genome: a scored genome better than
            # the winner means a score or the selection was wrong
            t.gap(fit, top, f"{tag} winner vs best scored genome")
    return t


def check_jobs(window: Dict, refs: References) -> Tally:
    """The daemon cell: every job due in the window answers its own spec
    with an artifact that holds against the reference."""
    t = Tally()
    for j in window["jobs"]:
        tag = f"job {j.get('id')} ({j['spec']['accelerator']}, " \
              f"seed {j['spec']['seed']})"
        if j.get("state") != "done":
            t.missing(f"{tag} ended {j.get('state')}: {j.get('error')}")
            continue
        art = window["artifacts"].get(j.get("key"))
        if art is None or "genome_mask" not in art:
            t.missing(f"{tag}: no artifact under {j.get('key')}")
            continue
        check_artifact(art, j["spec"], refs, t, tag)
    return t


def check(window: Dict, ctx: Dict, refs: Optional[References] = None
          ) -> Tally:
    refs = refs or References(ctx["config"])
    if "searches" in window:
        return check_searches(window, refs)
    return check_jobs(window, refs)


def verdict(t: Tally, limits: Dict, attempted: int) -> bool:
    nums = t.numbers()
    return (attempted > 0 and t.compared > 0
            and all(nums[k] <= limits[k] for k in nums))


# ---- the control ----------------------------------------------------------
def _answer32(a: Dict, ref32: Reference) -> Dict:
    """An artifact whose numbers the float32 reference computed."""
    a = copy.deepcopy(a)
    mask = int(a["genome_mask"], 16)
    s = ref32.schedule(mask)
    if s is None:
        return a
    for name, src in (("best", s), ("baseline", ref32.baseline())):
        for k in _COST_FLOATS:
            a[name][k] = float(src[k])
    fit = float(ref32.fitness(mask))
    a["best_fitness"] = fit
    if a.get("history"):
        a["history"][-1] = fit
    groups = {tuple(g["members"]): g for g in s["groups"]}
    for g in a.get("group_breakdowns", []):
        w = groups.get(tuple(sorted(g["members"])))
        if w is not None:
            for k in _GROUP_FLOATS:
                g[k] = float(w[k])
    return a


def control_answers(window: Dict, config: Dict) -> Dict:
    """The window's answers with every float the program returned replaced
    by the reference's, computed in float32."""
    refs32 = References(config, np.float32)
    w = copy.deepcopy(window)
    if "searches" in w:
        for s in w["searches"]:
            ref32 = refs32(s["spec"]["accelerator"])
            s["samples"] = [(masks, [float(ref32.fitness(m)) for m in masks])
                            for masks, _ in s.get("samples", ())]
            if s.get("artifact") is not None:
                s["artifact"] = _answer32(s["artifact"], ref32)
    else:
        by_key = {}
        for j in w["jobs"]:
            k = j.get("key")
            if k in w["artifacts"] and k not in by_key:
                by_key[k] = _answer32(w["artifacts"][k],
                                      refs32(j["spec"]["accelerator"]))
        w["artifacts"].update(by_key)
    return w
