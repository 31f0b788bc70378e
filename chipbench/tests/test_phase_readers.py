"""The readers of the program's phase spans on synthetic window records: the
sums, the completed searches they count, and nothing read where there is
nothing to read."""
from __future__ import annotations

import pytest

from chipbench import harness

READERS = ("engine_host_ms_per_batch", "label_wait_ms_per_batch",
           "group_costing_ms_per_batch", "ga_mutate_ms_per_gen",
           "ga_select_ms_per_gen", "session_build_ms")


def ph(**spans):
    """A phases dict from ``name=(calls, seconds)``; dots spelt ``_``."""
    return {k.replace("_", "."): {"calls": c, "seconds": s}
            for k, (c, s) in spans.items()}


def search(phases, cut=False):
    if cut:
        return {"artifact": None, "cut": True, "offspring": 7}
    return {"artifact": {"backend_stats": {"phases": phases}}, "cut": False}


A = ph(pop_batch=(10, 0.030), pop_labels_launch=(10, 0.004),
       pop_labels_wait=(10, 0.008), pop_cost=(3, 0.002),
       ga_generation=(5, 0.040), ga_mutate=(10, 0.002),
       ga_select=(5, 0.001), session_build=(1, 0.050),
       pop_build=(1, 0.010), session_finish=(1, 0.020))
B = ph(pop_batch=(30, 0.090), pop_labels_launch=(30, 0.012),
       pop_labels_wait=(30, 0.024), ga_generation=(15, 0.120),
       ga_mutate=(30, 0.006), ga_select=(15, 0.003),
       session_build=(1, 0.030), pop_build=(1, 0.010),
       session_finish=(1, 0.020))
#: a cut search's spans would be huge; it carries no artifact, so none count
CUT = ph(pop_batch=(1000, 100.0), ga_generation=(1000, 100.0))

WANT = {
    # (0.120 batch - 0.016 launch - 0.032 wait - 0.002 cost) / 40 batches
    "engine_host_ms_per_batch": 1e3 * 0.070 / 40,
    "label_wait_ms_per_batch": 1e3 * 0.048 / 40,
    "group_costing_ms_per_batch": 1e3 * 0.002 / 40,
    "ga_mutate_ms_per_gen": 1e3 * 0.008 / 20,
    "ga_select_ms_per_gen": 1e3 * 0.004 / 20,
    "session_build_ms": 1e3 * 0.140 / 2,
}


def record(searches):
    return {"window": {"searches": searches, "attempted": len(searches)}}


@pytest.mark.parametrize("name", READERS)
def test_reader_sums_completed_searches_and_leaves_out_the_cut_one(name):
    read = harness.metric_reader(name)
    got = read(record([search(A), search(B), search(CUT, cut=True)]))
    assert got == pytest.approx(WANT[name])


@pytest.mark.parametrize("name", READERS)
def test_reader_reads_nothing_without_a_completed_search(name):
    read = harness.metric_reader(name)
    assert read(record([search(CUT, cut=True)])) is None
    assert read(record([])) is None


@pytest.mark.parametrize("name", READERS)
def test_reader_reads_nothing_from_a_program_without_spans(name):
    """A program whose artifacts carry no ``phases`` (the parent of the
    change that added them) gives no reading, and no error."""
    read = harness.metric_reader(name)
    bare = {"artifact": {"backend_stats": {"batch_time_s": 1.0}}}
    assert read(record([bare, {"artifact": None, "error": "X: y"}])) is None


def test_numpy_engine_has_no_label_wait():
    read = harness.metric_reader("label_wait_ms_per_batch")
    numpy_run = ph(pop_batch=(4, 0.01), pop_labels_host=(4, 0.004),
                   ga_generation=(2, 0.02))
    assert read(record([search(numpy_run)])) is None
    host = harness.metric_reader("engine_host_ms_per_batch")
    assert host(record([search(numpy_run)])) == pytest.approx(2.5)
