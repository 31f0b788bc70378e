"""``ga_ahead_share`` on synthetic window records: the share of completed
searches' generations that drew the next offspring ahead, 0 from a loop
that never does, and nothing read where no search completed."""
from __future__ import annotations

import pytest

from chipbench import harness
from chipbench.tests.test_phase_readers import A, B, CUT, ph, record, search

READ = harness.metric_reader("ga_ahead_share")


def test_share_sums_completed_searches_and_leaves_out_the_cut_one():
    a = {**A, **ph(ga_ahead=(4, 0.001))}          # 4 of 5 generations
    b = {**B, **ph(ga_ahead=(15, 0.003))}         # 15 of 15
    cut = {**CUT, **ph(ga_ahead=(1000, 1.0))}
    got = READ(record([search(a), search(b), search(cut, cut=True)]))
    assert got == pytest.approx(100.0 * 19 / 20)


def test_share_is_zero_without_the_span():
    """A loop that never scores ahead (islands, or a program without the
    span) reads 0, not nothing."""
    assert READ(record([search(A), search(B)])) == 0.0


def test_share_reads_nothing_without_a_completed_search():
    assert READ(record([search(CUT, cut=True)])) is None
    assert READ(record([])) is None
    bare = {"artifact": {"backend_stats": {"batch_time_s": 1.0}}}
    assert READ(record([bare, {"artifact": None, "error": "X: y"}])) is None
