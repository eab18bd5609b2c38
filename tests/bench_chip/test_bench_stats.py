"""Tail arithmetic: every request counts from its due time, and a stall
inside the window moves the tail."""

from __future__ import annotations

import math

import pytest

import benchtiny  # noqa: F401
import stats


def test_percentile_interpolates():
    assert stats.percentile([1, 2, 3, 4, 5], 0.5) == 3
    assert stats.percentile([0, 10], 0.95) == pytest.approx(9.5)
    assert stats.percentile([7], 0.95) == 7


def test_ttft_counts_from_due_time_over_all_requests():
    due = {0: 0.0, 1: 1.0, 2: 2.0, 3: 3.0}
    first = {0: 0.1, 1: 1.1, 2: 2.1}          # request 3 never answered
    t = stats.ttfts(due, first)
    assert sorted(t)[:3] == pytest.approx([0.1, 0.1, 0.1])
    assert math.isinf(max(t))


def test_a_stall_inside_the_window_moves_the_tail():
    due = {u: u * 0.1 for u in range(100)}
    first = {u: t + 0.05 for u, t in due.items()}
    calm = stats.percentile(stats.ttfts(due, first), 0.95)
    # a 1 s stall at t = 5 s: everything due in it waits for its end, and
    # the submits were late, but the clock starts at the due time
    stalled = {u: max(t + 0.05, 6.0) if 5.0 <= t < 6.0 else t + 0.05
               for u, t in due.items()}
    assert stats.percentile(stats.ttfts(due, stalled), 0.95) > 10 * calm


def test_token_gaps_and_tokens_inside_the_window():
    times = {0: [0.5, 1.0, 2.5], 1: [1.5, 1.6]}
    assert sorted(stats.token_gaps(times, 1.0, 2.0)) == pytest.approx(
        [0.1, 0.5])
    assert stats.tokens_between(times, 1.0, 2.0) == 3
