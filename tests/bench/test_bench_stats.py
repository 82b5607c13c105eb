"""End-to-end metric arithmetic, including requests unfinished at the
window's end."""
from __future__ import annotations

import pytest

from bench.lib.stats import Record, percentile, tokens_by, tpot, ttft


def rec(due, first=None, deliveries=(), want=5):
    r = Record(rid=0, tenant="m", due=due, prompt_len=8, want=want,
               first=first)
    r.deliveries = list(deliveries)
    return r


def test_percentile_interpolates():
    assert percentile(range(11), 90) == pytest.approx(9.0)
    assert percentile([1.0, 2.0], 90) == pytest.approx(1.9)


def test_finished_request():
    r = rec(10.0, first=11.0, deliveries=[(11.0, 1), (11.5, 2), (12.0, 2)])
    assert r.finished
    assert ttft(r, end=99.0) == pytest.approx(1.0)
    assert tpot(r, end=99.0) == pytest.approx(1.0 / 4)


def test_unfinished_request_enters_the_tail_at_what_it_reached():
    r = rec(10.0, first=11.0, deliveries=[(11.0, 1), (12.0, 1)])
    assert not r.finished
    assert tpot(r, end=15.0) == pytest.approx(4.0 / 2)
    never = rec(10.0)
    assert ttft(never, end=15.0) == pytest.approx(5.0)
    assert tpot(never, end=15.0) == pytest.approx(5.0)


def test_tokens_by_cuts_at_the_deadline():
    a = rec(0.0, first=1.0, deliveries=[(1.0, 1), (2.0, 16), (3.0, 16)])
    b = rec(0.0, first=2.5, deliveries=[(2.5, 1), (3.5, 16)])
    assert tokens_by([a, b], 3.0) == 1 + 16 + 16 + 1
    assert tokens_by([a, b], 0.5) == 0
