"""The trace reduction on a recorded chip trace and on hand-made events."""
from __future__ import annotations

import json

import pytest

from benchtools import ROOT
from bench.lib import trace

DATA = ROOT / "tests/bench/data"
EXPECTED = json.loads((DATA / "small_trace.expected.json").read_text())


@pytest.fixture(scope="module")
def reduced():
    return trace.reduce(trace.load(str(DATA / "small_trace.xplane.pb.gz")))


def test_recorded_trace_busy_union_and_idle_share(reduced):
    assert reduced.n_devices == 1
    assert reduced.busy_s == pytest.approx(EXPECTED["busy_s"], rel=1e-9)
    window = EXPECTED["window_s"]
    assert 0 < reduced.busy_s <= window
    assert 100 * (1 - reduced.busy_s / window) == pytest.approx(
        EXPECTED["idle_share_pct"], rel=1e-6)


def test_recorded_trace_program_attribution(reduced):
    assert reduced.program_runs == EXPECTED["program_runs"]
    for kind, s in EXPECTED["program_s"].items():
        assert reduced.program_s[kind] == pytest.approx(s, rel=1e-9)
    # every prefill chunk of this window ran the jitted prefill_chunk
    assert reduced.program_s["prefill"] == pytest.approx(reduced.busy_s,
                                                         rel=1e-3)


def test_recorded_trace_breakdown(reduced):
    name, secs = EXPECTED["top_op"]
    assert reduced.ops[0][0] == name
    assert reduced.ops[0][1] == pytest.approx(secs, rel=1e-9)
    assert len(reduced.ops) <= 10 and len(reduced.idle_gaps) <= 10
    assert sum(v for _, v in reduced.ops) <= reduced.busy_s
    assert [g[0] for g in reduced.idle_gaps] == \
        [g[0] for g in EXPECTED["idle_gaps"]]


def test_union_and_self_times_by_hand():
    assert trace._union([(0, 5), (3, 8), (10, 12), (11, 11)]) == \
        [(0, 8), (10, 12)]
    events = [("%while.1 = ...", 0, 100), ("%fusion.2 = ...", 10, 40),
              ("%fusion.3 = ...", 50, 60), ("%copy.4 = ...", 120, 130)]
    got = {trace._op(n): t for n, _, t in trace._self_times(events)}
    assert got == {"while.1": 60, "fusion.2": 30, "fusion.3": 10,
                   "copy.4": 10}


def test_program_kinds_follow_the_jitted_names():
    assert trace.kind_of("jit_prefill_chunk(1234)") == "prefill"
    assert trace.kind_of("jit_qfn(99)") == "decode"
    assert trace.kind_of("jit_write(7)") is None
