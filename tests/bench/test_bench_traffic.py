"""The traffic generator: deterministic per seed, the same sizes for every
seed, and the mean rate and mix its traffic file asks for."""
from __future__ import annotations

import json

import numpy as np
import pytest

from bench.lib import traffic
from benchtools import ROOT

CHAT = {"kind": "open_loop", "rate_per_s": 0.32, "burstiness": 2.0,
        "interval_s": 2.0,
        "popularity": {"starcoder2-3b": 0.75, "mamba2-780m": 0.25},
        "prompt_len": [64, 512], "output_len": 64}
BACKLOG = json.loads((ROOT / "bench/traffic/gen-backlog.json").read_text())
TENANTS = list(CHAT["popularity"])
BIG_SEED = 2**31 + 12345


def test_same_seed_same_requests():
    a = traffic.generate(CHAT, TENANTS, 51.0, BIG_SEED)
    b = traffic.generate(CHAT, TENANTS, 51.0, BIG_SEED)
    assert a == b


@pytest.mark.parametrize("kind", [dict(CHAT, group=8), BACKLOG])
def test_every_seed_gets_the_same_work_in_another_order(kind):
    """Sizes and their order are fixed by the traffic file (in groups);
    the seed draws the open loop's arrivals."""
    tenants = TENANTS if "popularity" in kind else ["starcoder2-3b"]
    a = traffic.generate(kind, tenants, 2000.0, 7)
    b = traffic.generate(kind, tenants, 2000.0, BIG_SEED)
    g = kind["group"]
    n = min(len(a), len(b)) // g * g        # whole groups of both
    size = [(r.tenant, r.prompt_len, r.output_len) for r in a]
    assert size[:n] == [(r.tenant, r.prompt_len, r.output_len)
                        for r in b][:n]
    same_times = [r.due_s for r in a] == [r.due_s for r in b]
    assert same_times == (kind["kind"] == "backlog")


def test_open_loop_rate_and_mix():
    t = dict(CHAT, rate_per_s=2.0)
    reqs = traffic.generate(t, TENANTS, 2000.0, 3)
    assert abs(len(reqs) / 2000.0 - 2.0) < 0.3      # Gamma(0.5, 2) bursts
    due = [r.due_s for r in reqs]
    assert due == sorted(due) and len(set(due)) == len(due)
    assert 0.0 <= due[0] and due[-1] < 2000.0
    share = sum(r.tenant == "starcoder2-3b" for r in reqs) / len(reqs)
    assert abs(share - 0.75) <= 1.0 / len(reqs)
    lens = np.array([r.prompt_len for r in reqs])
    assert lens.min() >= 64 and lens.max() <= 512
    assert abs(lens.mean() - 288.0) < 5.0
    assert {r.output_len for r in reqs} == {64}


def test_backlog_all_due_at_start():
    reqs = traffic.generate(BACKLOG, ["starcoder2-3b"], 51.0, 5)
    assert len(reqs) == BACKLOG["n_requests"]
    assert {r.due_s for r in reqs} == {0.0}
    (plo, phi), (olo, ohi) = BACKLOG["prompt_len"], BACKLOG["output_len"]
    assert plo <= min(r.prompt_len for r in reqs)
    assert max(r.prompt_len for r in reqs) <= phi
    assert olo <= min(r.output_len for r in reqs)
    assert max(r.output_len for r in reqs) <= ohi
    g = BACKLOG["group"]
    for key in ("prompt_len", "output_len"):  # every group: the same work
        first = sorted(getattr(r, key) for r in reqs[:g])
        for i in range(0, len(reqs) - g + 1, g):
            assert sorted(getattr(r, key) for r in reqs[i:i + g]) == first
    assert [r.output_len for r in reqs[:g]] != [r.output_len
                                                 for r in reqs[g:2 * g]]
    # prompt and output lengths are not paired in step
    p = np.array([r.prompt_len for r in reqs[:g]], float)
    o = np.array([r.output_len for r in reqs[:g]], float)
    assert abs(np.corrcoef(p, o)[0, 1]) < 0.5


def test_lengths_spread_evenly():
    assert traffic.spread_lengths(64, 1024, 4) == [184, 424, 664, 904]
    assert traffic.length_range(64) == (64, 64)
    assert traffic.length_range([32, 128]) == (32, 128)


def test_popularity_must_name_the_served_models():
    with pytest.raises(ValueError):
        traffic.generate(CHAT, ["starcoder2-3b"], 10.0, 1)


@pytest.mark.parametrize("n", [1, 3, 16, 17])
def test_tenant_counts_exact(n):
    c = traffic.tenant_counts({"a": 0.75, "b": 0.25}, n)
    assert sum(c.values()) == n
    assert abs(c["a"] - 0.75 * n) < 1
