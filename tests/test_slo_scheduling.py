"""SLO-tiered quantum scheduling + admission control (ISSUE-6).

(1) Bursty/diurnal arrival generation: deterministic per seed at
thousands of requests, with the burstiness knob actually raising
inter-arrival variance at equal offered load.
(2) Tier semantics: deadline-carrying QueryRecords, per-tier metrics
from the one shared summarize(), qps_at_qos as the headline rate.
(3) Preemption ordering: an interactive-tier admission arriving
mid-stream runs its first prefill chunk before any further batch-tier
decode quantum — and every served stream stays identical to a plain
per-request greedy reference (scheduling reorders, never corrupts).
(4) Admission control: shed/deferred queries are counted, never
silently dropped.
(5) The unified serving API: ``step()``/``step_quantum`` ride the
begin/finish path, and ``run_to_completion`` defaults to fused dispatch
with identical tokens.
"""
import math

import numpy as np
import pytest

from repro.core import cost_model as cm
from repro.core.qos import (DEFAULT_TIERS, TIER_ORDER, QueryRecord,
                            TierMetrics, tier_spec, summarize)
from repro.core.scheduler import FixedBlockPolicy, VeltairPolicy
from repro.serving import (AdmissionController, OnlineRuntime, Workload,
                           build_paper_plans, diurnal_workload,
                           gamma_poisson_workload, synth_prompts)
from repro.serving.engine import Request, ServingEngine

from greedy_ref import manual_greedy

HW = cm.CPU_3990X
TENANTS = ["resnet50", "googlenet"]
TIERS = {"resnet50": "interactive", "googlenet": "batch"}


@pytest.fixture(scope="module")
def plans():
    return build_paper_plans(TENANTS, HW)


@pytest.fixture(scope="module")
def engine_factory():
    import jax

    from repro.configs import get_reduced_config
    from repro.models import build_model

    cfg = get_reduced_config("gemma-2b")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))

    def make(**kw):
        kw.setdefault("batch_slots", 2)
        kw.setdefault("max_len", 32)
        return ServingEngine(cfg, params, **kw)
    return make


# ---------------------------------------------------------------------------
# (1) bursty / diurnal arrival generation
# ---------------------------------------------------------------------------
def test_gamma_poisson_is_deterministic_and_bursty():
    n = 3000
    a1 = gamma_poisson_workload(TENANTS, 500.0, n, burstiness=4.0, seed=9)
    a2 = gamma_poisson_workload(TENANTS, 500.0, n, burstiness=4.0, seed=9)
    assert a1 == a2, "same seed must replay identically"
    assert len(a1) == n
    times = np.array([t for t, _ in a1])
    assert np.all(np.diff(times) >= 0), "arrivals must be sorted"
    # equal offered load, higher variance: the burstiness knob must raise
    # the coefficient of variation of inter-arrival gaps above Poisson's
    smooth = gamma_poisson_workload(TENANTS, 500.0, n, burstiness=0.0,
                                    seed=9)
    g_b = np.diff(times)
    g_s = np.diff([t for t, _ in smooth])
    cv_b = g_b.std() / g_b.mean()
    cv_s = np.std(g_s) / np.mean(g_s)
    assert cv_b > 1.5 * cv_s, (cv_b, cv_s)
    # mean offered load stays comparable (within 2x either way)
    assert 0.5 < (times[-1] / ([t for t, _ in smooth][-1])) < 2.0


def test_diurnal_workload_modulates_rate():
    n = 4000
    arr = diurnal_workload(["m"], 1000.0, n, period_s=1.0, floor=0.1,
                           seed=3)
    assert arr == diurnal_workload(["m"], 1000.0, n, period_s=1.0,
                                   floor=0.1, seed=3)
    phase = np.array([t for t, _ in arr]) % 1.0
    # rate(t) peaks at phase 0.25 and troughs at 0.75
    peak = np.sum((phase > 0.0) & (phase < 0.5))
    trough = np.sum((phase > 0.5) & (phase < 1.0))
    assert peak > 2 * trough, (peak, trough)


def test_workload_constructors_carry_tiers():
    wl = Workload.bursty(TENANTS, 300, 50, seed=1, tiers=TIERS)
    assert wl.n_queries == 50
    assert wl.tier_of("resnet50") == "interactive"
    assert wl.tier_of("googlenet") == "batch"
    untiered = Workload.poisson(TENANTS, 300, 10)
    assert untiered.tier_of("resnet50") is None
    # trace replay sorts a recorded stream
    wl2 = Workload.replay([(0.5, "a"), (0.1, "b")], tiers={"a": "standard"})
    assert [t for t, _ in wl2.arrivals] == [0.1, 0.5]


# ---------------------------------------------------------------------------
# (2) tier semantics and the shared record schema
# ---------------------------------------------------------------------------
def test_tier_specs_scale_deadlines_in_order():
    scales = [DEFAULT_TIERS[t].deadline_scale for t in TIER_ORDER]
    assert scales == sorted(scales), "interactive tightest, batch loosest"
    assert DEFAULT_TIERS["batch"].sheddable is False
    assert DEFAULT_TIERS["interactive"].sheddable is True
    assert tier_spec(None) is DEFAULT_TIERS["standard"]
    with pytest.raises(ValueError):
        tier_spec("platinum")


def test_query_record_deadline_vs_legacy_satisfaction():
    legacy = QueryRecord("t", arrival=0.0, finish=0.5, qos_s=1.0)
    assert legacy.satisfied and legacy.deadline is None
    tiered = QueryRecord("t", arrival=0.0, finish=0.5, qos_s=0.1,
                         tier="batch", deadline=0.8)
    assert tiered.satisfied, "deadline overrides qos_s when set"
    late = QueryRecord("t", arrival=0.0, finish=0.9, qos_s=10.0,
                       tier="interactive", deadline=0.8)
    assert not late.satisfied


def test_summarize_reports_per_tier_and_qps_at_qos():
    recs = [QueryRecord("a", 0.0, 0.5, 1.0, tier="interactive",
                        deadline=1.0),
            QueryRecord("a", 0.0, 2.0, 1.0, tier="interactive",
                        deadline=1.0),
            QueryRecord("b", 0.0, 1.0, 1.0, tier="batch", deadline=8.0)]
    m = summarize(recs, 10.0, 0.0, 1.0, 2.0, shed=2, deferred=3)
    assert set(m.per_tier) == {"interactive", "batch"}
    assert isinstance(m.per_tier["interactive"], TierMetrics)
    assert m.per_tier["interactive"].n_queries == 2
    assert m.per_tier["interactive"].qos_rate == pytest.approx(0.5)
    assert m.per_tier["batch"].qos_rate == 1.0
    assert m.shed_queries == 2 and m.deferred_queries == 3
    # 2 satisfied over a 2.0s span
    assert m.qps_at_qos == pytest.approx(1.0)
    empty = summarize([], 10.0, 0.0, 0.0, 0.0, shed=5)
    assert empty.shed_queries == 5 and empty.qps_at_qos == 0.0


def test_metrics_schema_parity_online_vs_cluster(plans, engine_factory):
    """Per-tier qos_rate/TTFT/p99 report through the SAME schema from
    both runtimes: one QueryRecord shape, one summarize()."""
    from repro.serving import ClusterRuntime, build_cluster

    wl = Workload.bursty(TENANTS, 300, 12, prompt_len=4, max_new_tokens=2,
                         seed=4, tiers=TIERS)
    rt = OnlineRuntime(engine_factory(), VeltairPolicy(HW), plans, HW)
    m_online = rt.serve(wl)

    archs = ["gemma-2b", "mamba2-780m"]
    ctiers = {"gemma-2b": "interactive", "mamba2-780m": "batch"}
    cluster = ClusterRuntime(
        build_cluster(archs, HW, batch_slots=2, max_len=32, tiers=ctiers),
        VeltairPolicy(HW), HW)
    wl_c = Workload.bursty(archs, 300, 12, prompt_len=4, max_new_tokens=2,
                           seed=4)
    m_cluster = cluster.serve(wl_c).aggregate

    for m in (m_online, m_cluster):
        assert type(m).__name__ == "ServingMetrics"
        assert m.per_tier, "tiered serve must report per-tier slices"
        for tm in m.per_tier.values():
            assert isinstance(tm, TierMetrics)
            assert math.isfinite(tm.p99_latency_s)
        assert m.qps_at_qos > 0.0
    # tier labels land on the records themselves, identically shaped
    for recs in (rt.records, cluster.outputs):
        assert recs
    assert {r.tier for r in rt.records} <= set(TIER_ORDER)


# ---------------------------------------------------------------------------
# (3) preemption ordering + token identity (the tentpole property)
# ---------------------------------------------------------------------------
def test_interactive_prefill_preempts_batch_decode(plans, engine_factory):
    """A batch-tier stream is decoding; an interactive request arrives
    mid-stream.  Its first prefill chunk must be the next scheduled
    quantum — before any further batch-tier decode quantum."""
    wl = Workload(
        [(0.0, "googlenet"), (0.004, "resnet50")],
        prompt_len=12, max_new_tokens=8, tiers=TIERS)
    rt = OnlineRuntime(engine_factory(prefill_chunk_len=4),
                       FixedBlockPolicy(HW, 1), plans, HW)
    rt.serve(wl)
    t_arr = 0.004
    after = [ev for ev in rt.sched_trace if ev[-1] >= t_arr]
    assert after, "trace must cover the interactive arrival"
    first = after[0]
    assert first[0] == "prefill" and first[2] == "interactive", (
        f"interactive admission must preempt batch decode, got {first} "
        f"(trace after arrival: {after[:5]})")
    # and batch decode work did exist to preempt
    assert any(ev[0] == "decode" for ev in rt.sched_trace)


def test_slo_and_fifo_schedules_are_token_identical(plans, engine_factory):
    """Scheduling reorders quanta, never corrupts streams: every
    per-request output of a tiered bursty serve equals the plain
    one-request-at-a-time greedy reference."""
    wl = Workload.bursty(TENANTS, 400, 16, prompt_len=6, max_new_tokens=3,
                         seed=6, prompt_len_spread=3, tiers=TIERS)
    engine = engine_factory()
    rt = OnlineRuntime(engine, VeltairPolicy(HW), plans, HW)
    m = rt.serve(wl)
    assert m.n_queries == wl.n_queries
    assert set(rt.outputs) == set(range(wl.n_queries))
    prompts = synth_prompts(wl.n_queries, wl.prompt_len,
                            engine.cfg.vocab_size, wl.seed)
    lens = wl.prompt_lengths()
    assert len(set(lens)) > 1, "spread must mix lengths"
    for rid, out in rt.outputs.items():
        want = manual_greedy(engine.model, engine.params,
                             prompts[rid, :lens[rid]], wl.max_new_tokens,
                             engine.max_len)
        assert out == want, rid


# ---------------------------------------------------------------------------
# (4) admission control: counted, never silently dropped
# ---------------------------------------------------------------------------
def test_admission_control_sheds_and_defers_under_overload(
        plans, engine_factory):
    # one slot, a pile of simultaneous interactive arrivals: the ones
    # whose deadline is already hopeless at admission are shed
    wl = Workload([(i * 1e-4, "resnet50") for i in range(12)],
                  prompt_len=8, max_new_tokens=4,
                  tiers={"resnet50": "interactive"})
    rt = OnlineRuntime(engine_factory(batch_slots=1), VeltairPolicy(HW),
                       plans, HW, admission=AdmissionController())
    m = rt.serve(wl)
    assert m.shed_queries == rt.shed > 0
    assert m.deferred_queries == rt.deferred > 0
    # every arrival is accounted for: served or shed, nothing vanishes
    assert m.n_queries + m.shed_queries == wl.n_queries
    # shed requests never produced records
    assert len(rt.records) == m.n_queries


def test_no_admission_controller_means_no_shedding(plans, engine_factory):
    wl = Workload([(i * 1e-4, "resnet50") for i in range(8)],
                  prompt_len=8, max_new_tokens=4,
                  tiers={"resnet50": "interactive"})
    rt = OnlineRuntime(engine_factory(batch_slots=1), VeltairPolicy(HW),
                       plans, HW)
    m = rt.serve(wl)
    assert m.shed_queries == 0
    assert m.n_queries == wl.n_queries


def test_tier_qos_ordering_under_overload(plans, engine_factory):
    """Under sustained overload the SLO scheduler must privilege the
    tight tier: interactive qos_rate >= batch qos_rate (deterministic
    virtual-time serve)."""
    wl = Workload.bursty(TENANTS, 900, 30, burstiness=4.0, prompt_len=6,
                         max_new_tokens=4, seed=11, tiers=TIERS)
    rt = OnlineRuntime(engine_factory(), VeltairPolicy(HW), plans, HW,
                       admission=AdmissionController())
    m = rt.serve(wl)
    pt = m.per_tier
    assert "interactive" in pt and "batch" in pt
    assert pt["interactive"].qos_rate >= pt["batch"].qos_rate


# ---------------------------------------------------------------------------
# (5) the unified serving API
# ---------------------------------------------------------------------------
def test_run_to_completion_fused_matches_per_step(engine_factory):
    rng = np.random.default_rng(1)
    prompts = [rng.integers(0, 4096, n).astype(np.int32) for n in (5, 9, 7)]

    def reqs():
        return [Request(rid=i, prompt=p, max_new_tokens=4)
                for i, p in enumerate(prompts)]
    fused_engine = engine_factory()
    done_fused = fused_engine.run_to_completion(reqs())
    per_step_engine = engine_factory()
    done_step = per_step_engine.run_to_completion(reqs(), fused=False)
    assert len(done_fused) == len(done_step) == 3
    by_rid = lambda rs: {r.rid: r.output for r in rs}          # noqa: E731
    assert by_rid(done_fused) == by_rid(done_step)
    # fused default actually coarsened the host boundary
    assert fused_engine.tokens_per_sync > per_step_engine.tokens_per_sync
    assert fused_engine.quantum_calls > 0
    assert per_step_engine.quantum_calls == 0, \
        "per-step dispatch must not count as fused quantum calls"


def test_step_is_a_thin_wrapper_over_the_quantum_path(engine_factory):
    engine = engine_factory()
    rng = np.random.default_rng(2)
    req = Request(rid=0, prompt=rng.integers(
        0, engine.cfg.vocab_size, 4).astype(np.int32), max_new_tokens=3)
    engine.admit_request(req, drain=True)
    syncs0, calls0 = engine.host_syncs, engine.quantum_calls
    engine.step()
    assert engine.host_syncs == syncs0 + 1, "one sync per per-step dispatch"
    assert engine.quantum_calls == calls0, "step() is not a fused quantum"
    handle = engine.begin_quantum(2)
    assert handle is not None and handle.steps <= 2
    engine.finish_quantum(handle)
    assert engine.quantum_calls == calls0 + 1
