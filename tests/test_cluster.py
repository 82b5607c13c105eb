"""Co-location cluster path: ISSUE-3 acceptance properties.

(1) per-tick unit partitioning never exceeds (and under saturation
reaches) ``hw.n_units``, and every grant is returned; (2) per-engine
interference levels diverge under asymmetric load — the lightly-loaded
engine sees its heavy co-runner's pressure, not its own; (3) the
calibrated LinearProxy agrees with the oracle on calibration data, so
routing online decisions through it is sound; (4) a smoke co-location
serve completes in Pallas interpret mode with per-engine version caches;
(5) every stream a co-located serve retires equals the plain
per-request greedy reference; (6) paged engines behind the cluster share
a tenant's common prompt prefix through the page pool's prefix index.
"""
import numpy as np
import pytest

from repro.core import cost_model as cm
from repro.core.interference import (Interference, calibrate_proxy,
                                     read_counters, synthesize_counters)
from repro.core.scheduler import ModelWisePolicy, PremaPolicy, VeltairPolicy
from repro.kernels import dispatch
from repro.serving import (AdmissionController, ClusterRuntime, Workload,
                           build_cluster, cluster_plans)

from greedy_ref import manual_greedy

HW = cm.CPU_3990X
ARCHS = ["gemma-2b", "mamba2-780m"]


@pytest.fixture(scope="module")
def plans():
    return cluster_plans(ARCHS, HW)


@pytest.fixture(scope="module")
def cluster_factory(plans):
    import jax

    from repro.configs import get_reduced_config
    from repro.models import build_model
    from repro.serving import EngineTenant
    from repro.serving.engine import ServingEngine

    built = {}
    for arch in ARCHS:
        cfg = get_reduced_config(arch)
        model = build_model(cfg)
        built[arch] = (cfg, model.init(jax.random.PRNGKey(0)))

    def make(batch_slots=2):
        return [EngineTenant(
            name=a, plan=plans[a],
            engine=ServingEngine(built[a][0], built[a][1],
                                 batch_slots=batch_slots, max_len=32,
                                 version_sets=plans[a].version_sets))
            for a in ARCHS]
    return make


@pytest.fixture(autouse=True)
def _clean_overrides():
    yield
    dispatch.clear_tile_overrides()
    dispatch.set_mode("xla")


def test_partition_conserves_units(plans, cluster_factory):
    wl = Workload.poisson(ARCHS, 120, 14, prompt_len=4, max_new_tokens=3,
                          seed=1)
    runtime = ClusterRuntime(cluster_factory(), VeltairPolicy(HW), HW)
    m = runtime.serve(wl)
    assert m.aggregate.n_queries == wl.n_queries
    sums = [sum(p.values()) for p in m.partition_trace]
    assert max(sums) <= HW.n_units
    assert m.pool_peak_used <= HW.n_units
    # work-conserving under contention: some tick saturated the pool
    assert max(sums) > HW.n_units // 2
    # every grant was returned: the pool is whole again
    assert runtime.pool.free == runtime.pool.total
    # both engines actually got scheduling quanta and level decisions
    assert all(m.quanta[a] >= 1 for a in ARCHS)
    assert all(len(m.level_traces[a]) == m.quanta[a] for a in ARCHS)


def test_per_engine_levels_diverge_under_asymmetric_load(cluster_factory):
    """Victim semantics: the *lightly* loaded engine reads its heavy
    co-runner's slots as pressure, while the heavy engine sees almost
    none — so its level trace must sit strictly higher."""
    heavy, light = ARCHS
    arrivals = []
    t = 0.0
    for i in range(14):                       # keep the heavy engine full
        arrivals.append((t + i * 1e-3, heavy))
    arrivals.append((2e-3, light))
    arrivals.append((8e-3, light))
    wl = Workload(sorted(arrivals), prompt_len=4, max_new_tokens=4, seed=0)
    runtime = ClusterRuntime(cluster_factory(batch_slots=4),
                             VeltairPolicy(HW), HW)
    m = runtime.serve(wl)
    lv_heavy = m.mean_levels[heavy]
    lv_light = m.mean_levels[light]
    assert lv_light > lv_heavy, (
        f"light tenant should read co-runner pressure: {m.mean_levels}")
    # and the decisions reached the engines as distinct code versions
    assert len(m.level_traces[light]) >= 1
    assert m.aggregate.n_queries == wl.n_queries


def test_proxy_matches_oracle_on_calibration_data():
    proxy, counters, levels = calibrate_proxy(HW)
    assert proxy.r2 > 0.9
    preds = np.array([proxy.predict(c[:2]) for c in counters])
    assert float(np.abs(preds - levels).mean()) < 0.08
    # the policy's counter hook is the same proxy: a synthetic sample at a
    # known pressure must come back near that pressure
    policy = VeltairPolicy(HW, proxy=proxy)
    rng = np.random.default_rng(7)
    errs = []
    for x in (0.2, 0.5, 0.9):
        truth = Interference.from_level(x)
        vals = synthesize_counters(HW, truth, rng)
        sample = type("S", (), {"values": vals, "t": 0.0, "truth": truth})
        errs.append(abs(policy.level_from_counters(sample) - x))
    assert max(errs) < 0.15
    # ground truth stays out of the online decision: only the sample's
    # counter values matter
    sample_no_truth = type("S", (), {"values": vals, "t": 0.0,
                                     "truth": None})
    assert policy.level_from_counters(sample_no_truth) == \
        policy.level_from_counters(sample)


def test_read_counters_exposes_cosrunner_pressure_only():
    from repro.core.interference import RunningDemand
    rng = np.random.default_rng(0)
    demands = [RunningDemand(tenant=0, bw=0.5, cache=0.8, ici=0.0,
                             start=0.0, finish=10.0),
               RunningDemand(tenant=1, bw=0.1, cache=0.1, ici=0.0,
                             start=0.0, finish=10.0)]
    s0 = read_counters(HW, 0, demands, 1.0, rng)     # victim 0: sees only 1
    s1 = read_counters(HW, 1, demands, 1.0, rng)     # victim 1: sees only 0
    assert s1.truth.cache > s0.truth.cache
    assert s0.truth.bw == pytest.approx(0.1)
    assert s1.truth.bw == pytest.approx(0.5)


def test_baselines_share_loop_but_pin_solo_version(plans, cluster_factory):
    wl = Workload.poisson(ARCHS, 100, 8, prompt_len=4, max_new_tokens=2,
                          seed=2)
    for policy in (ModelWisePolicy(HW), PremaPolicy(HW)):
        runtime = ClusterRuntime(cluster_factory(), policy, HW)
        m = runtime.serve(wl)
        assert m.aggregate.n_queries == wl.n_queries
        assert all(lv == 0.0 for tr in m.level_traces.values() for lv in tr)
    # PREMA quanta are exclusive: no tick grants units to both engines
    for part in runtime.partition_trace:
        assert sum(1 for g in part.values() if g > 0) <= 1


def test_cluster_rejects_unknown_tenant(cluster_factory):
    wl = Workload([(0.0, "not-a-model")])
    runtime = ClusterRuntime(cluster_factory(), VeltairPolicy(HW), HW)
    with pytest.raises(KeyError):
        runtime.serve(wl)


def test_cluster_smoke_interpret_mode(plans, cluster_factory):
    """Co-location on the Pallas interpret path: distinct engines keep
    distinct compiled version entries and every query completes."""
    dispatch.set_mode("interpret")
    tenants = cluster_factory()
    wl = Workload.poisson(ARCHS, 150, 4, prompt_len=2, max_new_tokens=2,
                          seed=3)
    runtime = ClusterRuntime(tenants, VeltairPolicy(HW), HW)
    m = runtime.serve(wl)
    assert m.aggregate.n_queries == wl.n_queries
    assert m.aggregate.qos_rate >= 0.0
    for t in tenants:
        assert len(t.engine.version_cache) >= 1


def test_cluster_serve_matches_per_request_reference(cluster_factory):
    """The loop the benchmark runs: a tiered bursty serve of two
    co-located families (linear KV, SSM state) with mixed prompt lengths
    retires every request with the reference's tokens."""
    tenants = cluster_factory()
    wl = Workload.bursty(ARCHS, 300, 8, prompt_len=9, max_new_tokens=6,
                         seed=8, prompt_len_spread=5,
                         tiers={ARCHS[0]: "interactive",
                                ARCHS[1]: "batch"})
    runtime = ClusterRuntime(tenants, VeltairPolicy(HW), HW)
    m = runtime.serve(wl)
    assert m.aggregate.n_queries == wl.n_queries
    assert set(runtime.outputs) == set(range(wl.n_queries))
    prompts = runtime.tenant_prompts(wl)
    lens = wl.prompt_lengths()
    by_name = {t.name: t.engine for t in tenants}
    for rid, (_, name) in enumerate(sorted(wl.arrivals)):
        eng = by_name[name]
        want = manual_greedy(eng.model, eng.params,
                             prompts[name][rid, :lens[rid]],
                             wl.max_new_tokens, eng.max_len)
        assert runtime.outputs[rid] == want, (rid, name)
    assert sum(m.prefill_quanta.values()) >= wl.n_queries
    assert max(m.tokens_per_sync.values()) > 1.0, "fused quanta ran"


def test_cluster_paged_engines_share_prompt_prefixes():
    """Each tenant's prompts open with one system prompt
    (``shared_prefix_len``); requests resident together on a paged
    engine borrow its published pages instead of prefilling them."""
    archs = ["gemma-2b", "starcoder2-3b"]
    tenants = build_cluster(archs, HW, batch_slots=2, max_len=32,
                            page_size=8)
    runtime = ClusterRuntime(tenants, VeltairPolicy(HW), HW,
                             admission=AdmissionController())
    wl = Workload.bursty(archs, 200.0, 16, prompt_len=12, max_new_tokens=4,
                         seed=5, shared_prefix_len=10,
                         tiers={archs[0]: "interactive", archs[1]: "batch"})
    m = runtime.serve(wl)
    assert m.aggregate.n_queries + m.aggregate.shed_queries == wl.n_queries
    assert sum(s["shared_hits"] for s in m.page_stats.values()) >= 1, \
        m.page_stats
