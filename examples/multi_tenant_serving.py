"""Flagship example: multi-tenant serving with VELTAIR vs baselines.

    PYTHONPATH=src python examples/multi_tenant_serving.py \
        [--no-online] [--no-colocate]

Part 1 (simulator): compiles multi-version plans for the paper's MLPerf
mix, then serves a Poisson query stream under every scheduling policy and
prints the QoS table (Fig. 12-style).  All scheduling decisions run the
production repro.core code; time advancement is simulated.

Part 2 (online runtime): replays one tenant mix through the *real* JAX
ServingEngine with the VELTAIR policy in the loop — every engine step the
runtime polls the synthesized performance counters and the policy's
proxy maps them to the interference level that swaps the active kernel
code version (tile overrides via repro.kernels.dispatch) — and prints
the engine-vs-simulator ServingMetrics side by side.

Part 3 (co-location cluster): three *different* real models share the
unit pool under one global scheduler; see colocation_demo below for the
step-by-step walkthrough.

Part 4 (speculative decode): the same engine serving the same prompts
twice — plain fused quanta vs draft -> batched-verify -> rollback — and
asserting the streams are token-identical while speculation emits
multiple tokens per dispatch.
"""
import argparse
import time

from repro.configs.paper_suite import WORKLOAD_CLASSES, paper_models
from repro.core import cost_model as cm
from repro.core.qos import compare_metrics
from repro.core.scheduler import (LayerWisePolicy, ModelWisePolicy,
                                  PremaPolicy, VeltairPolicy)
from repro.serving import Simulator, build_paper_plans, poisson_workload


def sim_policy_table(hw, plans, models, weights):
    policies = [
        ("model-wise FCFS", lambda: ModelWisePolicy(hw)),
        ("layer-wise (Planaria-ported)", lambda: LayerWisePolicy(hw)),
        ("PREMA (temporal)", lambda: PremaPolicy(hw)),
        ("VELTAIR-AS", lambda: VeltairPolicy(hw, adaptive_compile=False)),
        ("VELTAIR-AC", lambda: VeltairPolicy(hw, adaptive_schedule=False)),
        ("VELTAIR-FULL", lambda: VeltairPolicy(hw)),
    ]
    print(f"\n{'policy':32s} " + " ".join(f"qps={q:<5d}" for q in (60, 140,
                                                                   220)))
    for name, pf in policies:
        rates = []
        for qps in (60, 140, 220):
            wl = poisson_workload(models, qps, 400, seed=1, weights=weights)
            m = Simulator(hw, plans, pf()).run(wl)
            rates.append(m.qos_rate)
        print(f"{name:32s} " + " ".join(f"{r:.2f}    " for r in rates))


def online_engine_demo(hw):
    """The real JAX engine under VeltairPolicy: one tenant mix replayed
    through simulator AND engine, metrics side by side."""
    import jax

    from repro.configs import get_reduced_config
    from repro.models import build_model
    from repro.serving import (OnlineRuntime, Workload,
                               engine_version_sets,
                               replay_through_simulator)
    from repro.serving.engine import ServingEngine

    tenants = ["resnet50", "googlenet"]
    plans = build_paper_plans(tenants, hw)
    policy = VeltairPolicy(hw)

    cfg = get_reduced_config("gemma-2b")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    engine = ServingEngine(cfg, params, batch_slots=2, max_len=32,
                           version_sets=engine_version_sets(plans))

    # mixed-length prompts (spread > 0): slots decode at their own
    # positions, no alignment needed
    wl = Workload.poisson(tenants, 60, 24, prompt_len=4, max_new_tokens=4,
                          seed=1, prompt_len_spread=2)
    # AOT-compile every interference level's code version: each switch
    # during serve() is then a dictionary swap, never a re-jit stall
    engine.warmup()
    t0 = time.time()
    runtime = OnlineRuntime(engine, policy, plans, hw)
    m_eng = runtime.serve(wl)
    wall = time.time() - t0
    m_sim = replay_through_simulator(wl, hw, plans, VeltairPolicy(hw))

    lv = runtime.level_trace
    print(f"\nonline runtime: {m_eng.n_queries} queries through the real "
          f"engine in {wall:.1f}s wall ({runtime.steps} decode steps in "
          f"{runtime.quanta} fused dispatch quanta, "
          f"{engine.tokens_per_sync:.1f} tokens per host sync, "
          f"{engine.level_switches} version switches, "
          f"{1e3 * runtime.compile_time_s:.1f}ms in switches, "
          f"version cache {engine.version_cache.stats}, interference level "
          f"{min(lv):.2f}..{max(lv):.2f})")
    print(f"{'metric':18s} {'simulator':>12s} {'engine':>12s}")
    for field, (a, b) in compare_metrics(m_sim, m_eng).items():
        print(f"{field:18s} {a:12.4f} {b:12.4f}")


def colocation_demo(hw):
    """Co-location walkthrough: heterogeneous models, one unit pool.

    Each numbered step below is one knob of the co-location path; the
    printed block at the end is reproduced verbatim in README.md (keep
    them in sync)."""
    from repro.core.scheduler import ModelWisePolicy, PremaPolicy
    from repro.serving import ClusterRuntime, Workload, build_cluster, \
        cluster_plans

    # (1) Pick the tenants: three architectures from repro.configs with
    #     genuinely different layer profiles (dense attention, GQA code
    #     model, SSM).  Each gets an analytic ModelPlan on this hardware
    #     with a feasible auto-derived QoS (qos_scale x solo latency).
    archs = ["gemma-2b", "starcoder2-3b", "mamba2-780m"]
    plans = cluster_plans(archs, hw, qos_scale=3.0)

    # (2) Stand up one real (reduced) JAX engine per model.  Every engine
    #     owns its params, KV/SSM cache, and precompiled VersionCache;
    #     its tile table comes from its OWN plan's multi-version
    #     compilation, so per-engine levels select per-model code.
    tenants = build_cluster(archs, hw, batch_slots=2, max_len=32,
                            plans=plans)

    # (3) One shared Poisson stream whose tenant names route queries to
    #     the matching engine.
    wl = Workload.poisson(archs, 90, 18, prompt_len=4, max_new_tokens=3,
                          seed=1)

    # (4) Serve under the global scheduler.  Per quantum and per engine:
    #     counters are synthesized from the live slot occupancy of the
    #     co-resident engines, the calibrated LinearProxy maps them to a
    #     pressure estimate, plan_chunk_at forms the next layer-block
    #     (its size = the engine's dispatch quantum, its unit need = the
    #     engine's share of hw.n_units), and set_interference_level swaps
    #     that engine to the matching precompiled code version.
    print(f"\nco-locating {len(archs)} heterogeneous real engines on "
          f"{hw.n_units} {hw.unit}s ...")
    rows = []
    for name, policy in (("veltair", VeltairPolicy(hw)),
                         ("model-wise", ModelWisePolicy(hw)),
                         ("prema", PremaPolicy(hw))):
        runtime = ClusterRuntime(tenants if name == "veltair"
                                 else build_cluster(archs, hw, plans=plans),
                                 policy, hw)
        m = runtime.serve(wl)
        lv = "/".join(f"{m.mean_levels[a]:.2f}" for a in archs)
        rows.append((name, m.aggregate.qos_rate,
                     1e3 * m.aggregate.p99_latency_s,
                     sum(m.quanta.values()), m.pool_peak_used, lv))
    print(f"{'policy':12s} {'qos':>5s} {'p99_ms':>7s} {'quanta':>7s} "
          f"{'peak_units':>10s}  mean levels ({'/'.join(archs)})")
    for name, qos, p99, quanta, peak, lv in rows:
        print(f"{name:12s} {qos:5.2f} {p99:7.2f} {quanta:7d} {peak:10d}  "
              f"{lv}")


def speculative_demo():
    """Speculative decode quanta: the same prompts served twice through
    the same reduced model — plain fused quanta, then draft -> batched
    verify -> rollback — with the streams asserted token-identical."""
    import jax
    import numpy as np

    from repro.configs import get_reduced_config
    from repro.models import build_model
    from repro.serving.engine import Request, ServingEngine

    cfg = get_reduced_config("gemma-2b")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    # repetitive continuations (the serving analogue of templated text)
    # are where prompt-lookup drafts land; fresh random prompts would
    # still be token-identical but mostly fall back to plain quanta
    prompts = [np.full(n, 7 + n, np.int32) for n in (12, 9, 6)]

    def serve(speculative):
        eng = ServingEngine(cfg, params, batch_slots=2, max_len=160,
                            speculative=speculative)
        eng.warmup()
        reqs = [Request(rid=i, prompt=p, max_new_tokens=96)
                for i, p in enumerate(prompts)]
        pending = list(reqs)
        while pending and eng.admit_request(pending[0], drain=True):
            pending.pop(0)
        t0 = time.time()
        while pending or not all(r.done for r in reqs):
            eng.step_quantum(8)
            while pending and eng.admit_request(pending[0], drain=True):
                pending.pop(0)
        return eng, [list(r.output) for r in reqs], time.time() - t0

    _, plain, dt_p = serve(False)
    eng, spec, dt_s = serve(True)
    s = eng.spec_stats
    toks = sum(len(o) for o in spec)
    print(f"\nspeculative decode: {toks} tokens, token-identical="
          f"{plain == spec}, plain {toks/dt_p:.0f} tok/s -> spec "
          f"{toks/dt_s:.0f} tok/s ({s['spec_quanta']} spec quanta, "
          f"hit rate {s['draft_hit_rate']:.0%}, "
          f"{s['spec_rollbacks']} rollbacks, "
          f"{s['spec_fallbacks']} fallbacks)")
    assert plain == spec, "speculation must never change the tokens"


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--no-online", action="store_true",
                    help="skip the real-engine replay (simulator only)")
    ap.add_argument("--no-colocate", action="store_true",
                    help="skip the multi-engine co-location demo")
    ap.add_argument("--no-spec", action="store_true",
                    help="skip the speculative-decode demo")
    args = ap.parse_args()

    hw = cm.CPU_3990X
    pm = paper_models()
    models = list(WORKLOAD_CLASSES["mix"])
    print(f"compiling multi-version plans for {len(models)} tenants ...")
    t0 = time.time()
    plans = build_paper_plans(models, hw)
    print(f"  done in {time.time()-t0:.1f}s; per-model versions: "
          + ", ".join(
          f"{n}={sum(len(v.versions) for v in p.version_sets)}"
          for n, p in plans.items()))

    weights = [1.0 / pm[m].qos_ms for m in models]
    sim_policy_table(hw, plans, models, weights)

    if not args.no_online:
        online_engine_demo(hw)

    if not args.no_colocate:
        colocation_demo(hw)

    if not args.no_spec:
        speculative_demo()


if __name__ == "__main__":
    main()
