"""Set-up and the measured window: the system under test, driven as a
deployment would drive it.

Set-up builds, for each model the configuration names, its weights (one
jitted draw from the seed) and a ``ServingEngine``, behind one
``ClusterRuntime`` (a one-model configuration is a cluster of one
tenant), as the program's own chip smoke run builds them:
``VeltairPolicy``, ``cluster_plans`` over the configuration's hardware
spec, ``wall_clock=True``.  Every other option is the program's default;
the configuration file names each argument passed.  Then
``ClusterRuntime.warmup()`` and a few warm requests (which compile the
row writer and slot release the warmup does not).  The window then
drives ``ClusterRuntime.serve`` over the cell's traffic.
"""
from __future__ import annotations

import dataclasses
import time

from bench.lib import traffic as traffic_mod
from bench.lib.hooks import Stamps, Window
from bench.lib.spec import family_module
from bench.lib.stats import Record

# a 16-token chunk then each tail bucket (1, 2, 4, 8, 16): every prefill
# shape the engine can dispatch; 17 new tokens reach the 16-step quantum
WARM_PROMPTS, WARM_NEW = (17, 18, 20, 24, 32), 17


@dataclasses.dataclass
class Tenant:
    name: str
    family: str
    spec: dict                       # the model's entry in the config file
    model_cfg: object                # repro ModelConfig
    params: object


def build_tenants(config: dict, seed: int, log) -> list[Tenant]:
    from repro.models import build_model
    from bench.lib import weights
    out = []
    for i, (name, spec) in enumerate(config["models"].items()):
        adapter = family_module("adapters", spec["family"])
        mcfg = adapter.model_config(name, spec)
        t = time.perf_counter()
        params = weights.draw(build_model(mcfg), seed, i)
        log(f"[setup] {name}: weights drawn in "
            f"{time.perf_counter() - t:.3f} s")
        out.append(Tenant(name, spec["family"], spec, mcfg, params))
    return out


def build_engines(config: dict, tenants: list[Tenant], plans) -> list:
    from repro.serving import EngineTenant
    from repro.serving.engine import ServingEngine
    return [EngineTenant(
        name=t.name, plan=plans[t.name],
        engine=ServingEngine(t.model_cfg, t.params,
                             version_sets=plans[t.name].version_sets,
                             **config["engine"][t.name]))
        for t in tenants]


def make_runtime(config: dict, engines: list, seed: int):
    from repro.core import cost_model as cm
    from repro.core.scheduler import VeltairPolicy
    from repro.serving import ClusterRuntime
    rt = config["runtime"]
    hw = getattr(cm, rt["hardware"])
    return ClusterRuntime(engines, VeltairPolicy(hw), hw,
                          wall_clock=bool(rt["wall_clock"]), seed=seed)


def plans_for(config: dict, tenants: list[Tenant]):
    from repro.core import cost_model as cm
    from repro.serving import cluster_plans
    return cluster_plans([t.name for t in tenants],
                         getattr(cm, config["runtime"]["hardware"]))


def workload(requests, seed: int):
    """The runtime's ``Workload`` carrying the harness's own arrivals and
    prompt lengths (the runtime draws prompt token ids from ``seed``).  It
    carries one output length, the longest; the admission wrap
    (``bench/lib/hooks.py``) gives each request its own."""
    from repro.serving import Workload

    class BenchWorkload(Workload):
        def prompt_lengths(self):
            return [r.prompt_len for r in requests]
    return BenchWorkload([(r.due_s, r.tenant) for r in requests],
                         prompt_len=max(r.prompt_len for r in requests),
                         max_new_tokens=max(r.output_len for r in requests),
                         seed=seed)


def warm(config, engines, seed: int, log) -> None:
    """Warm every engine's compiled programs through the serving path."""
    runtime = make_runtime(config, engines, seed)
    t = time.perf_counter()
    stats = runtime.warmup()
    log(f"[setup] ClusterRuntime.warmup {time.perf_counter() - t:.3f} s, "
        f"version caches {stats}")
    reqs = [traffic_mod.Request(0.0, e.name, n, WARM_NEW)
            for e in engines for n in WARM_PROMPTS]
    t = time.perf_counter()
    runtime.serve(workload(reqs, seed))
    log(f"[setup] warm requests {time.perf_counter() - t:.3f} s")


def window(runtime, requests, traffic: dict, seconds: float, seed: int, *,
           trace: bool, trace_dir: str | None, drain_cap_s: float):
    """Serve the window's requests; returns (stamps, window controller,
    counter snapshots before and after, end time)."""
    backlog = traffic["kind"] == "backlog"
    counters0 = snapshot(runtime)
    t0 = time.perf_counter()
    records = {rid: Record(rid=rid, tenant=r.tenant, due=t0 + r.due_s,
                           prompt_len=r.prompt_len, want=r.output_len + 1)
               for rid, r in enumerate(requests)}
    stamps = Stamps(t0, records)
    stop_at = t0 + seconds if backlog else t0 + seconds + drain_cap_s
    span = None
    if trace:
        # a few seconds from the first arrival at or after 40% of the
        # window: that request's prefill and decode both fall inside
        due = [r.due_s for r in requests if r.due_s >= 0.4 * seconds]
        start = t0 + (min(due) if due else 0.4 * seconds)
        span = (start, start + min(4.0, 0.3 * seconds))
    win = Window(runtime, stamps, stop_at=stop_at, trace=span,
                 trace_dir=trace_dir)
    try:
        runtime.serve(workload(requests, seed))
    finally:
        win.close()
    end = time.perf_counter()
    return stamps, win, counters0, snapshot(runtime), end


def snapshot(runtime) -> dict:
    """The program's own counters, per tenant."""
    keys = ("host_syncs", "tokens_decoded", "prefill_chunks",
            "prefill_tokens", "prefill_pad_tokens")
    return {t.name: {**{k: getattr(t.engine, k) for k in keys},
                     "traces": t.engine.version_cache.traces}
            for t in runtime.tenants}
