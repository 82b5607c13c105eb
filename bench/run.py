"""One benchmark run of one cell on the chip(s) it is started on.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process: it touches JAX once, starts no child, and exits non-zero
without a result when JAX finds no TPU or fewer chips than the cell asks
for.  Set-up (weights drawn from the seed on the device, engines, runtime,
warmup, warm requests) counts as ``setup_s``, from process start to the
window's start.  The window serves the cell's traffic for ``--seconds``
through ``ClusterRuntime.serve``, every timestamp on the harness's own
clock (``bench/lib/hooks.py``).  Then device peak memory is read, the
engines are freed, and the sample of served requests is checked against
the plain reference.  ``--trace 1`` traces a few seconds inside the window
and reports the per-layer metrics; ``--trace 0`` the end-to-end ones.

The last line of standard output is one JSON object (``correct``,
``attempted``, ``failed``, ``metrics``, ``device``, with ``--trace 1``
``breakdown``, and last ``checks``: each number compared with its limit);
the last lines of standard error repeat the checks.

``--control 1`` runs the control of the correctness check instead of
judging the program: the same window, then the float8 reference's tokens
(``bench/lib/correct.py``) are judged in the program's place, against the
same limits, and ``correct`` has to come out false.  Benchmark runs never
pass it.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse     # noqa: E402
import collections  # noqa: E402
import gc           # noqa: E402
import glob         # noqa: E402
import json         # noqa: E402
import pathlib      # noqa: E402
import shutil       # noqa: E402
import sys          # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]
TRACE_DIR = ROOT / ".bench_trace"


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


class RunError(Exception):
    """The run cannot produce a result (no chip, missing program, ...)."""


def require_tpu(chips: int):
    import jax
    try:
        devices = jax.devices()
    except RuntimeError as e:
        raise RunError(f"no TPU: JAX could not start a backend "
                       f"({str(e).splitlines()[0]})") from None
    if devices[0].platform != "tpu":
        raise RunError(f"no TPU: JAX's device is {devices[0].platform} "
                       f"({devices[0].device_kind})")
    if len(devices) < chips:
        raise RunError(f"the cell needs {chips} chips, JAX has "
                       f"{len(devices)}")
    return devices


def peaks_for(kind: str) -> dict:
    table = json.loads((ROOT / "bench" / "peaks.json").read_text())
    if kind not in table:
        raise RunError(f"device kind {kind!r} is not in bench/peaks.json")
    return table[kind]


def per_layer(cell, ctx) -> dict:
    from bench.lib.spec import metric_reader
    out = {}
    for m in cell.per_layer:
        v = metric_reader(m["name"])(ctx)
        if v is not None:
            out[m["name"]] = {"value": v, "unit": m["unit"]}
    return out


def end_to_end(cell, stamps, seconds: float, end: float,
               setup_s: float) -> dict:
    from bench.lib.stats import percentile, tokens_by, tpot, ttft
    recs = list(stamps.records.values())
    values = {"setup_s": setup_s}
    if recs:
        values["ttft_p90_s"] = percentile([ttft(r, end) for r in recs], 90)
        values["tpot_p90_s"] = percentile([tpot(r, end) for r in recs], 90)
    values["out_tok_s"] = tokens_by(recs, stamps.t0 + seconds) / seconds
    return {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
            for m in cell.end_to_end if m["name"] in values}


def work_summary(stamps, close: float) -> str:
    """What the program did up to the window's close: decode quanta by
    steps, their mean active rows, and prefill calls."""
    quanta = [(rows, steps) for t, _, rows, steps in stamps.decode_calls
              if t <= close]
    by_steps = collections.Counter(steps for _, steps in quanta)
    rows = sum(len(r) for r, _ in quanta) / max(len(quanta), 1)
    prefills = sum(t <= close for t, *_ in stamps.prefill_calls)
    return (f"decode quanta {len(quanta)} by steps "
            f"{dict(sorted(by_steps.items()))}, mean rows {rows:.2f}; "
            f"prefill calls {prefills}")


def sample_gaps(cell, tenants, stamps, seed: int, control: bool) -> dict:
    """Per model: the reference's gaps of a sample of served requests
    (``bench/lib/correct.py``), or None when nothing was served."""
    from bench.lib import correct
    want = cell.cell["correct"]
    width = correct.seq_width(cell.traffic)
    out = {}
    for t in tenants:
        k = int(want["sample"][t.name])
        rids = correct.sample(stamps, t.name, k, seed)
        if not rids:
            out[t.name] = None
            continue
        toks, tgt, mask = correct.sequences(stamps, rids, k, width)
        adapter = correct.family_module("adapters", t.family)
        out[t.name] = correct.gaps(t.family, t.spec,
                                   adapter.reference_weights(t.params),
                                   toks, tgt, mask, control)
        out[t.name]["tokens"] = int(mask.sum())
    return out


def checks_of(cell, gaps: dict, judged: str = "program") -> dict:
    """{check: {"value", "limit"}}: each model's widest gap of the
    ``judged`` tokens ("program", or "control": the float8 reference's)
    against the cell's limit; a model with nothing served fails."""
    want = cell.cell["correct"]
    out = {}
    for model, g in gaps.items():
        if g is None:
            out[f"unserved.{model}"] = {"value": 1, "limit": 0}
            continue
        out[f"gap.{model}"] = {"value": g[judged]["max"],
                                  "limit": want["limits"][model],
                                  "tokens": g["tokens"]}
    return out


def run(cell, seed: int, seconds: float, trace: bool, devices,
        control: bool = False) -> dict:
    from bench.lib import serve, traffic
    from bench.lib.context import Context
    from repro.launch.compile_cache import enable_compile_cache
    import jax

    cache = enable_compile_cache()
    # cache every program, also those under a second to compile, so that
    # a warm run's set-up loads and never compiles
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    compiles = [0]

    def on_compile(event: str, *_a, **_kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            compiles[0] += 1
    jax.monitoring.register_event_duration_secs_listener(on_compile)
    log(f"[setup] device {devices[0].device_kind} x{len(devices)}, "
        f"compile cache {cache}")

    tenants = serve.build_tenants(cell.config, seed, log)
    plans = serve.plans_for(cell.config, tenants)
    engines = serve.build_engines(cell.config, tenants, plans)
    serve.warm(cell.config, engines, seed, log)
    requests = traffic.generate(cell.traffic, [t.name for t in tenants],
                                seconds, seed)
    runtime = serve.make_runtime(cell.config, engines, seed)
    compiled_before = compiles[0]
    if trace:
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
    stamps, win, c0, c1, end = serve.window(
        runtime, requests, cell.traffic, seconds, seed, trace=trace,
        trace_dir=str(TRACE_DIR),
        drain_cap_s=float(cell.cell["drain_cap_s"]))
    setup_s = stamps.t0 - T_START
    compiled = compiles[0] - compiled_before
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devices)
    log(f"[window] {len(requests)} requests due, served until "
        f"{end - stamps.t0:.3f} s after the window opened; "
        f"compiles in window {compiled}; peak_bytes_in_use {peak}")
    log(f"[window] {work_summary(stamps, stamps.t0 + seconds)}")
    ctx = Context(stamps=stamps, counters0=c0, counters1=c1, end=end,
                  specs={t.name: t.spec for t in tenants},
                  peaks=peaks_for(devices[0].device_kind),
                  traced=win.traced)
    del runtime, engines, win
    gc.collect()

    backlog = cell.traffic["kind"] == "backlog"
    recs = list(stamps.records.values())
    if backlog:
        attempted = sum(r.finished and r.last <= stamps.t0 + seconds
                        for r in recs)
        failed = 0
    else:
        attempted = len(recs)
        failed = sum(not r.finished for r in recs)

    result = {"correct": None, "attempted": attempted, "failed": failed}
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": peak}
    if trace:
        from bench.lib import trace as trace_mod
        paths = sorted(glob.glob(str(TRACE_DIR / "**" / "*.xplane.pb"),
                                 recursive=True))
        if not paths or ctx.traced is None or ctx.traced[1] is None:
            raise RunError("the window ended before its traced part")
        red = trace_mod.reduce(trace_mod.load(paths[-1]))
        ctx.reduction = red
        device["busy_s"] = red.busy_s
        device["window_s"] = ctx.window_s
        result["metrics"] = per_layer(cell, ctx)
        result["breakdown"] = {"device_ops": red.ops,
                               "idle_gaps": red.idle_gaps}
    else:
        result["metrics"] = end_to_end(cell, stamps, seconds, end, setup_s)
    result["device"] = device
    t = time.perf_counter()
    checks = checks_of(cell, sample_gaps(cell, tenants, stamps, seed,
                                         control=control),
                       "control" if control else "program")
    log(f"[check] reference took {time.perf_counter() - t:.3f} s")
    result["correct"] = all(c["value"] <= c["limit"] for c in checks.values())
    result["checks"] = checks
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", type=int, choices=(0, 1), default=0,
                    help="judge the float8 control instead of the program")
    args = ap.parse_args(argv)
    if not (ROOT / "src" / "repro").is_dir():
        log(f"bench: no program at {ROOT / 'src' / 'repro'}")
        return 2
    sys.path.insert(0, str(ROOT / "src"))
    sys.path.insert(0, str(ROOT))
    from bench.lib.spec import SpecError, load_cell
    try:
        cell = load_cell(args.workload)
        devices = require_tpu(cell.chips)
        result = run(cell, args.seed, args.seconds, bool(args.trace),
                     devices, control=bool(args.control))
    except (RunError, SpecError) as e:
        log(f"bench: {e}")
        return 1
    for name, c in result["checks"].items():
        log(f"check {name}: {c['value']} (limit {c['limit']})")
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
