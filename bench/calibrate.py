"""Readings that set the benchmark's limits and rates, many windows in one
process (set-up is paid once).  Not part of a benchmark run.

    python3 bench/calibrate.py --workload <cell> --seeds 1,2,3 \
        [--control-seeds 1,2,3] [--rates 0.5,1,2] --seconds <s> --out <file>

For each seed: weights drawn anew from the seed and swapped into the
warm engines, one window of the cell's traffic (at each ``--rates`` entry
instead of the traffic file's rate, when given), then the correctness
comparison of ``bench/run.py`` and, for ``--control-seeds``, the control
(the reference computed in float8 in the program's place).  One JSON line
per window: seed, rate, end-to-end numbers, drain time, checks.
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parents[1]


def _ints(s: str) -> list[int]:
    return [int(x) for x in s.split(",") if x]


def clear(engines) -> None:
    """Free every slot a stopped window left busy."""
    for e in engines:
        e.engine._prefill.clear()
        for i, r in enumerate(e.engine.slot_req):
            if r is not None:
                e.engine.release_slot(i)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--rates", default="")
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--drain-cap", type=float, default=None)
    ap.add_argument("--knee", action="store_true",
                    help="stop raising the rate once a window fails the "
                         "knee rule (a request failed or drained > 10%%)")
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)
    sys.path.insert(0, str(ROOT / "src"))
    sys.path.insert(0, str(ROOT))
    import jax

    from bench import run as R
    from bench.lib import serve, traffic, weights
    from bench.lib.spec import load_cell
    from repro.launch.compile_cache import enable_compile_cache
    from repro.models import build_model

    cell = load_cell(args.workload)
    devices = R.require_tpu(cell.chips)
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    seeds = _ints(args.seeds)
    control = set(_ints(args.control_seeds))
    rates = [float(r) for r in args.rates.split(",") if r] or [None]
    cap = (args.drain_cap if args.drain_cap is not None
           else float(cell.cell["drain_cap_s"]))
    tenants = serve.build_tenants(cell.config, seeds[0], R.log)
    plans = serve.plans_for(cell.config, tenants)
    engines = serve.build_engines(cell.config, tenants, plans)
    serve.warm(cell.config, engines, seeds[0], R.log)
    out = open(args.out, "a")
    for seed in seeds:
        for i, t in enumerate(tenants):
            t.params = engines[i].engine.params = None    # one copy at most
            gc.collect()
            t.params = weights.draw(build_model(t.model_cfg), seed, i)
            engines[i].engine.params = t.params
        for rate in rates:
            traf = dict(cell.traffic)
            if rate is not None:
                traf["rate_per_s"] = rate
            reqs = traffic.generate(traf, [t.name for t in tenants],
                                    args.seconds, seed)
            runtime = serve.make_runtime(cell.config, engines, seed)
            stamps, _, _, _, end = serve.window(
                runtime, reqs, traf, args.seconds, seed, trace=False,
                trace_dir=None, drain_cap_s=cap)
            clear(engines)
            recs = list(stamps.records.values())
            e2e = R.end_to_end(
                dataclasses.replace(cell, end_to_end=[
                    {"name": n, "unit": u} for n, u in (
                        ("ttft_p90_s", "s"), ("tpot_p90_s", "s"),
                        ("out_tok_s", "tokens/s"))]),
                stamps, args.seconds, end, 0.0)
            last = max((r.last for r in recs if r.last is not None),
                       default=stamps.t0)
            t = time.perf_counter()
            gaps = R.sample_gaps(cell, tenants, stamps, seed,
                                 control=seed in control)
            checks = R.checks_of(cell, gaps)
            line = {"seed": seed, "rate": rate, "requests": len(recs),
                    "finished": sum(r.finished for r in recs),
                    "drain_s": last - (stamps.t0 + args.seconds),
                    "metrics": {k: v["value"] for k, v in e2e.items()},
                    "checks": checks, "gaps": gaps,
                    "peak_bytes": (devices[0].memory_stats() or {}).get(
                        "peak_bytes_in_use"),
                    "check_s": time.perf_counter() - t}
            R.log(json.dumps(line))
            out.write(json.dumps(line) + "\n")
            out.flush()
            if args.knee and (line["finished"] < line["requests"]
                              or line["drain_s"] > 0.1 * args.seconds):
                break
    jax.effects_barrier()
    return 0


if __name__ == "__main__":
    sys.exit(main())
