"""Benchmark orchestrator — one section per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows (benchmarks.common.emit).
Each ``F.fig*`` call below reproduces the paper figure it is named for.

    PYTHONPATH=src python -m benchmarks.run [--quick]
"""
import argparse
import sys
import time


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="3-point QPS grids instead of 5")
    args = ap.parse_args()

    from benchmarks import bench_figures as F
    from benchmarks import bench_kernels as K

    t0 = time.time()
    print("name,us_per_call,derived")
    K.run_all()
    F.fig4_core_scaling()
    F.fig6_multiversion()
    F.fig7_version_count()
    F.fig11_proxy()
    F.fig3_granularity()
    F.fig5_conflicts()
    out12 = F.fig12_qps(quick=args.quick)
    F.fig13_latency(out12)
    F.fig14_efficiency()

    # append dry-run / roofline / hillclimb summaries from results/*.jsonl
    try:
        from benchmarks import report
        report.main()
    except Exception as e:  # reports are optional if sweeps haven't run
        print(f"# report unavailable: {e}", file=sys.stderr)
    print(f"# total wall: {time.time()-t0:.1f}s", file=sys.stderr)


if __name__ == "__main__":
    main()
