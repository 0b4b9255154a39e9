"""Benchmark harness: one function per paper table/figure.

``PYTHONPATH=src python -m benchmarks.run [--quick] [--only fig9]``
prints ``name,us_per_call,derived`` CSV lines (plus a header).
"""
from __future__ import annotations

import argparse
import sys
import time
import traceback

from benchmarks import (ablation_partitioner, bench_build,
                        fig5_access_rate, fig6_precision, fig7_throughput,
                        fig8_latency, fig9_comparison, fig10_mips,
                        fig11_scalability, fig12_straggler, fig13_failure,
                        roofline)
from repro.common.compile_cache import enable_compile_cache

SUITES = {
    "build": bench_build.run,
    "fig5": fig5_access_rate.run,
    "fig6": fig6_precision.run,
    "fig7": fig7_throughput.run,
    "fig8": fig8_latency.run,
    "fig9": fig9_comparison.run,
    "fig10": fig10_mips.run,
    "fig11": fig11_scalability.run,
    "fig12": fig12_straggler.run,
    "fig13": fig13_failure.run,
    "ablation": ablation_partitioner.run,
    "roofline": roofline.run,
}


def main() -> None:
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="small datasets (CI-speed)")
    ap.add_argument("--only", default=None,
                    help="comma-separated suite names")
    args = ap.parse_args()
    names = (args.only.split(",") if args.only else list(SUITES))
    print("name,us_per_call,derived")
    failures = []
    for name in names:
        t0 = time.time()
        try:
            SUITES[name](quick=args.quick)
            print(f"# {name} done in {time.time()-t0:.1f}s", file=sys.stderr)
        except Exception:
            failures.append(name)
            traceback.print_exc()
    if failures:
        raise SystemExit(f"benchmark failures: {failures}")


if __name__ == "__main__":
    main()
