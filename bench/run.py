"""Run one benchmark cell on the chips of this machine.

    python3 bench/run.py --workload deep96-f32.online --seed 7 \
        --seconds 30 --trace 0

The cell ``<config>.<mix>`` is looked up in ``BENCHMARK.json``; its
configuration, traffic and metric readers are files under ``bench/``.
The last line of standard output is the result as one JSON object; the
numbers the correctness check compared, each beside its limit, are the
last lines of standard error. Without a TPU, or with fewer chips than
the cell asks for, it prints no result and exits non-zero.
"""
import time

T_PROCESS = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent / "src"))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from benchlib import runner
    runner.prepare_environment(BENCH / ".cache")
    try:
        import repro  # noqa: F401  the system under test
    except ImportError as e:
        print(f"bench: the program is not here ({e})", file=sys.stderr)
        return 2
    try:
        result = runner.run_cell(args.workload, args.seed, args.seconds,
                                 bool(args.trace), t_process=T_PROCESS)
    except runner.NoAccelerator as e:
        print(f"bench: {e}", file=sys.stderr)
        return 1
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
