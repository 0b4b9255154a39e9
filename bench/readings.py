"""Read the correctness check's numbers on the chip, at a cell's own
size and load: of the program as it serves, of the program with a fault
planted in its timed path, or of the control in its place.

    python3 bench/readings.py --workload deep96-f32.online \
        --seeds 5,6,7 --seconds 10 [--control | --fault alter_id]

The control is the plain reference one precision step below the
configuration's float32: exact brute force with vectors and queries in
bfloat16 (``benchlib.reference.Bf16BruteForce``). The faults are
``benchlib.faults``. Each run goes through the same window, traffic and
check as a benchmark run, in one process, and prints one JSON line of
the numbers it read (the run's own lines go to standard error): the program's set the lower readings, the
control's and the faults' the upper ones. The benchmark's own runs
never run this.
"""
import argparse
import contextlib
import json
import sys
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent / "src"))


def control_client(index, x, engine, tracer):
    from benchlib.reference import Bf16BruteForce
    return Bf16BruteForce(x)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    which = ap.add_mutually_exclusive_group()
    which.add_argument("--control", action="store_true")
    which.add_argument("--fault", default=None)
    args = ap.parse_args(argv)

    from benchlib import faults, runner, spec
    runner.prepare_environment(BENCH / ".cache")
    n = spec.load_cell(args.workload).config["n"]
    as_ = "control" if args.control else args.fault or "program"
    for seed in (int(s) for s in args.seeds.split(",")):
        t0 = time.monotonic()
        plant = (faults.planted(args.fault, n) if args.fault
                 else contextlib.nullcontext())
        with plant:
            result = runner.run_cell(
                args.workload, seed, args.seconds, False,
                t_process=time.monotonic(),
                client_factory=control_client if args.control else None,
                log=lambda *a, **k: print(*a, file=sys.stderr, **k))
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "as": as_, "correct": result["correct"],
                          "attempted": result["attempted"],
                          "recall_at_10": result["metrics"].get(
                              "recall_at_10", {}).get("value"),
                          "check": result["check"],
                          "seconds": time.monotonic() - t0}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
