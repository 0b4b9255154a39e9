"""Find where a cell's traffic saturates the system, on the chip.

    python3 bench/sweep.py --workload deep96-f32.online \
        --rates 24,32,40,48 --seconds 30 --seed 11
    python3 bench/sweep.py --workload deep96-f32.batch \
        --callers 1,2,3,4 --seconds 15 --seed 11

One process, one engine. For an open mix, each rate replays the mix's
arrival trace at that rate and prints latency percentiles, the 95th
percentile of the requests due in each half of the window, and how late
the generator sent, with the process's pauses (``benchlib.pauses``),
so that a window a stall of the whole process met is known by what
was measured in it. The knee is the highest rate at which the backlog
does not grow: the generator's median lateness stays at a few
milliseconds and the second half's p95 is not above the first half's by
more than a run's own spread. For a closed mix, each caller count prints
queries per second and the walk launches' batch fill. The cells'
benchmark runs (``bench/run.py``) never run this.
"""
import time

T_PROCESS = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent / "src"))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=11)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--rates", default="")
    ap.add_argument("--callers", default="")
    args = ap.parse_args(argv)

    from benchlib import runner
    runner.prepare_environment(BENCH / ".cache")
    import numpy as np

    from benchlib import pauses, readers, spec, stats, system, traffic
    from benchlib.data import make_corpus
    from benchlib.drive import run_closed, run_open

    cell = spec.load_cell(args.workload)
    runner.devices_for(cell.chips, require_tpu=True)
    config, mix = cell.config, dict(cell.traffic)
    k = config["k"]
    x, queries = make_corpus(config)
    index, _ = system.load_or_build_index(
        config, x, cell.bench_dir / ".cache", workers=os.cpu_count())
    tracer = system.make_tracer()
    client = system.start_client(index, config["engine"], tracer)
    try:
        runner.warm_up(client, queries, mix, k)
        print(json.dumps({"setup_s": time.monotonic() - T_PROCESS}),
              flush=True)
        if mix["loop"] == "open":
            points = [("rate_qps", float(r)) for r in args.rates.split(",")]
        else:
            points = [("callers", int(c)) for c in args.callers.split(",")]
        for i, (key, value) in enumerate(points):
            mix[key] = value
            watch = pauses.PauseWatch(cell.bench_dir / ".cache" / "pauses"
                                      / f"sweep.{key}.{value}.{i}.txt")
            t_start = time.monotonic() + 0.05
            watch.start()
            if mix["loop"] == "open":
                sched = traffic.open_schedule(mix, args.seconds, args.seed,
                                              len(queries))
                win = run_open(client, queries, sched.due_s,
                               sched.query_idx, k, t_start, args.seconds,
                               runner.CLOSE_WAIT_S)
            else:
                win = run_closed(client, queries,
                                 traffic.closed_order(args.seed,
                                                      len(queries)),
                                 mix["batch"], mix["callers"], k, t_start,
                                 args.seconds, runner.CLOSE_WAIT_S)
            watch.stop()
            run = runner.Run(cell, 0.0, win, None, {}, {},
                             [s for s in tracer.snapshot()
                              if win.t_start <= s.t0 <= win.t_end], None)
            lat = stats.latencies_ms(win.due, win.done)
            half = win.due < win.t_start + args.seconds / 2
            late = (win.submitted - win.due) * 1e3
            print(json.dumps({
                key: value, "t_start_monotonic": t_start,
                "requests": win.attempted,
                "failed": win.failed,
                "qps": readers.qps(run),
                "p50_ms": stats.percentile(lat, 50),
                "p95_ms": stats.percentile(lat, 95),
                "p99_ms": stats.percentile(lat, 99),
                "p95_first_half_ms": stats.percentile(lat[half], 95),
                "p95_second_half_ms": stats.percentile(lat[~half], 95),
                "late_p50_ms": stats.percentile(late, 50),
                "late_max_ms": float(np.max(late)),
                "overshoot": (stats.overshoot(win) if mix["loop"] == "open"
                              else None),
                "pauses": watch.summary(t_start),
                "batch_fill": readers.batch_fill(run),
                "engine": system.engine_stats(client)}), flush=True)
            client.engine.drain(timeout=120)
    finally:
        system.stop_client(client)
    return 0


if __name__ == "__main__":
    sys.exit(main())
