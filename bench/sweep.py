"""Find the knee of an open-loop cell: one set-up, then one window at
each of several fixed rates, lowest first.

    python3 bench/sweep.py --workload lubm.lookup-zipf --seed 5 --seconds 20 --rates 2,4,6,8

For each rate it prints the requests, p50 and p95 from the due time, and
the median wait before submit in the first and the last quarter of the
window: a last quarter that waits far longer than the first is a backlog
that grows, so the rate is past what the server sustains.  The knee is
written into the traffic file by hand, as a number in queries/s.
"""
import argparse
import statistics
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True,
                    help="comma-separated queries/s, lowest first")
    args = ap.parse_args()
    from bench.run import boot
    from bench.traffic import load_cell
    cell = load_cell(args.workload)
    boot(cell.chips)
    import numpy as np
    from bench.drive import open_loop
    from bench.harness import DRAIN_S, log, open_schedule, prepare
    from bench.system import Program
    p = prepare(cell, args.seed, False, Program(cell.chips))
    for rate in (float(r) for r in args.rates.split(",")):
        seq, dues = open_schedule(cell, args.seed, args.seconds,
                                  len(p.pool), rate)
        start = time.perf_counter()
        reqs, _ = open_loop(p.server, p.queries, seq, dues, start,
                            start + args.seconds, DRAIN_S)
        end = time.perf_counter()
        lat = np.asarray([((r.done or end) - r.due) * 1e3 for r in reqs])
        q = max(len(reqs) // 4, 1)
        waits = [((r.sent or end) - r.due) * 1e3 for r in reqs]
        log(f"sweep rate={rate} requests={len(reqs)} "
            f"p50_ms={np.percentile(lat, 50)} p95_ms={np.percentile(lat, 95)} "
            f"wait_first_quarter_ms={statistics.median(waits[:q])} "
            f"wait_last_quarter_ms={statistics.median(waits[-q:])} "
            f"failed={sum(r.result is None for r in reqs)} "
            f"drain_s={max(end - start - args.seconds, 0.0)}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
