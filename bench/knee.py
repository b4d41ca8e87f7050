"""Sweep of offered load, to find the highest rate a cell's system sustains.

    python3 bench/knee.py --workload <a Poisson cell> --rates 20,40,60,80 --seconds 6

One process sets the cell up once, then offers each rate in turn for
``--seconds`` through the cell's own traffic kind with ``rate_rps``
replaced, and prints one JSON line per rate: latency p50/p95, the requests
answered per second of the window, how long after the window's close the
last answer came (a backlog that grew drains long), and the mean batch.
The rate a Poisson cell offers is then written into its traffic file as a
number; nothing is calibrated inside a benchmark run.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True, help="comma-separated req/s")
    ap.add_argument("--seconds", type=float, default=6.0)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)

    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    import numpy as np

    from bench import harness

    harness.use_compile_cache()
    cell = harness.resolve(args.workload)
    try:
        harness.check_chips(cell.chips)
    except harness.NoChip as e:
        print(f"bench/knee.py: {e}", file=sys.stderr)
        return 2
    s = harness.setup(cell, args.seed, harness.BENCH / ".store")
    eng, xs = s.eng, s.xs
    for i, rate in enumerate(float(r) for r in args.rates.split(",")):
        c = dataclasses.replace(cell, traffic=dict(cell.traffic, rate_rps=rate))
        eng.reset_stats()
        t0 = time.perf_counter()
        times, _, _ = harness.drive(eng, c, xs, args.seed + i, args.seconds,
                                    harness.Spans(False), harness.Reservoir(1, 0), t0)
        done, arr = times["done"], times["arrival"]
        lat = (done - arr)[np.isfinite(done)] * 1e3
        st = eng.stats()
        print(json.dumps({
            "rate_rps": rate,
            "requests": int(arr.size),
            "answered_in_window_rps": float((done <= args.seconds).sum())
            / args.seconds,
            "latency_p50_ms": float(np.percentile(lat, 50)),
            "latency_p95_ms": float(np.percentile(lat, 95)),
            "drain_s": float(np.nanmax(done) - args.seconds),
            "batch_mean": st["requests"] / max(1, st["batches"]),
        }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
