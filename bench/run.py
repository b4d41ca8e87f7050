"""Run one benchmark cell once and print its result line.

    python3 bench/run.py --workload pubmed-backlog --seed 7 --seconds 10 --trace 0

The workload names a cell of ``BENCHMARK.json``. ``--seed`` makes the
weights, the request features and the arrivals; the graph belongs to the
configuration. ``--trace 0`` reports the cell's end-to-end metrics, and
``--trace 1`` records a profiler trace of the window and reports its
per-layer metrics instead. Everything runs in this one process, on the
chips JAX finds here; without a TPU, or with fewer chips than the cell
asks for, it exits non-zero and prints no result.

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics``, ``device`` (and ``breakdown`` with
``--trace 1``), then ``checks``: each number compared with its limit, which
the last lines of standard error repeat. JAX's compilation cache and the
tuning store live under ``bench/`` (``.jax_cache/``, ``.store/``), so only
a checkout's first run of a cell compiles and sweeps.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--save-trace", type=Path, default=None,
                    help="also copy the recorded .xplane.pb here")
    args = ap.parse_args(argv)

    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from bench import harness

    harness.use_compile_cache()
    try:
        line = harness.run(args.workload, args.seed, args.seconds, bool(args.trace),
                           t_start=T_START, save_trace=args.save_trace)
    except harness.NoChip as e:
        print(f"bench/run.py: {e}", file=sys.stderr)
        return 2
    print(json.dumps(line), flush=True)
    harness.print_checks(line)
    return 0


if __name__ == "__main__":
    sys.exit(main())
