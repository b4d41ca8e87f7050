"""Benchmark harness — one module per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--only utilization,...] \
        [--json BENCH_spmm.json]

Prints human tables per benchmark, then the machine-readable
``name,us_per_call,derived`` CSV block. ``--json PATH`` additionally writes
the same rows as JSON (with a timestamp and the jax backend), so the perf
trajectory is tracked across PRs. A failed suite leaves a ``<suite>/FAILED``
row, the other suites still run, and the harness exits non-zero.
"""
from __future__ import annotations

import argparse
import json
import os
import time


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default="")
    ap.add_argument("--pe", type=int, default=1024)
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="also write rows as machine-readable JSON")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny synthetic size preset (CI bench-smoke): "
                         "exercises the full measurement pipeline in "
                         "minutes; gate only ratios, never absolutes")
    args = ap.parse_args()

    if args.smoke:
        # must land before the suite imports below: benchmarks.common
        # freezes its dataset scales at import time
        os.environ["BENCH_SMOKE"] = "1"
        print("[smoke] tiny synthetic preset active")

    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()

    from benchmarks import (convergence, latency, moe_imbalance, openloop,
                            order_ops, reorder, roofline_table, scaling,
                            schedule_tuning, schedule_util, serving,
                            sharded_spmm, streaming, utilization)

    suites = {
        "order_ops": order_ops.run,                    # Table II
        "utilization": lambda: utilization.run(args.pe),  # Figs 14/15
        "convergence": convergence.run,                # Figs 3/17
        "scaling": scaling.run,                        # Fig 18
        "latency": latency.run,                        # Tables III/IV
        "schedule_util": schedule_util.run,            # TPU Fig-14 analogue
        "schedule_tuning": schedule_tuning.run,        # kernel-param sweep
        "sharded_spmm": sharded_spmm.run,              # multi-device executor
        "reorder": reorder.run,                        # islandization axis
        "serving": serving.run,                        # store + batching
        "openloop": openloop.run,                      # overload/admission
        "streaming": streaming.run,                    # incremental repair
        "moe_imbalance": moe_imbalance.run,            # beyond-paper (EP)
        "roofline": roofline_table.run,                # §Roofline
    }
    only = [s for s in args.only.split(",") if s]
    rows = []
    failed = []
    for name, fn in suites.items():
        if only and name not in only:
            continue
        try:
            rows.extend(fn())
        except Exception as e:  # run the other suites, then exit non-zero
            print(f"[{name}] FAILED: {type(e).__name__}: {e}")
            rows.append((f"{name}/FAILED", 0.0, str(e)[:80]))
            failed.append(name)

    print("\n== CSV ==")
    print("name,us_per_call,derived")
    for name, us, derived in rows:
        print(f"{name},{us:.1f},{derived}")

    if args.json:
        import jax

        payload = {
            "timestamp": time.strftime("%Y-%m-%dT%H:%M:%S"),
            "backend": jax.default_backend(),
            "smoke": bool(args.smoke),
            "rows": [{"name": name, "us_per_call": round(float(us), 1),
                      "derived": derived} for name, us, derived in rows],
        }
        # per-device-count latency of the sharded executor and the serving
        # engine's cold/warm-start numbers as their own sections, so the
        # perf trajectory across PRs tracks device scaling and store-hit
        # latency separately from the single-device rows
        for section in ("sharded_spmm", "reorder", "serving", "openloop",
                        "streaming"):
            sub = [r for r in payload["rows"]
                   if r["name"].startswith(f"{section}/")]
            if sub:
                payload[section] = sub
        with open(args.json, "w") as f:
            json.dump(payload, f, indent=2)
            f.write("\n")
        print(f"wrote {len(rows)} rows to {args.json}")
    if failed:
        raise SystemExit(f"suite(s) failed: {', '.join(failed)}")


if __name__ == "__main__":
    main()
