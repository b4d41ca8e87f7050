"""Readings that set the limit of a cell's check, and its control.

    python3 bench/control.py --workload pubmed-backlog --seeds 11,12,13 --seconds 3

For each seed, in one process: the program serves a short window of the
cell's own traffic at the cell's own size, and the largest relative error
of the kept answers against the reference is read — at the precision the
configuration states (the number ``correct`` compares) and against a
float32 reference at "highest" precision with no operand rounding (for the
record). Then the control does the same: the program with its own
bfloat16-accumulation path switched on (the winning geometry of the sweep
with ``bf16_accumulate``), the nearest precision below the stated float32
aggregation. The reference computed one step down (``lower_precision``)
is read too. The benchmark's own runs never run this.

One JSON line per seed and reading goes to standard output; the last line
sums them up: the program's largest reading (the lower end for the limit)
and the control's smallest (the upper end).
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]

#: a float32 reference with no operand rounding, at "highest" precision
EXACT = {"storage": "float32", "xw": "float32", "aggregate": "float32"}


def bf16_overrides(rep) -> dict:
    """Engine settings that admit the graph on the sweep's winning geometry
    with the program's bfloat16 accumulation switched on."""
    c = rep.config
    cand = dict(
        nnz_per_step=c.nnz_per_step, rows_per_window=c.rows_per_window,
        cols_per_block=c.cols_per_block, window_nnz=c.window_nnz,
        routing=c.routing, ktile=c.ktile, reorder=c.reorder, bf16_accumulate=True,
    )
    return {"autotune_kwargs": {"sweep": [cand], "allow_bf16": True,
                                "bf16_report": False, "iters": 1, "warmup": 1}}


def readings(cell, seed: int, seconds: float, store: Path, *, control: bool,
             require_tpu: bool = True) -> list[dict]:
    """The readings of one seed: the program's, and with ``control`` the
    control's too."""
    import jax

    from bench import harness
    from bench.reference import lower_precision, max_rel_err

    devs = harness.check_chips(cell.chips) if require_tpu else jax.devices()
    platform = devs[0].platform
    s = harness.setup(cell, seed, store)
    stated = cell.config["precision"]
    out = []
    sides = [("program", s.eng)]
    if control:
        ctl, _ = harness.build_engine(cell, s.weights, s.coo, store,
                                      **bf16_overrides(s.rep))
        harness.warm_up(ctl, cell, s.xs, cell.traffic.get("deadline_s"))
        sides.append(("control_bf16_accumulate", ctl))
    for side, e in sides:
        max_batch = int(cell.config["deployment"]["engine"].get("max_batch", 32))
        keep = harness.Reservoir(max(8, harness.KEEP_ROWS // max_batch), seed)
        _, _, variant = harness.drive(e, cell, s.xs, seed, seconds, harness.Spans(False),
                                      keep, time.perf_counter())
        for ref_name, prec in (("stated", stated), ("exact", EXACT)):
            res = harness.check(keep, variant, s.xs, s.weights, s.graph_dev, cell,
                                platform, precision=prec)
            out.append({"seed": seed, "side": side, "reference": ref_name,
                        "max_rel_err": res["max_rel_err"], "rows": res["rows"]})
    s.eng = None
    del sides
    if control:
        lower = lower_precision(stated)
        ref = cell.model.reference_logits
        worst = 0.0
        for x in s.xs:
            want = ref(x, s.weights, s.graph_dev, stated, platform)
            got = ref(x, s.weights, s.graph_dev, lower, platform)
            worst = max(worst, max_rel_err(got, want))
        out.append({"seed": seed, "side": "control_reference_lower",
                    "reference": "stated", "max_rel_err": worst, "rows": len(s.xs)})
    return out


def summary(lines: list[dict]) -> dict:
    prog = [r["max_rel_err"] for r in lines
            if r["side"] == "program" and r["reference"] == "stated"]
    ctl = [r["max_rel_err"] for r in lines
           if r["side"] == "control_bf16_accumulate" and r["reference"] == "stated"]
    return {"program_max": max(prog) if prog else None,
            "control_min": min(ctl) if ctl else None,
            "seeds": len(prog), "control_seeds": len(ctl)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated")
    ap.add_argument("--control-seeds", type=int, default=3,
                    help="how many of the seeds, first first, also run the control")
    ap.add_argument("--seconds", type=float, default=3.0)
    args = ap.parse_args(argv)

    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from bench import harness

    harness.use_compile_cache()
    cell = harness.resolve(args.workload)
    seeds = [int(s) for s in args.seeds.split(",")]
    lines = []
    try:
        for i, seed in enumerate(seeds):
            got = readings(cell, seed, args.seconds, harness.BENCH / ".store",
                           control=i < args.control_seeds)
            for r in got:
                print(json.dumps(r), flush=True)
            lines += got
    except harness.NoChip as e:
        print(f"bench/control.py: {e}", file=sys.stderr)
        return 2
    print(json.dumps(summary(lines)))
    return 0


if __name__ == "__main__":
    sys.exit(main())
