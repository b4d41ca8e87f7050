"""Train a (reduced) assigned-architecture LM end-to-end on the synthetic
token pipeline with checkpoint/restart — the training-side driver.

    PYTHONPATH=src python examples/train_lm.py [--arch qwen2-0.5b]

Delegates to ``repro.launch.train`` (the same factory the multi-pod dry-run
lowers); asserts the loss decreases.
"""
import argparse
import tempfile

from repro.launch import train as train_mod


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2-0.5b")
    ap.add_argument("--steps", type=int, default=60)
    args = ap.parse_args()

    with tempfile.TemporaryDirectory() as d:
        losses = train_mod.main([
            "--arch", args.arch, "--reduced", "--steps", str(args.steps),
            "--batch", "8", "--seq", "48", "--ckpt-dir", d,
            "--lr", "2e-3",
        ])
    drop = losses[0] - losses[-1]
    print(f"loss {losses[0]:.3f} -> {losses[-1]:.3f} (drop {drop:.3f})")
    assert drop > 0.1, "loss should decrease"
    print("OK")


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
