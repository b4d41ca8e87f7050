"""A benchmark cell at a size a CPU test can hold: the 2-layer GCN of the
configurations on a 300-node graph, through the same harness."""
from __future__ import annotations

import json
import time
from pathlib import Path

from bench import harness

FIXTURES = Path(__file__).resolve().parent / "fixtures"


def config(**deployment) -> dict:
    cfg = json.loads((FIXTURES / "tiny.json").read_text())
    cfg["deployment"].update(deployment)
    return cfg


def cell(kind: str, cfg: dict | None = None, chips: int = 1, model=None,
         **traffic) -> harness.Cell:
    """A cell of the tiny configuration (or ``cfg``) under a traffic ``kind``;
    ``model`` takes the place of the architecture module its ``arch``
    names."""
    cfg = config() if cfg is None else cfg
    traffic = dict({"kind": kind, "deadline_s": 0}, **traffic)
    return harness.Cell(
        name=f"tiny-{kind}",
        chips=chips,
        config=cfg,
        model=harness.load_model(cfg["arch"]) if model is None else model,
        traffic=traffic,
        kind=harness.load_module(harness.BENCH / "traffic" / "kinds" / f"{kind}.py"),
        end_to_end=[],
        per_layer=[],
    )


def run(c: harness.Cell, store: Path, seed: int = 2**33 + 7,
        seconds: float = 1.0) -> dict:
    """One run of the cell on whatever device JAX has, the look for a chip
    skipped."""
    return harness.run(c.name, seed, seconds, False, t_start=time.perf_counter(),
                       require_tpu=False, cell=c, store_root=store)
