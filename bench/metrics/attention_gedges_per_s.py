"""Batched forward (edge attention): the GAT's edge-heads served per second
of forward device time, in G edge-heads/s — the program's
``stats()["attention_edge_heads"]`` (non-zeros × heads × layers × requests
awaited in the window and its drain) over the device time of the traced
executions of the cell's forward program (device trace). None where the
program has no such counter or it reads 0 (a GCN)."""
from bench.metrics import _forward


def read(run):
    n = run.stats.get("attention_edge_heads")
    r = _forward.runs(run)
    if not n or not r:
        return None
    spent = sum(e - s for _, s, e in r) * 1e-9
    return n / spent * 1e-9 if spent > 0 else None
