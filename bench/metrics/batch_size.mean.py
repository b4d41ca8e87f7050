"""Queueing and dispatch: requests per batch the engine served in the
window and its drain (``stats()`` ``requests / batches``, counted by the
program)."""


def read(run):
    b = run.stats.get("batches", 0)
    return run.stats["requests"] / b if b else None
