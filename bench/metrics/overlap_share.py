"""Queueing and dispatch: the share of the engine's batches that were
dispatched while an earlier batch was still in flight, so that their
host-to-device copies could overlap its forward, in % (``stats()``
``overlapped_batches / batches``, counted by the program); None where the
program has no such counter."""


def read(run):
    n = run.stats.get("overlapped_batches")
    b = run.stats.get("batches", 0)
    if n is None or not b:
        return None
    return 100.0 * n / b
