"""The engine's own per-stage host times (``stats()["stages"]``: the
``engine.*`` spans, timed on the host clock inside the program), shared by
the readers of the engine's host path."""


def mean_ms(run, stage: str):
    """Mean duration of one ``engine.<stage>`` span over the window and its
    drain, in ms; None where the program records no such stage."""
    s = (run.stats.get("stages") or {}).get(stage)
    if not s or not s.get("n"):
        return None
    return s["s"] / s["n"] * 1e3
