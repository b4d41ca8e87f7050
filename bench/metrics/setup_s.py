"""Seconds from the start of the process to the window's first scheduled
arrival: imports, the graph, the weights, admission (with the autotune
sweep on a checkout's first run), the request variants and the warm-up
(host clock)."""


def read(run):
    return run.setup_s
