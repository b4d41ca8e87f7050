"""The readers of the engine's own stage times (``stats()["stages"]``):
the mean of each ``engine.*`` span in ms, and nothing where the program
records no such stage."""
import types

import pytest

from bench import harness

READERS = {"copy_ms.mean": "copy", "dispatch_ms.mean": "dispatch",
           "await_ms.mean": "await"}
STAGES = {
    "copy": {"n": 16, "s": 0.064, "self_s": 0.064},
    "dispatch": {"n": 2, "s": 0.003, "self_s": 0.001},
    "await": {"n": 2, "s": 0.040, "self_s": 0.040},
}


def _run(stats):
    return types.SimpleNamespace(stats=stats)


@pytest.mark.parametrize("name, stage", sorted(READERS.items()))
def test_stage_readers_give_the_mean_span_in_ms(name, stage):
    got = harness.metric_reader(name).read(_run({"stages": STAGES}))
    assert got == pytest.approx(STAGES[stage]["s"] / STAGES[stage]["n"] * 1e3)


@pytest.mark.parametrize("name", sorted(READERS))
@pytest.mark.parametrize("stats", [
    {"requests": 3},  # a program without the stage recorder
    {"stages": {}},
    {"stages": {"copy": {"n": 0, "s": 0.0, "self_s": 0.0},
                "dispatch": {"n": 0, "s": 0.0, "self_s": 0.0},
                "await": {"n": 0, "s": 0.0, "self_s": 0.0}}},
], ids=["no-stages", "empty", "zero-spans"])
def test_stage_readers_find_nothing_without_the_stage(name, stats):
    assert harness.metric_reader(name).read(_run(stats)) is None
