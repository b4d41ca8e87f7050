"""The reader of the engine's overlap counter: the share of batches
dispatched while an earlier one was in flight, and nothing where the
program has no such counter."""
import types

import pytest

from bench import harness


def _run(stats):
    return types.SimpleNamespace(stats=stats)


@pytest.mark.parametrize("overlapped, batches, share", [
    (0, 4, 0.0), (167, 168, 100.0 * 167 / 168), (12, 12, 100.0)])
def test_overlap_share_is_overlapped_over_batches(overlapped, batches, share):
    stats = {"batches": batches, "requests": 8 * batches,
             "overlapped_batches": overlapped}
    got = harness.metric_reader("overlap_share").read(_run(stats))
    assert got == pytest.approx(share)


@pytest.mark.parametrize("stats", [
    {"batches": 168, "requests": 1344},  # a program without the counter
    {"batches": 0, "requests": 0, "overlapped_batches": 0},
    {"requests": 0, "overlapped_batches": 0},
], ids=["no-counter", "no-batches", "no-batch-count"])
def test_overlap_share_finds_nothing_without_batches_or_counter(stats):
    assert harness.metric_reader("overlap_share").read(_run(stats)) is None
