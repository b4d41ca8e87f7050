"""The check that decides ``correct`` fails when the timed path is broken.

Each test drives the rest of a run on the CPU at a tiny size — the look for
a chip skipped — with the served path broken underneath, and sees
``correct`` come out false: an answer altered where it is produced, half
of a batch left out, and (on four virtual devices) the exchange of a
replica's chunk left out; and a reference that reads twice the logits. The
sound run reads true, and the control (the program's own bfloat16
accumulation) reads above the limit.
"""
import json
import os
import subprocess
import sys
import textwrap

import jax.numpy as jnp
import pytest

from bench import control, harness
from bench.harness import ROOT
from bench.tests import tiny
from repro.core import executor


@pytest.fixture
def store(tmp_path):
    return tmp_path / "store"


def test_sound_run_is_correct(store):
    line = tiny.run(tiny.cell("closed", outstanding=8), store)
    assert line["correct"] and line["failed"] == 0
    err = line["checks"]["max_rel_err"]
    assert err["value"] <= err["limit"]
    assert line["attempted"] > 0 and line["checks"]["rows_compared"]["value"] > 0


def test_an_altered_answer_is_not_correct(store, monkeypatch):
    fwd = executor._batched_forward_jit

    def altered(geom, ops, params, xs):
        out = fwd(geom, ops, params, xs)
        return out.at[0, 0, 0].add(1e-2 * jnp.abs(out).max())

    monkeypatch.setattr(executor, "_batched_forward_jit", altered)
    line = tiny.run(tiny.cell("poisson", rate_rps=150), store)
    assert not line["correct"] and line["failed"] > 0


def test_half_of_the_batch_left_out_is_not_correct(store, monkeypatch):
    fwd = executor._batched_forward_jit

    def half(geom, ops, params, xs):
        b = xs.shape[0]
        h = max(1, b // 2)
        out = fwd(geom, ops, params, xs[:h])
        return jnp.concatenate([out, out[: b - h]])

    monkeypatch.setattr(executor, "_batched_forward_jit", half)
    line = tiny.run(tiny.cell("closed", outstanding=8), store)
    assert not line["correct"] and line["failed"] > 0


def test_a_model_whose_reference_doubles_the_logits_is_not_correct(store, tmp_path):
    """The check takes its reference from the cell's model file: a copy of
    ``bench/models/gcn.py`` whose reference doubles the logits fails a sound
    run of the program."""
    src = (harness.BENCH / "models" / "gcn.py").read_text()
    path = tmp_path / "gcn_doubled_reference.py"
    path.write_text(src + "\n\n_reference_logits = reference_logits\n\n\n"
                    "def reference_logits(*args):\n"
                    "    return 2 * _reference_logits(*args)\n")
    c = tiny.cell("closed", model=harness.load_module(path), outstanding=8)
    line = tiny.run(c, store)
    assert not line["correct"] and line["failed"] > 0
    assert line["checks"]["max_rel_err"]["value"] == pytest.approx(0.5, rel=1e-3)


def test_warm_up_compiles_the_join_of_settled_batches(store, monkeypatch):
    """A poll that finds both batches of the closed loop settled joins them
    into one result. Set-up compiles that join even where no warm-up poll
    finds a later batch settled (as on a chip, where the next batch's copy
    is still running), so serving such a poll compiles nothing."""
    import jax

    from bench.clock import CompileClock
    from repro.serving.gcn_engine import GCNServingEngine

    jax.clear_caches()
    monkeypatch.setattr(GCNServingEngine, "_settled", staticmethod(lambda b: False))
    c = tiny.cell("closed", outstanding=8)
    s = harness.setup(c, 11, store)
    clock = CompileClock()
    for i in range(8):
        s.eng.submit(harness.GID, s.xs[i % len(s.xs)], deadline_s=0)
    out = s.eng.flush()[harness.GID]
    assert out.shape[0] == 8
    _, compiles, _, names = clock.take()
    assert compiles == 0, names


def test_control_reads_above_the_limit_and_the_program_below(store):
    c = tiny.cell("poisson", rate_rps=150)
    got = control.readings(c, 5, 0.5, store, control=True, require_tpu=False)
    limit = c.config["correct"]["max_rel_err"]
    s = control.summary(got)
    assert s["program_max"] <= limit < s["control_min"]


FOUR_DEVICES = textwrap.dedent(
    """
    import json, sys
    sys.path[:0] = [{root!r}, {src!r}]
    import jax
    from bench.tests import tiny
    from repro.core import executor

    cfg = tiny.config(chips=4, replicas=4)
    cfg["deployment"]["engine"].update(
        devices=4, max_replicas=4, max_batch=8, replicate_after_s=1e-6)
    cell = tiny.cell("closed", cfg, chips=4, outstanding=16)
    sound = tiny.run(cell, {store!r} + "/a")
    put = jax.device_put

    def commit(self, x):  # the chunk never crosses: zeros arrive instead
        return x if self.device is None else put(jax.numpy.zeros_like(x), self.device)

    executor._ExecutorBase.commit = commit
    broken = tiny.run(cell, {store!r} + "/b")
    print(json.dumps([sound["correct"], broken["correct"], broken["failed"]]))
    """
)


def test_replica_exchange_left_out_is_not_correct(tmp_path):
    script = FOUR_DEVICES.format(
        root=str(ROOT), src=str(ROOT / "src"), store=str(tmp_path)
    )
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    proc = subprocess.run([sys.executable, "-c", script], env=env, capture_output=True,
                          text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    sound, broken, failed = json.loads(proc.stdout.strip().splitlines()[-1])
    assert sound is True
    assert broken is False and failed > 0
