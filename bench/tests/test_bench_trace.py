"""The reduction from a profiler trace to the per-layer metrics, on traces
whose numbers are known."""
import types

import numpy as np
import pytest

from bench import devtrace, harness

MS = 1_000_000  # nanoseconds
FORWARD = harness.load_model("gcn").FORWARD_MODULE


def _trace():
    # window 0..100 ms; device 0 busy 10..30 and 50..60 (ops overlap inside),
    # device 1 busy 40..90; host spans: submit 0..35, poll 35..70, idle 70..100
    d0 = devtrace.Device(
        0,
        ops=[(10 * MS, 25 * MS, "fusion.1"), (20 * MS, 30 * MS, "gather.2"),
             (50 * MS, 60 * MS, "fusion.1")],
        modules=[(10 * MS, 30 * MS, "jit__batched_forward_body(7)"),
                 (50 * MS, 60 * MS, "jit__batched_forward_body(7)"),
                 (0, 1 * MS, "jit_other(1)")],
    )
    d1 = devtrace.Device(
        1,
        ops=[(40 * MS, 90 * MS, "scatter.3")],
        modules=[(40 * MS, 90 * MS, "jit__batched_forward_body(9)")],
    )
    spans = [(0, 100 * MS, "bench.window"), (0, 35 * MS, "bench.submit"),
             (35 * MS, 70 * MS, "bench.poll"), (70 * MS, 100 * MS, "bench.idle_wait")]
    return devtrace.Trace([d0, d1], spans)


def test_busy_idle_and_gaps():
    tr = _trace()
    lo, hi = tr.window()
    assert (lo, hi) == (0, 100 * MS)
    assert devtrace.busy_ns(tr.devices[0], lo, hi) == 30 * MS
    assert devtrace.busy_ns(tr.devices[1], lo, hi) == 50 * MS
    assert devtrace.gaps(tr.devices[0], lo, hi) == [
        (0, 10 * MS), (30 * MS, 50 * MS), (60 * MS, 100 * MS)]


def test_idle_by_span_splits_gaps_over_host_spans():
    got = devtrace.idle_by_span(_trace(), 0, 100 * MS)
    # device 0 idle: submit 10+5, poll 15+10, idle_wait 30; device 1 idle:
    # submit 35, poll 5, idle_wait 10 -> means over the two devices
    assert got == pytest.approx({"bench.submit": 0.025, "bench.poll": 0.015,
                                 "bench.idle_wait": 0.020})


def test_op_seconds_and_breakdown():
    tr = _trace()
    assert devtrace.op_seconds(tr, 0, 100 * MS) == pytest.approx(
        {"fusion.1": 0.025, "gather.2": 0.010, "scatter.3": 0.050})
    b = harness.breakdown(tr)
    assert b["device_ops"][0] == ["scatter.3", pytest.approx(0.05)]
    assert [n for n, _ in b["idle_gaps"]] == ["bench.submit", "bench.idle_wait",
                                              "bench.poll"]


def _run(tr, chips=2):
    cfg = harness.load_json(harness.BENCH / "configs" / "gcn-pubmed.json")
    cell = types.SimpleNamespace(chips=chips, config=cfg,
                                 model=harness.load_model(cfg["arch"]))
    done = np.array([0.05, 0.09, 0.2])
    return types.SimpleNamespace(
        trace=tr, cell=cell, nnz=cfg["graph"]["nnz"], seconds=0.1,
        stats={"requests": 24, "batches": 3}, done=done,
        peak=harness.peak_table("TPU v5 lite"),
    )


def test_per_layer_readers_reduce_the_trace():
    run = _run(_trace())
    idle = harness.metric_reader("device_idle_share").read(run)
    assert idle == pytest.approx(100 * (1 - (30 + 50) / 2 / 100))
    fwd = harness.metric_reader("forward_device_ms.backlog").read(run)
    assert fwd == pytest.approx(20.0)  # median of 20, 10 and 50 ms
    roof = harness.metric_reader("forward_roofline").read(run)
    sizes, nnz, gcn = run.cell.config["sizes"], run.nnz, run.cell.model
    per_req = gcn.batch_bytes(sizes, nnz, 1) - gcn.batch_bytes(sizes, nnz, 0)
    nbytes = 24 * per_req + 3 * gcn.batch_bytes(sizes, nnz, 0)
    assert roof == pytest.approx(100 * nbytes / 819e9 / 0.08)
    mfu = harness.metric_reader("mfu").read(run)
    assert mfu == pytest.approx(
        100 * 2 * 321_909_024 / (0.1 * 2 * 197e12))


def test_a_model_that_doubles_its_flops_doubles_mfu(tmp_path):
    """The readers take the work counts from the cell's model file: a copy
    of ``bench/models/gcn.py`` whose ``flops_per_request`` doubles reads
    twice the ``mfu`` of the GCN on the same run."""
    src = (harness.BENCH / "models" / "gcn.py").read_text()
    path = tmp_path / "gcn_doubled_flops.py"
    path.write_text(src + "\n\n_flops = flops_per_request\n\n\n"
                    "def flops_per_request(sizes, nnz):\n"
                    "    return 2 * _flops(sizes, nnz)\n")
    run = _run(_trace())
    mfu = harness.metric_reader("mfu").read(run)
    run.cell.model = harness.load_module(path)
    assert harness.metric_reader("mfu").read(run) == pytest.approx(2 * mfu)


def test_readers_find_nothing_without_a_trace():
    run = _run(None)
    for name in ("device_idle_share", "forward_device_ms.backlog",
                 "forward_roofline", "mfu"):
        assert harness.metric_reader(name).read(run) is None


def test_require_accepts_a_readable_trace():
    devtrace.require(_trace(), 2, FORWARD)


@pytest.mark.parametrize("broken, says", [
    ("no_ops", "no 'XLA Ops' event"),
    ("no_forward", "no execution of"),
    ("one_plane", "device planes"),
])
def test_require_refuses_a_trace_the_readers_cannot_read(broken, says):
    tr = _trace()
    if broken == "no_ops":
        tr.devices[1].ops = []
    elif broken == "no_forward":
        for d in tr.devices:
            d.modules = [m for m in d.modules if FORWARD not in m[2]]
    else:
        tr.devices = tr.devices[:1]
    with pytest.raises(ValueError, match=says):
        devtrace.require(tr, 2, FORWARD)
