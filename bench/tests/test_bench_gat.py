"""The GAT's cell through the harness at a CPU size: its architecture file
(weights, reference, counts), the check that decides ``correct`` with the
program's softmax mask sound and broken, the limit's control, and the
reader of ``attention_gedges_per_s``."""
import json
import types

import jax.numpy as jnp
import numpy as np
import pytest

from bench import control, devtrace, harness
from bench.tests import tiny
from repro.core import executor

MS = 1_000_000  # nanoseconds
GAT = harness.load_model("gat")


def _cell(**traffic):
    cfg = json.loads((tiny.FIXTURES / "tiny-gat.json").read_text())
    return tiny.cell(traffic.pop("kind", "closed"), cfg=cfg, **traffic)


@pytest.fixture
def store(tmp_path):
    return tmp_path / "store"


def test_sound_gat_run_is_correct(store):
    line = tiny.run(_cell(outstanding=8), store)
    assert line["correct"] and line["failed"] == 0
    err = line["checks"]["max_rel_err"]
    assert err["value"] <= err["limit"]
    assert line["checks"]["rows_compared"]["value"] > 0


def test_a_broken_softmax_mask_is_not_correct(store, monkeypatch):
    """Every slot of the stream counted as an edge (empty PE slots and
    padding given ``val`` 1, as if the mask were gone)."""
    fwd = executor._batched_gat_jit

    def unmasked(geom, ops, params, xs):
        return fwd(geom, dict(ops, val=jnp.ones_like(ops["val"])), params, xs)

    monkeypatch.setattr(executor, "_batched_gat_jit", unmasked)
    line = tiny.run(_cell(outstanding=8), store)
    assert not line["correct"] and line["failed"] > 0


def test_gat_control_reads_above_the_limit_and_the_program_below(store):
    c = _cell(kind="poisson", rate_rps=150)
    got = control.readings(c, 5, 0.5, store, control=True, require_tpu=False)
    s = control.summary(got)
    assert s["program_max"] <= c.config["correct"]["max_rel_err"] < s["control_min"]
    lower = [r["max_rel_err"] for r in got if r["side"] == "control_reference_lower"]
    assert lower and min(lower) > c.config["correct"]["max_rel_err"]


def test_reference_agrees_with_the_programs_own_reference():
    """Two independent writings of equations 1-6: the benchmark's and
    ``repro.core.gat.forward``."""
    from repro.core import csc, gat

    cfg = json.loads((tiny.FIXTURES / "tiny-gat.json").read_text())
    (rows, cols, vals), dev = harness.make_graph(cfg)
    w = GAT.init_weights(cfg["sizes"], 2**33 + 7)
    x = harness.make_requests(cfg, 2**33 + 7)[0]
    got = GAT.reference_logits(x, w, dev, cfg["precision"], "cpu")
    n = cfg["sizes"]["num_nodes"]
    want = gat.forward(w, csc.coo_from_arrays(rows, cols, vals, (n, n)), jnp.asarray(x))
    assert np.abs(got - np.asarray(want)).max() <= 1e-5 * np.abs(want).max()


def test_gat_counts_at_pubmed_size():
    cfg = harness.load_json(harness.BENCH / "configs" / "gat-pubmed.json")
    sizes, nnz, n = cfg["sizes"], cfg["graph"]["nnz"], 19717
    assert GAT.layer_dims(sizes) == [(500, 8, 8), (64, 8, 3)]
    layer1 = 2 * n * 500 * 64 + 4 * n * 64 + nnz * 8 * (5 + 16)
    layer2 = 2 * n * 64 * 24 + 4 * n * 24 + nnz * 8 * (5 + 6)
    assert GAT.flops_per_request(sizes, nnz) == layer1 + layer2 + n * 8 * 3
    assert GAT.flops_per_request(sizes, nnz) == 1_360_485_720
    weights = (500 * 64 + 8 * 16 + 64 * 24 + 8 * 6) * 4
    assert GAT.batch_bytes(sizes, nnz, 0) == 2 * (nnz + n + 1) * 4 + weights
    assert GAT.batch_bytes(sizes, nnz, 8) - GAT.batch_bytes(sizes, nnz, 0) == (
        8 * n * (500 + 3) * 4)


def _gat_run(stats):
    d0 = devtrace.Device(
        0, ops=[(10 * MS, 30 * MS, "scatter.1"), (50 * MS, 80 * MS, "scatter.1")],
        modules=[(10 * MS, 30 * MS, "jit__batched_gat_body(3)"),
                 (50 * MS, 80 * MS, "jit__batched_gat_body(3)"),
                 (0, 5 * MS, "jit__batched_forward_body(1)")])
    tr = devtrace.Trace([d0], [(0, 100 * MS, "bench.window")])
    cfg = harness.load_json(harness.BENCH / "configs" / "gat-pubmed.json")
    cell = types.SimpleNamespace(chips=1, config=cfg, model=GAT)
    return types.SimpleNamespace(trace=tr, cell=cell, stats=stats)


def test_attention_gedges_per_s_is_the_counter_over_forward_device_time():
    read = harness.metric_reader("attention_gedges_per_s").read
    # 2.5e9 edge-heads over 20 + 30 ms of _batched_gat_body executions
    got = read(_gat_run({"attention_edge_heads": 2_500_000_000}))
    assert got == pytest.approx(50.0)


@pytest.mark.parametrize("stats", [{}, {"attention_edge_heads": 0}],
                         ids=["no-counter", "gcn"])
def test_attention_gedges_per_s_finds_nothing_on_a_gcn(stats):
    assert harness.metric_reader("attention_gedges_per_s").read(_gat_run(stats)) is None
    assert harness.metric_reader("attention_gedges_per_s").read(
        types.SimpleNamespace(**dict(vars(_gat_run({"attention_edge_heads": 5})),
                                     trace=None))) is None


def test_poisson_knee80_offers_a_fixed_rate():
    t = harness.load_json(harness.BENCH / "traffic" / "poisson-knee80.json")
    assert t["kind"] == "poisson" and t["deadline_s"] == 0
    assert isinstance(t["rate_rps"], (int, float)) and t["rate_rps"] > 0
    assert t["rate_rps"] % 10 == 0  # 0.8 of the knee, rounded down to tens
    cell = harness.resolve("pubmed-poisson")
    assert cell.traffic == t and cell.config["name"] == "gcn-pubmed"
