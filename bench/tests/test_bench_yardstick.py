"""The benchmark's yardstick on the CPU: its generators are deterministic
per seed, the GCN's FLOP and byte counts (``bench/models/gcn.py``) give the
published sizes' numbers, and its reference agrees with a dense
``A @ (X @ W)``."""
import hashlib

import numpy as np
import pytest

from bench import graph, harness, reference, shapes

GCN = harness.load_model("gcn")

PUBMED = {"num_nodes": 19717, "num_features": 500, "hidden": 16, "num_classes": 3,
          "n_layers": 2}
CORA = {"num_nodes": 2708, "num_features": 1433, "hidden": 16, "num_classes": 7,
        "n_layers": 2}


def test_graph_is_fixed_by_its_seed():
    a = graph.power_law_adjacency(500, 0.01, 0.75, seed=0, max_degree=30)
    b = graph.power_law_adjacency(500, 0.01, 0.75, seed=0, max_degree=30)
    c = graph.power_law_adjacency(500, 0.01, 0.75, seed=1, max_degree=30)
    for x, y in zip(a, b):
        np.testing.assert_array_equal(x, y)
    assert a[0].shape != c[0].shape or not np.array_equal(a[1], c[1])
    rows, cols, vals = a
    assert (np.diff(rows * 500 + cols) > 0).all()  # row-major, no duplicates
    assert ((rows == cols).sum() == 500) and (vals > 0).all()


@pytest.mark.parametrize("seed", [0, 7, 2**33 + 5])
def test_requests_are_deterministic_per_seed(seed):
    cfg = harness.load_json(harness.BENCH / "tests" / "fixtures" / "tiny.json")
    a = harness.make_requests(cfg, seed)
    b = harness.make_requests(cfg, seed)
    assert len(a) == cfg["features"]["variants"]
    for x, y in zip(a, b):
        np.testing.assert_array_equal(x, y)
    other = harness.make_requests(cfg, seed + 1)
    assert not np.array_equal(a[0], other[0])
    assert not np.array_equal(a[0], a[1])  # variants differ from each other


@pytest.mark.parametrize("seed", [3, 2**31 + 11])
def test_poisson_arrivals_are_deterministic_and_of_fixed_count(seed):
    kind = harness.load_module(harness.BENCH / "traffic" / "kinds" / "poisson.py")
    a = kind.make({"rate_rps": 50}, seed, 10.0).times
    b = kind.make({"rate_rps": 50}, seed, 10.0).times
    c = kind.make({"rate_rps": 50}, seed + 1, 10.0).times
    np.testing.assert_array_equal(a, b)
    assert a.shape == c.shape == (500,) and not np.array_equal(a, c)
    assert (np.diff(a) >= 0).all() and 0 <= a[0] and a[-1] < 10.0


def test_poisson_hands_out_each_arrival_once():
    kind = harness.load_module(harness.BENCH / "traffic" / "kinds" / "poisson.py")
    gen = kind.make({"rate_rps": 100}, 5, 2.0)
    got = []
    for now in np.linspace(0.0, 2.0, 37):
        got += gen.take(float(now), 0)
    assert got == gen.times.tolist() and gen.finished(2.0)
    assert gen.next_time() is None


def test_closed_loop_keeps_its_outstanding_count():
    kind = harness.load_module(harness.BENCH / "traffic" / "kinds" / "closed.py")
    gen = kind.make({"outstanding": 16}, 1, 5.0)
    assert gen.take(0.0, 0) == [0.0] * 16
    assert gen.take(1.0, 10) == [1.0] * 6
    assert gen.take(5.0, 0) == [] and gen.finished(5.0)
    assert gen.warmup(8) == [16, 16, 16]


def test_flops_per_request_at_published_sizes():
    assert GCN.flops_per_request(PUBMED, 119584) == 321_909_024
    assert GCN.flops_per_request(CORA, 14770) == 125_464_060


def test_batch_bytes_and_roofline_bound_at_pubmed_batch_8():
    nbytes = GCN.batch_bytes(PUBMED, 119584, 8)
    assert nbytes == 319_468_112
    peak = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    least, bound = shapes.least_seconds(8 * 321_909_024, nbytes, peak)
    assert bound == "bytes"
    assert least == pytest.approx(390e-6, rel=0.01)
    # the FLOP term is some 13 us
    assert 8 * 321_909_024 / 197e12 == pytest.approx(13.07e-6, rel=0.01)


def test_peaks_table_names_the_v5e_and_refuses_others():
    assert harness.peak_table("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        harness.peak_table("TPU v9 imaginary")


def _dense_graph(n=60, seed=0):
    rows, cols, vals = graph.power_law_adjacency(
        n, 0.08, 0.75, seed=seed, max_degree=12
    )
    dense = np.zeros((n, n), np.float64)
    dense[rows, cols] = vals
    import jax.numpy as jnp

    g = {"n": n, "rows": jnp.asarray(rows, jnp.int32),
         "cols": jnp.asarray(cols, jnp.int32), "vals": jnp.asarray(vals)}
    return dense, g


def test_reference_agrees_with_dense_a_x_w():
    dense, g = _dense_graph()
    sizes = {"num_nodes": 60, "num_features": 24, "hidden": 16, "num_classes": 5,
             "n_layers": 2}
    w = GCN.init_weights(sizes, 9)
    x = graph.sparse_features(60, 24, 0.2, seed=4)
    exact = {"storage": "float32", "xw": "float32", "aggregate": "float32"}
    got = GCN.reference_logits(x, w, g, exact, "cpu")
    w0, w1 = (np.asarray(w[k], np.float64) for k in ("w0", "w1"))
    want = dense @ (np.maximum(dense @ (x.astype(np.float64) @ w0), 0) @ w1)
    assert reference.max_rel_err(got, want) < 1e-5


def test_stated_default_precision_rounds_operands_only_on_a_tpu():
    prec = {"storage": "float32", "xw": "default", "aggregate": "float32"}
    assert reference.operand_types(prec, "cpu") == ("float32", "float32", "float32")
    assert reference.operand_types(prec, "tpu") == ("float32", "bfloat16", "float32")
    with pytest.raises(ValueError):
        reference.operand_types(prec, "gpu")
    assert reference.lower_precision(prec) == {
        "storage": "bfloat16", "xw": "bfloat16", "aggregate": "bfloat16"}


def test_lower_precision_reference_reads_far_above_rounding():
    _, g = _dense_graph(n=200, seed=2)
    sizes = {"num_nodes": 200, "num_features": 64, "hidden": 16, "num_classes": 3,
             "n_layers": 2}
    w = GCN.init_weights(sizes, 3)
    x = graph.sparse_features(200, 64, 0.1, seed=1)
    prec = {"storage": "float32", "xw": "default", "aggregate": "float32"}
    want = GCN.reference_logits(x, w, g, prec, "cpu")
    low = GCN.reference_logits(x, w, g, reference.lower_precision(prec), "cpu")
    assert reference.max_rel_err(low, want) > 1e-3


def test_max_rel_err_refuses_wrong_shapes_and_non_finite():
    ref = np.ones((4, 3))
    assert reference.max_rel_err(ref, ref) == 0.0
    assert reference.max_rel_err(np.ones((4, 2)), ref) == float("inf")
    bad = ref.copy()
    bad[0, 0] = np.nan
    assert reference.max_rel_err(bad, ref) == float("inf")


def test_weights_are_glorot_and_fixed_by_the_seed():
    sizes = {"num_nodes": 10, "num_features": 500, "hidden": 16, "num_classes": 3,
             "n_layers": 2}
    a, b = GCN.init_weights(sizes, 2**33), GCN.init_weights(sizes, 2**33)
    np.testing.assert_array_equal(np.asarray(a["w0"]), np.asarray(b["w0"]))
    assert a["w0"].shape == (500, 16) and a["w1"].shape == (16, 3)
    assert float(np.abs(np.asarray(a["w0"])).max()) <= np.sqrt(6 / 516)
    c = GCN.init_weights(sizes, 2**33 + 1)
    assert not np.array_equal(np.asarray(a["w0"]), np.asarray(c["w0"]))


def test_bf16_rounding_on_the_bits_matches_a_cast():
    import jax.numpy as jnp

    x = np.random.default_rng(0).standard_normal(10000).astype(np.float32) * 7
    got = np.asarray(reference.round_to(jnp.asarray(x), "bfloat16"))
    want = np.asarray(jnp.asarray(x).astype(jnp.bfloat16).astype(jnp.float32))
    np.testing.assert_array_equal(got, want)
    assert reference.round_to(x, "float32") is x


def test_gcn_weights_and_logits_are_those_of_before_the_move():
    """Digests taken from the GCN weights and reference of
    ``bench/reference.py`` before they moved to ``bench/models/gcn.py``:
    the tiny fixture at one seed, the stated precision as on the CPU and as
    on a TPU (X·W operands rounded to bfloat16)."""
    cfg = harness.load_json(harness.BENCH / "tests" / "fixtures" / "tiny.json")
    seed = 2**33 + 7
    w = GCN.init_weights(cfg["sizes"], seed)
    h = hashlib.sha256()
    for k in sorted(w):
        a = np.asarray(w[k])
        h.update(k.encode() + str(a.shape).encode() + a.tobytes())
    assert h.hexdigest() == (
        "fb610ecb4dff236346227d6996bfaa98647d80bd6bdde584a5e5741f9e066c18")
    _, g = harness.make_graph(cfg)
    x = harness.make_requests(cfg, seed)[0]
    for platform, digest in (
        ("cpu", "c5bc1da19c51db7c11e8464801aa0c1d258df7bf55c5dd100917a531ddf86cd2"),
        ("tpu", "4f98b9cf2cfcc122e59be6e6809669326b01c77625776f054359ffc87e8c725e"),
    ):
        out = GCN.reference_logits(x, w, g, cfg["precision"], platform)
        assert out.dtype == np.float32 and out.shape == (300, 3)
        assert hashlib.sha256(out.tobytes()).hexdigest() == digest, platform
