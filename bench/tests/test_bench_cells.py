"""``BENCHMARK.json`` and the files it names: every workload resolves its
configuration, architecture, traffic mix, generator kind and metric readers
by name, and the file keeps the shape the benchmark's runner relies on."""
import json
import re

import pytest

from bench import harness

BM = json.loads((harness.ROOT / "BENCHMARK.json").read_text())
WORKLOADS = [w["name"] for w in BM["workloads"]]
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


@pytest.mark.parametrize("workload", WORKLOADS)
def test_workload_resolves_its_files_by_name(workload):
    cell = harness.resolve(workload, BM)
    assert cell.config["deployment"]["chips"] == cell.chips
    for name in ("init_weights", "reference_logits", "flops_per_request",
                 "batch_bytes"):
        assert callable(getattr(cell.model, name)), name
    assert isinstance(cell.model.FORWARD_MODULE, str) and cell.model.FORWARD_MODULE
    gen = cell.kind.make(cell.traffic, 1, 1.0)
    assert callable(gen.take) and callable(gen.warmup)
    for m in cell.end_to_end + cell.per_layer:
        assert callable(harness.metric_reader(m["name"]).read), m["name"]
    # every cell reports setup_s, another end-to-end metric and a per-layer one
    names = {m["name"] for m in cell.end_to_end}
    assert "setup_s" in names and len(names) >= 2
    assert cell.per_layer


@pytest.mark.parametrize("workload", WORKLOADS)
def test_per_layer_metrics_move_a_metric_their_cells_report(workload):
    cell = harness.resolve(workload, BM)
    e2e = {m["name"] for m in cell.end_to_end}
    for m in cell.per_layer:
        assert m["moves"] in e2e, (m["name"], m["moves"])


def test_names_units_and_keys_keep_to_the_format():
    assert set(BM) == {"command", "paths", "run_seconds", "configs", "workloads",
                       "end_to_end", "per_layer"}
    assert 1 <= BM["run_seconds"] <= 51
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [e["name"] for e in BM[group]]
        assert len(names) == len(set(names)), group
        assert all(NAME.match(n) for n in names), group
    for m in BM["end_to_end"] + BM["per_layer"]:
        assert re.match(r"^[A-Za-z0-9_/%.-]{1,16}$", m["unit"]), m
        assert m["better"] in ("lower", "higher")
    for m in BM["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    four = [w for w in BM["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(BM["workloads"]) // 2)


@pytest.mark.parametrize("entry", BM["configs"], ids=lambda e: e["name"])
def test_config_files_match_their_flop_counts(entry):
    cfg = json.loads((harness.ROOT / entry["file"]).read_text())
    assert cfg["name"] == entry["name"]
    rows, _, _ = harness.graph.power_law_adjacency(
        cfg["sizes"]["num_nodes"], cfg["graph"]["density"], cfg["graph"]["alpha"],
        seed=cfg["graph"]["seed"], max_degree=cfg["graph"]["max_degree"],
    )
    assert rows.shape[0] == cfg["graph"]["nnz"]
    model = harness.load_model(cfg["arch"])
    assert model.flops_per_request(cfg["sizes"], rows.shape[0]) > 0
    assert isinstance(cfg["correct"]["max_rel_err"], float)


@pytest.mark.parametrize("arch, says", [
    ("no-such-arch", "bench/models/no-such-arch.py"),
    (None, "names no 'arch'"),
], ids=["no-file", "no-arch"])
def test_a_config_must_name_an_architecture_that_has_a_file(tmp_path, arch, says):
    cfg = json.loads((harness.ROOT / BM["configs"][0]["file"]).read_text())
    del cfg["arch"]
    if arch is not None:
        cfg["arch"] = arch
    (tmp_path / "cfg.json").write_text(json.dumps(cfg))
    bm = dict(BM, configs=[dict(BM["configs"][0], file="cfg.json")],
              workloads=[dict(BM["workloads"][0], name="w")])
    with pytest.raises((KeyError, FileNotFoundError), match=re.escape(says)):
        harness.resolve("w", bm, root=tmp_path)
