"""The benchmark finds every piece by name, and new cells need only new
files and entries."""
import hashlib
import json
import shutil

import pytest

from bench import arrivals, spec

BENCH = spec.benchmark()


def test_peaks_by_device_kind():
    p = spec.peaks("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12
    assert p["int8_ops_per_s"] == 393e12
    assert p["hbm_bytes_per_s"] == 819e9
    with pytest.raises(spec.SpecError, match="not in bench/peaks.json"):
        spec.peaks("TPU v9 imaginary")


@pytest.mark.parametrize("cell", [w["name"] for w in BENCH["workloads"]])
def test_every_cell_resolves(cell):
    c = spec.cell(BENCH, cell)
    assert c["name"] == f"{c['config']}.{c['traffic']}"
    config, traffic = spec.config(BENCH, c), spec.traffic(c)
    spec.module("data", config["data"]["generator"])
    spec.module("work", config["estimator"])
    e2e = spec.metrics(BENCH, cell, trace=False)
    layer = spec.metrics(BENCH, cell, trace=True)
    names = {m["name"] for m in e2e}
    assert "setup_s" in names and len(names) >= 2 and layer
    for m in e2e + layer:
        assert callable(spec.module("metrics", m["name"]).read)
    for m in layer:
        assert m["moves"] in names
    if traffic["loop"] == "open" and "knee_share" in traffic["rate"]:
        assert config["knee_qps"] > 0


def test_per_layer_metrics_move_a_metric_each_of_their_cells_reports():
    for m in BENCH["per_layer"]:
        for cell in m["workloads"]:
            reported = {e["name"] for e in spec.metrics(BENCH, cell, False)}
            assert m["moves"] in reported, (m["name"], cell)


def _digest(root):
    h = hashlib.sha256()
    for p in sorted((root / "bench").rglob("*")):
        if p.is_file() and "__pycache__" not in p.parts:
            h.update(p.read_bytes())
    h.update((root / "BENCHMARK.json").read_bytes())
    return h.hexdigest()


def test_a_new_mix_and_cell_need_only_new_files(tmp_path):
    shutil.copytree(spec.ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(spec.ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    before = _digest(tmp_path)
    bench = json.loads((spec.ROOT / "BENCHMARK.json").read_text())
    (tmp_path / "bench" / "traffic" / "burst.json").write_text(json.dumps({
        "loop": "open", "arrivals": "poisson", "rate": {"qps": 300},
        "k": 10}))
    bench["workloads"].append({"name": "sift1m-flat.burst",
                               "config": "sift1m-flat", "traffic": "burst",
                               "chips": 1, "why": "test"})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    c = spec.cell(bench, "sift1m-flat.burst")
    traffic = spec.traffic(c, tmp_path)
    config = spec.config(bench, c, tmp_path)
    assert len(arrivals.open_schedule(traffic, config, 1, 2.0).due) == 600
    # a metric without a workload list follows the cells of what it moves
    assert "setup_s" in {m["name"] for m in spec.metrics(bench, c["name"],
                                                         False)}
    # the files the benchmark already had are as they were
    (tmp_path / "bench" / "traffic" / "burst.json").unlink()
    (tmp_path / "BENCHMARK.json").write_text(
        (spec.ROOT / "BENCHMARK.json").read_text())
    assert _digest(tmp_path) == before


def test_a_suffixed_metric_is_read_by_its_quantity_unless_it_has_a_file(
        tmp_path):
    mod = spec.module("metrics", "idle_share.closed")
    assert mod.__file__.endswith("metrics/idle_share.py")
    assert spec.module("metrics", "step_mfu.open").read is not None
    shutil.copytree(spec.ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    own = tmp_path / "bench" / "metrics" / "idle_share.closed.py"
    own.write_text("def read(rec):\n    return 42.0\n")
    assert spec.module("metrics", "idle_share.closed",
                       tmp_path).read(None) == 42.0
    assert spec.module("metrics", "idle_share.open",
                       tmp_path).__file__.endswith("metrics/idle_share.py")


def test_unknown_names_are_errors():
    with pytest.raises(spec.SpecError):
        spec.cell(BENCH, "no-such.cell")
    with pytest.raises(spec.SpecError):
        spec.module("metrics", "no_such_metric")
    with pytest.raises(spec.SpecError, match="no_such_metric.open"):
        spec.module("metrics", "no_such_metric.open")
    with pytest.raises(spec.SpecError):
        spec.traffic({"traffic": "no-such-mix"})
