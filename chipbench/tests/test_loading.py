"""A cell, a configuration, a traffic mix and a per-layer metric are
files found by name: new ones need no change to the harness."""
import json
import shutil

import pytest

import harness


def _tree(tmp_path, extra_layer=None):
    root = tmp_path / "repo"
    bench = root / "chipbench"
    for sub in ("configs", "traffic", "workloads", "metrics"):
        (bench / sub).mkdir(parents=True)
    (bench / "configs" / "toy.json").write_text(json.dumps({"name": "toy"}))
    (bench / "traffic" / "toy-mix.json").write_text(
        json.dumps({"kind": "train", "seq": 8}))
    (bench / "workloads" / "toy-cell.json").write_text(
        json.dumps({"workers": 2}))
    (bench / "metrics" / "toy.share.py").write_text(
        "def read(rec):\n    return 2.0 * rec['x']\n")
    spec = {
        "configs": [{"name": "toy", "file": "chipbench/configs/toy.json"}],
        "workloads": [{"name": "toy-cell", "config": "toy",
                       "traffic": "toy-mix", "chips": 1},
                      {"name": "other-cell", "config": "toy",
                       "traffic": "toy-mix", "chips": 1}],
        "end_to_end": [{"name": "rate", "unit": "1/s",
                        "workloads": ["toy-cell"]},
                       {"name": "setup_s", "unit": "s"}],
        "per_layer": [{"name": "toy.share", "unit": "%", "moves": "rate",
                       "workloads": ["toy-cell"]}],
    }
    if extra_layer:
        spec["per_layer"].append(extra_layer)
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    return root, bench


def test_new_files_are_found_by_name(tmp_path):
    root, bench = _tree(tmp_path)
    b = harness.Bench(root=root, bench_dir=bench)
    cell = b.cell("toy-cell")
    assert cell["config"] == {"name": "toy"}
    assert cell["traffic"]["seq"] == 8
    assert cell["deploy"] == {"workers": 2}
    assert cell["driver"] == bench / "drivers" / "train.py"
    assert [m["name"] for m in b.end_to_end("toy-cell")] == ["rate",
                                                             "setup_s"]
    assert [m["name"] for m in b.end_to_end("other-cell")] == ["setup_s"]
    assert [m["name"] for m in b.per_layer("toy-cell")] == ["toy.share"]
    assert b.per_layer("other-cell") == []
    assert b.reader("toy.share").read({"x": 1.5}) == 3.0


def test_metric_whose_cell_lacks_its_moved_metric_is_refused(tmp_path):
    root, bench = _tree(tmp_path, extra_layer={
        "name": "bad.share", "unit": "%", "moves": "rate",
        "workloads": ["other-cell"]})
    with pytest.raises(harness.BenchError, match="does not report rate"):
        harness.Bench(root=root, bench_dir=bench)


def test_metric_without_cells_must_fit_every_cell(tmp_path):
    root, bench = _tree(tmp_path, extra_layer={
        "name": "all.share", "unit": "%", "moves": "rate"})
    with pytest.raises(harness.BenchError):
        harness.Bench(root=root, bench_dir=bench)


def test_missing_file_is_an_error(tmp_path):
    root, bench = _tree(tmp_path)
    shutil.rmtree(bench / "workloads")
    with pytest.raises(FileNotFoundError):
        harness.Bench(root=root, bench_dir=bench).cell("toy-cell")


def test_the_committed_benchmark_loads():
    b = harness.Bench()
    for w in b.spec["workloads"]:
        cell = b.cell(w["name"])
        assert cell["driver"].is_file()
        for m in b.per_layer(w["name"]):
            assert callable(b.reader(m["name"]).read)
        assert any(m["name"] == "setup_s" for m in b.end_to_end(w["name"]))
