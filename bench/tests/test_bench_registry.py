"""The harness finds a cell's files by name: every cell of
``BENCHMARK.json`` loads, and a cell added as files alone is found."""
import json
import shutil

import bench_tiny
import pytest

from bench import cell as cell_lib

SPEC = json.loads((bench_tiny.ROOT / "BENCHMARK.json").read_text())


@pytest.mark.parametrize("workload", [w["name"] for w in SPEC["workloads"]])
def test_every_cell_loads(workload):
    cell = cell_lib.load_cell(workload)
    assert cell.chips in (1, 4)
    assert {m["name"] for m in cell.end_to_end} >= {"setup_s"}
    assert cell.per_layer and set(cell.readers) == {
        m["name"] for m in cell.per_layer}
    assert set(cell.limits) == {"loss_gap", "grad_gap", "delta_gap"}
    for k in ("batch_per_chip", "seq", "sparsifier", "optimizer"):
        assert k in cell.traffic


def test_cell_added_as_files_alone_is_found(tmp_path):
    """A new configuration, traffic mix, per-layer metric and cell, added
    as files and entries only, load without a change to the harness."""
    bench = tmp_path / "bench"
    for sub in ("configs", "traffic", "limits", "metrics"):
        (bench / sub).mkdir(parents=True)
    src = bench_tiny.BENCH
    shutil.copy(src / "configs" / "whisper-tiny.py",
                bench / "configs" / "whisper-small.py")
    cfg = json.loads((src / "configs" / "whisper-tiny.json").read_text())
    cfg["name"] = "whisper-small"
    (bench / "configs" / "whisper-small.json").write_text(json.dumps(cfg))
    mix = json.loads((src / "traffic" / "regtopk.b40x448.json").read_text())
    mix["batch_per_chip"] = 8
    (bench / "traffic" / "regtopk.b8x448.json").write_text(json.dumps(mix))
    (bench / "limits" / "whisper-small.regtopk.1chip.json").write_text(
        json.dumps({"loss_gap": 1, "grad_gap": 1, "delta_gap": 1}))
    (bench / "metrics" / "optimizer_ms.py").write_text(
        "def read(ctx):\n    return 1.5\n")
    spec = {
        "configs": [{"name": "whisper-small", "source": "x",
                     "file": "bench/configs/whisper-small.json",
                     "reduced": [], "why": "x"}],
        "workloads": [{"name": "whisper-small.regtopk.1chip",
                       "config": "whisper-small", "traffic": "regtopk.b8x448",
                       "chips": 1, "why": "x"}],
        "end_to_end": [{"name": "setup_s", "unit": "s"}],
        "per_layer": [
            {"name": "optimizer_ms", "unit": "ms", "layer": "optimizer",
             "moves": "tokens_per_s"},
            {"name": "elsewhere_ms", "unit": "ms", "layer": "x",
             "moves": "tokens_per_s", "workloads": ["other"]}],
    }
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    cell = cell_lib.load_cell("whisper-small.regtopk.1chip", root=tmp_path,
                              bench_dir=bench)
    assert cell.traffic["batch_per_chip"] == 8
    assert cell.config["name"] == "whisper-small"
    assert cell.model.param_count(cell.config["model"]) == 36_487_680
    assert [m["name"] for m in cell.per_layer] == ["optimizer_ms"]
    assert cell.readers["optimizer_ms"].read(None) == 1.5
    with pytest.raises(KeyError):
        cell_lib.load_cell("missing", root=tmp_path, bench_dir=bench)
