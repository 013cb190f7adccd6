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


def test_four_chip_cell_resolves():
    """The cell whose payloads cross chips: whisper-tiny's configuration,
    the 40 x 448 RegTop-k mix of one chip for four workers (a mix of its
    own: a pair of configuration and mix names one cell), limits of its
    own, and the wire's reader besides the round's."""
    cell = cell_lib.load_cell("whisper-tiny.regtopk.4chip")
    one = cell_lib.load_cell("whisper-tiny.regtopk.1chip")
    assert cell.chips == 4 and cell.traffic["workers"] == 4
    assert cell.config == one.config
    assert cell.traffic == dict(one.traffic, workers=4)
    assert cell.traffic["batch_per_chip"] == 40 and cell.traffic["seq"] == 448
    assert cell.traffic["sparsifier"]["kind"] == "regtopk"
    assert cell.traffic["collective"] == "sparse_allgather"
    assert set(cell.limits) == {"loss_gap", "grad_gap", "delta_gap"}
    assert set(cell.readers) == set(one.readers) | {"exchange_ms"}


@pytest.mark.parametrize("workload,traffic", [
    ("whisper-tiny.regtopk.4chip", "regtopk.b40x448"),
    ("whisper-tiny.regtopk.1chip", "regtopk.b40x448.dp4"),
])
def test_cell_whose_chips_are_not_its_mix_workers_is_refused(
        tmp_path, workload, traffic):
    spec = json.loads(json.dumps(SPEC))
    for w in spec["workloads"]:
        if w["name"] == workload:
            w["traffic"] = traffic
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    with pytest.raises(ValueError, match="workers"):
        cell_lib.load_cell(workload, root=tmp_path)


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


def test_configuration_cell_and_scope_reader_added_as_files_alone(tmp_path):
    """A copy of the benchmark with one more configuration (whisper-tiny's
    files under a new name, with tiny sizes of its own), one more cell and
    one more reader, of a named scope: the cell loads, its configuration
    is what the program runs, the tiny reference matches the program, and
    the reader reads its scope from a recorded reduction, with no file of
    the benchmark edited."""
    from test_bench_configs import check_config_is_what_runs
    from test_bench_trace import recorded

    from bench import run
    from bench import trace as T

    bench = tmp_path / "bench"
    shutil.copytree(bench_tiny.BENCH, bench,
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = {p.relative_to(bench): p.read_bytes()
              for p in bench.rglob("*") if p.is_file()}
    cfg = json.loads((bench / "configs" / "whisper-tiny.json").read_text())
    cfg["name"] = "whisper-twin"
    cfg["tiny"] = dict(cfg["tiny"], d_model=48, n_heads=3, vocab=300)
    (bench / "configs" / "whisper-twin.json").write_text(json.dumps(cfg))
    shutil.copy(bench / "configs" / "whisper-tiny.py",
                bench / "configs" / "whisper-twin.py")
    (bench / "limits" / "whisper-twin.regtopk.1chip.json").write_text(
        (bench / "limits" / "whisper-tiny.regtopk.1chip.json").read_text())
    (bench / "metrics" / "grads_ms.py").write_text(
        "def read(ctx):\n"
        "    s = ctx.trace.scope_s.get('train.grads')\n"
        "    return 1e3 * s / ctx.steps if s else None\n")
    spec = dict(SPEC)
    spec["configs"] = [*SPEC["configs"], {
        "name": "whisper-twin", "source": "https://arxiv.org/abs/2212.04356",
        "file": "bench/configs/whisper-twin.json", "reduced": [],
        "why": "x"}]
    spec["workloads"] = [*SPEC["workloads"], {
        "name": "whisper-twin.regtopk.1chip", "config": "whisper-twin",
        "traffic": "regtopk.b40x448", "chips": 1, "why": "x"}]
    spec["per_layer"] = [*SPEC["per_layer"], {
        "name": "grads_ms", "unit": "ms", "better": "lower",
        "source": "device_trace", "layer": "model", "moves": "tokens_per_s",
        "workloads": ["whisper-twin.regtopk.1chip"]}]
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))

    cell = cell_lib.load_cell("whisper-twin.regtopk.1chip", root=tmp_path,
                              bench_dir=bench)
    assert "grads_ms" in cell.readers
    assert "grads_ms" not in cell_lib.load_cell(
        "whisper-tiny.regtopk.1chip", root=tmp_path, bench_dir=bench).readers
    check_config_is_what_runs(spec["configs"][-1], cell.config, cell.model,
                              cell.config["repo_config"])
    tiny = bench_tiny.tiny_cell("whisper-twin", "regtopk.b40x448",
                                "whisper-twin.regtopk.1chip", root=tmp_path)
    assert tiny.config["model"]["d_model"] == 48
    assert bench_tiny.reference_loss_gap(tiny) < 1e-5

    ops, spans, hlo = recorded("tiny_whisper_scoped_v5e", tmp_path)
    red = T.reduce(ops, spans, 1, T.window_of(spans), T.hlo_layers(hlo),
                   T.hlo_scopes(hlo))
    ctx = run.LayerContext(
        trace=red, steps=2, window_s=1.0, chips=1, flops_per_step=1.0,
        peak_flops=197e12, hbm_bytes_per_s=819e9, wire_bytes=0,
        model=cell.model, sizes=cell.config["model"], traffic=cell.traffic)
    assert cell.readers["grads_ms"].read(ctx) == pytest.approx(
        1e3 * red.scope_s["train.grads"] / 2)
    assert red.scope_s["train.grads"] > 0
    after = {p.relative_to(bench): p.read_bytes()
             for p in bench.rglob("*") if p.is_file()}
    assert {k: after[k] for k in before} == before  # only files added
