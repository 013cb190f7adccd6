"""The reduction from a device trace to per-layer times."""
import gzip
import pathlib

import pytest

from bench import trace as T

MS = 1e6  # ns
DATA = pathlib.Path(__file__).parent / "data"

HLO = """HloModule jit_train_step

ENTRY %main {
  %fusion.1 = f32[8]{0} fusion(%p), kind=kLoop, calls=%f1, metadata={op_name="jit(train_step)/vmap(jvp())/while/body/dot_general" stack_frame_id=3}
  %fusion.2 = f32[8]{0} fusion(%p), kind=kLoop, calls=%f2, metadata={op_name="jit(train_step)/vmap(transpose(jvp()))/dot_general" stack_frame_id=3}
  %sort.3 = (f32[8]{0}, s32[8]{0}) sort(%a, %b), dimensions={0}, metadata={op_name="jit(train_step)/top_k" stack_frame_id=2}
  %all-reduce.4 = f32[8]{0} all-reduce(%x), replica_groups={}, to_apply=%add, metadata={op_name="jit(train_step)/all_gather" stack_frame_id=2}
  %fusion.5 = f32[8]{0} fusion(%p), kind=kLoop, calls=%f5, metadata={op_name="jit(train_step)/sqrt" stack_frame_id=4}
  %while.6 = (s32[]) while(%t), condition=%c, body=%b, metadata={op_name="jit(train_step)/vmap(jvp())/while" stack_frame_id=3}
  ROOT %tuple.7 = (f32[8]{0}) tuple(%fusion.5), metadata={op_name="jit(train_step)/pjit" stack_frame_id=1}
}

FileNames
1 "/x/bench/run.py"
2 "/x/src/repro/core/distributed.py"
3 "/x/src/repro/optim/optimizers.py"

FunctionNames
1 "<module>"
2 "make_train_step.<locals>.train_step"
3 "adam.<locals>.update"

FileLocations
1 {file_name_id=1 function_name_id=1 line=8 end_line=8 column=4 end_column=73}
2 {file_name_id=2 function_name_id=2 line=1018 end_line=1018 column=1 end_column=9}
3 {file_name_id=3 function_name_id=3 line=120 end_line=120 column=1 end_column=9}

StackFrames
1 {file_location_id=1 parent_frame_id=1}
2 {file_location_id=2 parent_frame_id=1}
3 {file_location_id=2 parent_frame_id=1}
4 {file_location_id=3 parent_frame_id=2}
"""


def test_hlo_layers_by_opcode_name_stack_and_frames():
    lay = T.hlo_layers(HLO)
    assert lay["fusion.1"] == ("fwd_bwd", "dot_general")
    assert lay["fusion.2"] == ("fwd_bwd", "dot_general")
    assert lay["sort.3"] == ("round", "top_k")
    assert lay["all-reduce.4"] == ("collective", "all_gather")
    assert lay["fusion.5"] == ("optimizer", "sqrt")
    assert lay["while.6"][0] == "fwd_bwd"
    assert lay["tuple.7"][0] == "other"


def _op(dev, name, start_ms, dur_ms):
    return T.Op(dev, name, start_ms * MS, dur_ms * MS)


def test_reduce_counts_innermost_events_and_attributes_idle_gaps():
    layers = {"while.6": ("fwd_bwd", "while"),
              "fusion.1": ("fwd_bwd", "dot_general"),
              "sort.3": ("round", "top_k")}
    ops = [
        _op(0, "while.6", 0, 6),  # holds the next two
        _op(0, "fusion.1", 0, 3),
        _op(0, "fusion.1", 3, 3),
        _op(0, "sort.3", 8, 1),
        _op(1, "fusion.1", 0, 9),
        _op(1, "broadcast.9", 9.5, 0.5),  # another program: "other"
        _op(0, "fusion.1", 50, 1),  # after the window: left out
    ]
    spans = [T.Span("bench.window", 0, 10 * MS),
             T.Span("bench.wait", 5 * MS, 10 * MS),
             T.Span("bench.feed", 6 * MS, 7 * MS)]
    red = T.reduce(ops, spans, 2, T.window_of(spans), layers)
    # chip 0 busy [0, 6) and [8, 9) = 7 ms; chip 1 busy 9.5 ms
    assert red.busy_s == pytest.approx(8.25e-3)
    assert red.layer_s == pytest.approx(
        {"fwd_bwd": 7.5e-3, "round": 0.5e-3, "other": 0.25e-3})
    assert dict(red.top_ops)["fwd_bwd:dot_general"] == pytest.approx(7.5e-3)
    # chip 0's gaps: [6, 8) in bench.feed (innermost), [9, 10) in bench.wait
    assert dict(red.idle_by_host) == pytest.approx(
        {"bench.feed": 2e-3, "bench.wait": 1e-3})


def test_window_needs_one_span():
    with pytest.raises(ValueError):
        T.window_of([T.Span("bench.feed", 0, 1)])


def test_recorded_chip_trace(tmp_path):
    """Two steps of the tiny whisper cell, traced on a TPU v5e, with the
    compiled step's HLO text: every layer of a RegTop-k step shows, and
    the layers' device time stays within the busy time."""
    pb = tmp_path / "trace.xplane.pb"
    with gzip.open(DATA / "tiny_whisper_v5e.xplane.pb.gz", "rb") as f:
        pb.write_bytes(f.read())
    ops, spans = T.read(str(pb))
    with gzip.open(DATA / "tiny_whisper_v5e.hlo.txt.gz", "rt") as f:
        layers = T.hlo_layers(f.read())
    red = T.reduce(ops, spans, 1, T.window_of(spans), layers)
    lo, hi = T.window_of(spans)
    assert 0 < red.busy_s <= (hi - lo) / 1e9
    assert {"fwd_bwd", "round", "optimizer"} <= set(red.layer_s)
    assert sum(red.layer_s.values()) <= red.busy_s * (1 + 1e-9)
    assert red.layer_s["round"] > red.layer_s["fwd_bwd"]  # tiny model
    assert dict(red.top_ops)["round:top_k"] > 0


def test_readers_over_a_reduction():
    """Each per-layer reader of ``BENCHMARK.json`` reads its number from a
    reduction, and reads nothing where there is nothing to read."""
    from bench import cell as cell_lib
    from bench import run

    red = T.Reduction(chips=4, busy_s=1.9, layer_s={
        "fwd_bwd": 1.2, "round": 0.4, "collective": 0.04}, top_ops=[],
        idle_by_host=[])
    ctx = run.LayerContext(trace=red, steps=4, window_s=2.0, chips=4,
                           flops_per_step=8e12, peak_flops=197e12,
                           wire_bytes=8_757_000)
    cell = cell_lib.load_cell("whisper-tiny.regtopk.1chip")
    got = {n: r.read(ctx) for n, r in cell.readers.items()}
    assert got == pytest.approx({
        "device_idle_share": 5.0, "step_mfu": 100 * 8e12 * 4 / (2 * 4 * 197e12),
        "fwd_bwd_ms": 300.0, "round_ms": 100.0})
    alone = ctx._replace(chips=1, trace=red._replace(layer_s={}))
    assert cell.readers["round_ms"].read(alone) is None
    assert cell.readers["fwd_bwd_ms"].read(alone) is None
