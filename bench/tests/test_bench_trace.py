"""The reduction from a device trace to device times by layer and by the
train step's named scopes."""
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


SCOPED_HLO = """HloModule jit_train_step

ENTRY %main {
  %fusion.1 = f32[8]{0} fusion(%p), kind=kLoop, calls=%f1, metadata={op_name="jit(train_step)/train.grads/vmap(jvp())/dot_general" stack_frame_id=1}
  %custom-call.2 = (f32[8]{0}, s32[8]{0}) custom-call(%a), custom_call_target="TopK", metadata={op_name="jit(train_step)/train.round/shard_map/spa_bucket001/spa.select/top_k" stack_frame_id=2}
  %all-gather.3 = f32[32]{0} all-gather(%x), dimensions={0}, metadata={op_name="jit(train_step)/train.round/shard_map/spa_bucket000/spa.exchange/all_gather" stack_frame_id=2}
  %fusion.4 = f32[8]{0} fusion(%p), kind=kLoop, calls=%f4, metadata={op_name="jit(train_step)/train.round/shard_map/spa_bucket000/broadcast_in_dim;spa_bucket000/spa.feedback/broadcast_in_dim"}
  %fusion.5 = f32[8]{0} fusion(%p), kind=kLoop, calls=%f5, metadata={op_name="jit(train_step)/train.optimizer/sqrt" stack_frame_id=3}
  %fusion.6 = f32[8]{0} fusion(%p), kind=kLoop, calls=%f6, metadata={op_name="jit(train_step)/train.round/shard_map/spa.score/gather" stack_frame_id=2}
  %fusion.7 = f32[] fusion(%p), kind=kLoop, calls=%f7, metadata={op_name="jit(train_step)/train.round/shard_map/reduce_sum" stack_frame_id=2}
  ROOT %tuple.8 = (f32[8]{0}) tuple(%fusion.5), metadata={op_name="jit(train_step)/reduce_sum" stack_frame_id=1}
}
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


def test_hlo_scopes_take_the_innermost_stage():
    assert T.hlo_scopes(SCOPED_HLO) == {
        "fusion.1": "train.grads",
        "custom-call.2": "spa.select",  # inside spa_bucket001
        "all-gather.3": "spa.exchange",
        "fusion.4": "spa.feedback",  # merged op_names: the last stage named
        "fusion.5": "train.optimizer",
        "fusion.6": "spa.score",
        "fusion.7": "train.round",  # the round's own bookkeeping
        "tuple.8": "none",
    }
    assert T.stage_of("spa_bucket002/spa.encode/reduce_max") == "spa.encode"
    # a scope under a transform, as a model's own scopes sit
    assert T.stage_of("jit(s)/train.grads/vmap(jvp(mla.attn))/dot_general"
                      ) == "mla.attn"


def test_stage_s_over_innermost_events_averaged_over_chips():
    scopes = {"while.1": "train.grads", "fusion.1": "train.grads",
              "custom-call.2": "spa.select"}
    ops = [
        _op(0, "while.1", 0, 6),  # holds the next two
        _op(0, "fusion.1", 0, 3),
        _op(0, "fusion.1", 3, 3),
        _op(0, "custom-call.2", 8, 1),
        _op(1, "fusion.1", 0, 9),
        _op(1, "broadcast.9", 9.5, 0.5),  # another program: "none"
        _op(0, "custom-call.2", 50, 1),  # after the window: left out
    ]
    got = T.reduce(ops, [], 2, (0, 10 * MS), {}, scopes).scope_s
    assert got == pytest.approx(
        {"train.grads": 7.5e-3, "spa.select": 0.5e-3, "none": 0.25e-3})


def test_window_needs_one_span():
    with pytest.raises(ValueError):
        T.window_of([T.Span("bench.feed", 0, 1)])


def recorded(name, tmp_path):
    """Ops, host spans and compiled HLO text of a recorded chip trace."""
    pb = tmp_path / f"{name}.xplane.pb"
    with gzip.open(DATA / f"{name}.xplane.pb.gz", "rb") as f:
        pb.write_bytes(f.read())
    ops, spans = T.read(str(pb))
    with gzip.open(DATA / f"{name}.hlo.txt.gz", "rt") as f:
        return ops, spans, f.read()


def test_recorded_chip_trace(tmp_path):
    """Two steps of the tiny whisper cell, traced on a TPU v5e, with the
    compiled step's HLO text: every layer of a RegTop-k step shows, and
    the layers' device time stays within the busy time."""
    ops, spans, hlo = recorded("tiny_whisper_v5e", tmp_path)
    red = T.reduce(ops, spans, 1, T.window_of(spans), T.hlo_layers(hlo))
    lo, hi = T.window_of(spans)
    assert 0 < red.busy_s <= (hi - lo) / 1e9
    assert {"fwd_bwd", "round", "optimizer"} <= set(red.layer_s)
    assert sum(red.layer_s.values()) <= red.busy_s * (1 + 1e-9)
    assert red.layer_s["round"] > red.layer_s["fwd_bwd"]  # tiny model
    assert dict(red.top_ops)["round:top_k"] > 0


def test_unscoped_chip_trace_reads_no_stage(tmp_path):
    """The trace of a program without stage scopes reduces by layer as it
    always did, and puts all of its time in no scope."""
    ops, spans, hlo = recorded("tiny_whisper_v5e", tmp_path)
    window = T.window_of(spans)
    red = T.reduce(ops, spans, 1, window, T.hlo_layers(hlo),
                   T.hlo_scopes(hlo))
    assert red.busy_s == pytest.approx(6.05079e-4)
    assert red.layer_s == pytest.approx({
        "other": 7.4186e-05, "fwd_bwd": 6.4271e-05, "round": 4.32815e-04,
        "optimizer": 3.3807e-05})
    assert [k for k, _ in red.top_ops[:3]] == [
        "round:top_k", "round:gather", "round:scatter-add"]
    assert dict(red.top_ops)["round:top_k"] == pytest.approx(2.19666e-4)
    assert red.scope_s == pytest.approx({"none": sum(red.layer_s.values())})


def test_scoped_chip_trace_splits_by_stage(tmp_path):
    """Two steps of the tiny whisper RegTop-k cell from the program with
    stage scopes, traced on a TPU v5e: the scopes cover the same events as
    the layers, each stage of a one-worker sparse round shows, top-k sits
    in ``spa.select``, and the optimizer's scope holds exactly what the
    layers find raised from ``repro/optim``."""
    ops, spans, hlo = recorded("tiny_whisper_scoped_v5e", tmp_path)
    window = T.window_of(spans)
    red = T.reduce(ops, spans, 1, window, T.hlo_layers(hlo),
                   T.hlo_scopes(hlo))
    got = red.scope_s
    assert sum(got.values()) == pytest.approx(sum(red.layer_s.values()))
    assert set(T.STAGES) - set(got) == {"spa.encode"}  # coo_fp32: no op
    assert got["spa.select"] >= dict(red.top_ops)["round:top_k"] > 0
    assert got["train.optimizer"] == pytest.approx(red.layer_s["optimizer"])
    round_s = sum(v for k, v in got.items()
                  if k == "train.round" or k.startswith("spa."))
    assert round_s == pytest.approx(red.layer_s["round"], rel=0.02)


def test_readers_over_a_reduction():
    """Each per-layer reader of ``BENCHMARK.json`` reads its number from a
    reduction, and reads nothing where there is nothing to read."""
    from bench import cell as cell_lib
    from bench import run

    red = T.Reduction(chips=4, busy_s=1.9, layer_s={
        "fwd_bwd": 1.2, "round": 0.4, "collective": 0.04}, scope_s={
        "spa.select": 0.2, "spa.score": 0.08, "spa.feedback": 0.06,
        "train.optimizer": 0.02}, top_ops=[], idle_by_host=[])
    cell = cell_lib.load_cell("whisper-tiny.regtopk.1chip")
    ctx = run.LayerContext(trace=red, steps=4, window_s=2.0, chips=4,
                           flops_per_step=8e12, peak_flops=197e12,
                           hbm_bytes_per_s=819e9, wire_bytes=8_757_000,
                           model=cell.model, sizes=cell.config["model"],
                           traffic=cell.traffic)
    got = {n: r.read(ctx) for n, r in cell.readers.items()}
    assert got == pytest.approx({
        "device_idle_share": 5.0, "step_mfu": 100 * 8e12 * 4 / (2 * 4 * 197e12),
        "fwd_bwd_ms": 300.0, "round_ms": 100.0, "select_ms": 50.0,
        "score_ms": 20.0, "feedback_ms": 15.0, "optimizer_ms": 5.0})
    alone = ctx._replace(chips=1, trace=red._replace(layer_s={}, scope_s={}))
    assert all(r.read(alone) is None for n, r in cell.readers.items()
               if n not in ("device_idle_share", "step_mfu"))
    assert set(cell_lib.load_cell(
        "mamba2-780m-16L.regtopk.1chip").readers) == set(got)
    assert set(cell_lib.load_cell("whisper-tiny.dense.1chip").readers) == (
        set(got) - {"round_ms", "select_ms", "score_ms", "feedback_ms"})
