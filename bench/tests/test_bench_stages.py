"""Device time by stage of the train step (``bench/stages.py``)."""
import gzip
import pathlib

import pytest

from bench import stages as S
from bench import trace as T

MS = 1e6  # ns
DATA = pathlib.Path(__file__).parent / "data"

HLO = """HloModule jit_train_step

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


def test_hlo_scopes_take_the_innermost_stage():
    assert S.hlo_scopes(HLO) == {
        "fusion.1": "train.grads",
        "custom-call.2": "spa.select",  # inside spa_bucket001
        "all-gather.3": "spa.exchange",
        "fusion.4": "spa.feedback",  # merged op_names: the last stage named
        "fusion.5": "train.optimizer",
        "fusion.6": "spa.score",
        "fusion.7": "train.round",  # the round's own bookkeeping
        "tuple.8": "none",
    }
    assert S.stage_of("spa_bucket002/spa.encode/reduce_max") == "spa.encode"


def _op(dev, name, start_ms, dur_ms):
    return T.Op(dev, name, start_ms * MS, dur_ms * MS)


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
    got = S.stage_s(ops, [], 2, (0, 10 * MS), scopes)
    assert got == pytest.approx(
        {"train.grads": 7.5e-3, "spa.select": 0.5e-3, "none": 0.25e-3})


def _recorded(name, tmp_path):
    """Ops, host spans and compiled HLO text of a recorded chip trace."""
    pb = tmp_path / f"{name}.xplane.pb"
    with gzip.open(DATA / f"{name}.xplane.pb.gz", "rb") as f:
        pb.write_bytes(f.read())
    ops, spans = T.read(str(pb))
    with gzip.open(DATA / f"{name}.hlo.txt.gz", "rt") as f:
        return ops, spans, f.read()


def test_unscoped_chip_trace_reads_no_stage(tmp_path):
    """The trace of a program without stage scopes reduces as it always
    did, and puts all of its time in no stage."""
    ops, spans, hlo = _recorded("tiny_whisper_v5e", tmp_path)
    window = T.window_of(spans)
    red = T.reduce(ops, spans, 1, window, T.hlo_layers(hlo))
    assert red.busy_s == pytest.approx(6.05079e-4)
    assert red.layer_s == pytest.approx({
        "other": 7.4186e-05, "fwd_bwd": 6.4271e-05, "round": 4.32815e-04,
        "optimizer": 3.3807e-05})
    assert [k for k, _ in red.top_ops[:3]] == [
        "round:top_k", "round:gather", "round:scatter-add"]
    assert dict(red.top_ops)["round:top_k"] == pytest.approx(2.19666e-4)
    got = S.stage_s(ops, spans, 1, window, S.hlo_scopes(hlo))
    assert got == pytest.approx({"none": sum(red.layer_s.values())})


def test_scoped_chip_trace_splits_by_stage(tmp_path):
    """Two steps of the tiny whisper RegTop-k cell from the program with
    stage scopes, traced on a TPU v5e: the stages cover the same events as
    the layers, each stage of a one-worker sparse round shows, top-k sits
    in ``spa.select``, and the optimizer's scope holds exactly what
    ``bench/trace.py`` finds raised from ``repro/optim``."""
    ops, spans, hlo = _recorded("tiny_whisper_scoped_v5e", tmp_path)
    window = T.window_of(spans)
    red = T.reduce(ops, spans, 1, window, T.hlo_layers(hlo))
    got = S.stage_s(ops, spans, 1, window, S.hlo_scopes(hlo))
    assert sum(got.values()) == pytest.approx(sum(red.layer_s.values()))
    assert set(S.STAGES) - set(got) == {"spa.encode"}  # coo_fp32: no op
    assert got["spa.select"] >= dict(red.top_ops)["round:top_k"] > 0
    assert got["train.optimizer"] == pytest.approx(red.layer_s["optimizer"])
    round_s = sum(v for k, v in got.items()
                  if k == "train.round" or k.startswith("spa."))
    assert round_s == pytest.approx(red.layer_s["round"], rel=0.02)
