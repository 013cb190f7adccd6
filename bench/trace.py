"""Reduce a profiler trace to device times by layer and by named scope.

Reads the ``.xplane.pb`` that ``jax.profiler.trace`` writes, with nothing
but JAX. Device operations come from each TPU plane's ``XLA Ops`` line,
named by their HLO instruction. The trace carries no name stack, so each
instruction is put in a layer, and in a scope, from the compiled step's
HLO text: its ``op_name`` (the name stack JAX recorded) and the Python
frames behind its ``stack_frame_id``.

Layers:

- ``collective``: all-gather, all-reduce, reduce-scatter, all-to-all,
  collective-permute and their async halves, by opcode;
- ``fwd_bwd``: under ``jvp`` or ``transpose`` (the model's forward and
  backward passes);
- ``round``: raised from the sparsify-and-aggregate round (``_spa_leaf``
  and ``rounds`` in ``core/distributed.py``, ``core/compact.py``,
  ``repro/comm``). Inside ``shard_map`` JAX records only the frame that
  called it, in ``train_step``, so what ``train_step`` raises itself
  outside the gradient and the optimizer counts here too: besides the
  round, only the batch's reshape, dtype casts and the loss's mean;
- ``optimizer``: raised from ``repro/optim``;
- ``other``: the rest, and every operation of another program (the feed).

Scopes: the innermost ``jax.named_scope`` an instruction's ``op_name``
names, a scope being a name with a dot in it (the program's train step
names ``train.grads``, ``train.round``, ``train.optimizer`` and, inside the
round, ``spa.score``, ``spa.select``, ``spa.encode``, ``spa.exchange``,
``spa.feedback``; a scope under a transform reads as ``jvp(mla.attn)``).
A fusion carries its root's ``op_name``; where XLA merged instructions it
joins their ``op_name``s with ``;`` and the last scope named counts. An
instruction in no scope, or of another program, is in ``"none"``. Scope
names are read from the text, not imported from the program, so a
benchmark laid over a program without them reads no scope and does not
fail.

Only innermost events count toward a layer or a scope (a loop's event
holds its body's), and busy time is the union of all events. Host spans
that the benchmark opens around its own calls (names starting ``bench.``)
say what the host was doing in each idle gap of the device.
"""
from __future__ import annotations

import collections
import re
from typing import Dict, List, NamedTuple, Optional, Tuple

COLLECTIVE = re.compile(
    r"(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute"
    r"|collective-broadcast)(-start|-done)?$")
_INSTR = re.compile(r"^\s*(?:ROOT )?%([\w.\-]+) = .*? ([\w\-]+)\(.*?"
                    r"metadata=\{op_name=\"([^\"]*)\"(?: stack_frame_id=(\d+))?")
_OP_NAME = re.compile(r"%([\w.\-]+) = .*?op_name=\"([^\"]*)\"")
_SCOPE = re.compile(r"[A-Za-z_][\w\-]*(?:\.[\w\-]+)+")
# the train step's stage scopes (``repro.core.stages``), as the readers
# of ``bench/metrics`` name them
STAGES = ("train.grads", "train.round", "train.optimizer", "spa.score",
          "spa.select", "spa.encode", "spa.exchange", "spa.feedback")
_ROUND_FUNCS = ("_spa_leaf", "make_sparsify_aggregate.<locals>.rounds",
                "make_train_step.<locals>.train_step")
_ROUND_FILES = ("/repro/core/compact.py", "/repro/comm/")


class Op(NamedTuple):
    device: int
    name: str  # HLO instruction name, e.g. "fusion.12" or "all-gather.3"
    start_ns: float
    dur_ns: float


class Span(NamedTuple):
    name: str
    start_ns: float
    end_ns: float


def _table(text: str, section: str) -> Dict[int, str]:
    m = re.search(rf"\n{section}\n(.*?)(?:\n\n|\Z)", text, re.S)
    rows = {}
    for line in (m.group(1).splitlines() if m else []):
        k, _, v = line.partition(" ")
        rows[int(k)] = v
    return rows


def _frames(text: str):
    """stack_frame_id -> [(file, function), ...] from the innermost out."""
    files = {k: v.strip('"') for k, v in _table(text, "FileNames").items()}
    funcs = {k: v.strip('"') for k, v in
             _table(text, "FunctionNames").items()}
    locs = {}
    for k, v in _table(text, "FileLocations").items():
        f = re.search(r"file_name_id=(\d+) function_name_id=(\d+)", v)
        locs[k] = (files.get(int(f.group(1)), ""),
                   funcs.get(int(f.group(2)), ""))
    parent = {}
    for k, v in _table(text, "StackFrames").items():
        f = re.search(r"file_location_id=(\d+) parent_frame_id=(\d+)", v)
        parent[k] = (int(f.group(1)), int(f.group(2)))

    def chain(fid: int):
        out, seen = [], set()
        while fid in parent and fid not in seen:
            seen.add(fid)
            loc, up = parent[fid]
            out.append(locs.get(loc, ("", "")))
            fid = up
        return out

    return chain


def layer_of(opcode: str, op_name: str, frames) -> str:
    if COLLECTIVE.match(opcode):
        return "collective"
    if "jvp(" in op_name or "transpose(" in op_name:
        return "fwd_bwd"
    if any("/repro/optim/" in f for f, _ in frames):
        return "optimizer"
    if frames and (frames[0][1] in _ROUND_FUNCS
                   or any(s in frames[0][0] for s in _ROUND_FILES)):
        return "round"
    return "other"


def hlo_layers(hlo_text: str) -> Dict[str, Tuple[str, str]]:
    """Instruction name -> (layer, label) for every instruction of a
    compiled module's text; the label is the last part of its op_name."""
    chain = _frames(hlo_text)
    out = {}
    for line in hlo_text.splitlines():
        m = _INSTR.match(line)
        if not m:
            continue
        name, opcode, op_name, frame = m.groups()
        frames = chain(int(frame)) if frame else []
        label = op_name.rsplit("/", 1)[-1] or opcode
        out[name] = (layer_of(opcode, op_name, frames), label)
    return out


def stage_of(op_name: str) -> str:
    """The innermost named scope an ``op_name`` names, else ``"none"``."""
    named = _SCOPE.findall(op_name)
    return named[-1] if named else "none"


def hlo_scopes(hlo_text: str) -> Dict[str, str]:
    """Instruction name -> innermost named scope, for every instruction of
    a compiled module's text that carries an ``op_name``."""
    return {name: stage_of(op_name)
            for name, op_name in _OP_NAME.findall(hlo_text)}


def _device_index(plane_name: str) -> int:
    return int(re.match(r"/device:TPU:(\d+)", plane_name).group(1))


def read(path: str) -> Tuple[List[Op], List[Span]]:
    """Device operations of every TPU plane, and the benchmark's host
    spans, from one ``.xplane.pb``."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    ops: List[Op] = []
    spans: List[Span] = []
    for plane in data.planes:
        if re.match(r"/device:TPU:\d+$", plane.name):
            dev = _device_index(plane.name)
            for line in plane.lines:
                if line.name != "XLA Ops":
                    continue
                for e in line.events:
                    m = re.match(r"%?([\w.\-]+)", e.name)
                    ops.append(Op(dev, m.group(1) if m else e.name,
                                  e.start_ns, e.duration_ns))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith("bench."):
                        spans.append(Span(e.name, e.start_ns,
                                          e.start_ns + e.duration_ns))
    return ops, spans


def _union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[Tuple[float, float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], e))
        else:
            out.append((s, e))
    return out


def _leaves(ops: List[Op]) -> List[Op]:
    """The events of one device that hold no other event."""
    ops = sorted(ops, key=lambda o: (o.start_ns, -o.dur_ns))
    out = []
    for i, o in enumerate(ops):
        nxt = ops[i + 1] if i + 1 < len(ops) else None
        if nxt is None or nxt.start_ns >= o.start_ns + o.dur_ns:
            out.append(o)
    return out


class Reduction(NamedTuple):
    chips: int
    busy_s: float  # union of operation intervals, averaged over chips
    layer_s: Dict[str, float]  # device seconds by layer, averaged over chips
    scope_s: Dict[str, float]  # the same by innermost named scope
    top_ops: List[Tuple[str, float]]  # "layer:label" by seconds, over chips
    idle_by_host: List[Tuple[str, float]]  # chip 0's idle s by host span


def reduce(ops: List[Op], spans: List[Span], chips: int,
           window_ns: Tuple[float, float],
           layers: Dict[str, Tuple[str, str]],
           scopes: Optional[Dict[str, str]] = None) -> Reduction:
    """Sum what started inside ``window_ns`` (the traced window on the
    trace's clock) by layer, by scope and by operation; attribute chip 0's
    idle gaps to the innermost host span that covers each gap's middle
    ("none" where no span does)."""
    lo, hi = window_ns
    inside = [o for o in ops if lo <= o.start_ns < hi]
    busy = 0.0
    layer: Dict[str, float] = collections.defaultdict(float)
    scope: Dict[str, float] = collections.defaultdict(float)
    by_op: Dict[str, float] = collections.defaultdict(float)
    for d in range(chips):
        mine = [o for o in inside if o.device == d]
        busy += sum(e - s for s, e in _union(
            [(o.start_ns, min(o.start_ns + o.dur_ns, hi)) for o in mine]))
        for o in _leaves(mine):
            lay, label = layers.get(o.name, ("other", re.sub(
                r"\.\d+$", "", o.name)))
            layer[lay] += o.dur_ns
            scope[(scopes or {}).get(o.name, "none")] += o.dur_ns
            by_op[f"{lay}:{label}"] += o.dur_ns
    idle: Dict[str, float] = collections.defaultdict(float)
    cursor = lo
    spans = sorted(spans, key=lambda s: s.end_ns - s.start_ns)
    for s, e in _union([(o.start_ns, o.start_ns + o.dur_ns)
                        for o in inside if o.device == 0]) + [(hi, hi)]:
        if s > cursor:
            mid = (s + cursor) / 2
            host = next((sp.name for sp in spans
                         if sp.start_ns <= mid <= sp.end_ns), "none")
            idle[host] += s - cursor
        cursor = max(cursor, e)
    top = sorted(by_op.items(), key=lambda kv: -kv[1])[:10]
    gaps = sorted(idle.items(), key=lambda kv: -kv[1])[:10]
    return Reduction(
        chips=chips,
        busy_s=busy / chips / 1e9,
        layer_s={k: v / chips / 1e9 for k, v in layer.items()},
        scope_s={k: v / chips / 1e9 for k, v in scope.items()},
        top_ops=[(k, v / chips / 1e9) for k, v in top],
        idle_by_host=[(k, v / 1e9) for k, v in gaps],
    )


def window_of(spans: List[Span]) -> Tuple[float, float]:
    """The traced window on the trace's clock: the benchmark's
    ``bench.window`` span."""
    w = [s for s in spans if s.name == "bench.window"]
    if len(w) != 1:
        raise ValueError(f"expected one bench.window span, found {len(w)}")
    return w[0].start_ns, w[0].end_ns
