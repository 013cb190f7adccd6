"""Device time by stage of the train step, from a device trace.

The program names the stages of its train step with ``jax.named_scope``:
``train.grads``, ``train.round`` and ``train.optimizer``, and inside the
round ``spa.score``, ``spa.select``, ``spa.encode``, ``spa.exchange`` and
``spa.feedback`` (``repro.core.stages``). The names reach the compiled
HLO's ``op_name``, and a fusion carries its root's, so each instruction
of the compiled step's text is put in the innermost stage its ``op_name``
names. Under the overlap schedule the stages sit inside ``spa_bucketNNN``;
the stage is still the innermost name. Where XLA merged instructions it
joins their ``op_name``s with ``;``, and the last stage named counts.

Device seconds by stage are ``trace.reduce``'s, with stages in place of
layers: the same innermost events, averaged over the chips. A program
without the scopes gives every instruction the stage ``"none"``.

The names are written out here, not imported from the program: a
benchmark laid over a program without them reads no stage, and does not
fail.
"""
from __future__ import annotations

import re
from typing import Dict, List, Tuple

from bench import trace

STAGES = ("train.grads", "train.round", "train.optimizer", "spa.score",
          "spa.select", "spa.encode", "spa.exchange", "spa.feedback")
_OP_NAME = re.compile(r"%([\w.\-]+) = .*?op_name=\"([^\"]*)\"")


def stage_of(op_name: str) -> str:
    """The innermost stage an ``op_name`` names, else ``"none"``."""
    named = [s for s in re.split(r"[/;]", op_name) if s in STAGES]
    return named[-1] if named else "none"


def hlo_scopes(hlo_text: str) -> Dict[str, str]:
    """Instruction name -> stage, for every instruction of a compiled
    module's text that carries an ``op_name``."""
    return {name: stage_of(op_name)
            for name, op_name in _OP_NAME.findall(hlo_text)}


def stage_s(ops: List[trace.Op], spans: List[trace.Span], chips: int,
            window_ns: Tuple[float, float],
            scopes: Dict[str, str]) -> Dict[str, float]:
    """Device seconds by stage over ``window_ns``, averaged over ``chips``;
    an instruction ``scopes`` does not know (another program's) counts as
    ``"none"``."""
    by_stage = dict(trace.reduce(
        ops, spans, chips, window_ns,
        {name: (stage, stage) for name, stage in scopes.items()}).layer_s)
    if "other" in by_stage:  # reduce's name for an unknown instruction
        by_stage["none"] = by_stage.get("none", 0.0) + by_stage.pop("other")
    return by_stage
