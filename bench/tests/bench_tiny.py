"""Tiny cells for the benchmark's CPU tests: the real configurations'
code and traffic, at sizes a test run holds."""
from __future__ import annotations

import json
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[2]
for p in (str(ROOT), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

from bench import cell as cell_lib  # noqa: E402

BENCH = ROOT / "bench"

SIZES = {
    "whisper-tiny": {
        "n_layers": 1, "n_enc_layers": 1, "enc_seq": 12, "d_model": 32,
        "n_heads": 2, "n_kv_heads": 2, "head_dim": 16, "d_ff": 64,
        "vocab": 500},
    "mamba2-780m-16L": {
        "n_layers": 2, "d_model": 32, "vocab": 500, "ssm_state": 8,
        "ssm_headdim": 8, "ssm_chunk": 4},
}


def tiny_cell(config: str, traffic: str, limits_of: str, chips: int = 1,
              batch_per_chip: int = 4, seq: int = 8) -> cell_lib.Cell:
    """The cell of ``config`` under ``traffic``, shrunk to a few thousand
    parameters, held to the limits of the workload ``limits_of``."""
    cfg = json.loads((BENCH / "configs" / f"{config}.json").read_text())
    cfg["model"].update(SIZES[config])
    mix = json.loads((BENCH / "traffic" / f"{traffic}.json").read_text())
    mix.update(batch_per_chip=batch_per_chip, seq=seq)
    return cell_lib.Cell(
        name=f"{config}.tiny", chips=chips, config=cfg,
        model=cell_lib.load_module(BENCH / "configs" / f"{config}.py"),
        traffic=mix,
        limits=json.loads(
            (BENCH / "limits" / f"{limits_of}.json").read_text()),
        end_to_end=[{"name": n, "unit": u} for n, u in (
            ("tokens_per_s", "tokens/s"), ("peak_hbm_gb", "GB"),
            ("setup_s", "s"))],
        per_layer=[], readers={})
