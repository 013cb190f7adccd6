"""Tiny cells for the benchmark's CPU tests: the real configurations'
code and traffic, at the sizes each configuration's file gives under
``"tiny"``, which a test run holds."""
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


def tiny_cell(config: str, traffic: str, limits_of: str, chips: int = 1,
              batch_per_chip: int = 4, seq: int = 8,
              root: pathlib.Path = ROOT) -> cell_lib.Cell:
    """The cell of ``config`` under ``traffic``, shrunk to its tiny sizes,
    held to the limits of the workload ``limits_of``; the files are those
    of the benchmark under ``root``."""
    spec = json.loads((root / "BENCHMARK.json").read_text())
    path = root / {c["name"]: c for c in spec["configs"]}[config]["file"]
    bench = root / "bench"
    cfg = json.loads(path.read_text())
    cfg["model"].update(cfg["tiny"])
    mix = json.loads((bench / "traffic" / f"{traffic}.json").read_text())
    mix.update(batch_per_chip=batch_per_chip, seq=seq)
    return cell_lib.Cell(
        name=f"{config}.tiny", chips=chips, config=cfg,
        model=cell_lib.load_module(path.with_suffix(".py")),
        traffic=mix,
        limits=json.loads(
            (bench / "limits" / f"{limits_of}.json").read_text()),
        end_to_end=[{"name": n, "unit": u} for n, u in (
            ("tokens_per_s", "tokens/s"), ("peak_hbm_gb", "GB"),
            ("setup_s", "s"))],
        per_layer=[], readers={})


def reference_loss_gap(cell: cell_lib.Cell, seed: int = 3) -> float:
    """The relative gap between the plain reference's loss and the
    program's on the same seeded weights and batch, at the cell's sizes."""
    from bench import feed
    from repro.models import get_family
    from repro.models.config import ModelConfig

    m = cell.config["model"]
    params = cell.model.init(feed.weights_key(seed), m)
    batch = feed.make_batch(feed.batches_key(seed), 0, m, 2, 16)
    ours = float(cell.model.loss(params, batch, m))
    cfg = ModelConfig(**m)
    theirs = float(get_family(cfg).loss_fn(params, cfg, batch)[0])
    return abs(ours - theirs) / abs(theirs)
