"""Each configuration's file is the configuration the program runs, and
its FLOP count is built on the parameter tree the program's init has."""
import json

import bench_tiny
import jax
import pytest

from bench import cell as cell_lib
from repro import configs as repo_configs
from repro.models import get_family
from repro.models.config import ModelConfig

SPEC = json.loads((bench_tiny.ROOT / "BENCHMARK.json").read_text())
CONFIGS = {c["name"]: c for c in SPEC["configs"]}


def _file(name):
    return json.loads((bench_tiny.ROOT / CONFIGS[name]["file"]).read_text())


def _load(name):
    entry = CONFIGS[name]
    return entry, _file(name), cell_lib.load_module(
        (bench_tiny.ROOT / entry["file"]).with_suffix(".py"))


def _program_shapes(model):
    cfg = ModelConfig(**model)
    return jax.eval_shape(
        lambda: get_family(cfg).init(jax.random.PRNGKey(0), cfg)[0])


# counts pinned here, apart from the files, for the configurations that
# had them before the files carried their own
PINNED = {"whisper-tiny": 36_487_680, "mamba2-780m-16L": 311_770_880}


def check_config_is_what_runs(entry, cfg, mod, repo_name):
    """The file's parameter tree and count are the program's, and it
    differs from the repository's configuration ``repo_name`` only in the
    keys it lists as reduced."""
    shapes = _program_shapes(cfg["model"])
    count = sum(x.size for x in jax.tree.leaves(shapes))
    assert count == cfg["params"] == mod.param_count(cfg["model"])
    assert jax.tree.map(lambda x: tuple(x.shape), shapes) == jax.tree.map(
        tuple, mod.param_shapes(cfg["model"]),
        is_leaf=lambda x: isinstance(x, tuple))
    repo = repo_configs.get_config(repo_name)
    ours = ModelConfig(**cfg["model"])
    changed = sorted(k for k in cfg["model"]
                     if getattr(repo, k) != getattr(ours, k))
    assert changed == sorted(entry["reduced"]) == sorted(cfg["reduced"])


@pytest.mark.parametrize("name,repo_name,expected", [
    (name, _file(name)["repo_config"], _file(name)["params"])
    for name in CONFIGS])
def test_config_is_what_runs(name, repo_name, expected):
    assert expected == PINNED.get(name, expected)
    check_config_is_what_runs(*_load(name), repo_name)


@pytest.mark.parametrize("name", sorted(CONFIGS))
def test_flops_grow_with_the_rows(name):
    _, cfg, mod = _load(name)
    assert mod.flops_per_step(cfg["model"], 5, 64) == 5 * mod.flops_per_step(
        cfg["model"], 1, 64)


def test_mamba_flops_are_six_per_weight_and_token():
    """Every weight of mamba2 is used once per token (the embedding as the
    tied head), forward and backward 6 FLOPs each; SSD's own products add
    under a tenth."""
    _, cfg, mod = _load("mamba2-780m-16L")
    n = _program_shapes(cfg["model"])
    count = sum(x.size for x in jax.tree.leaves(n))
    ratio = mod.flops_per_step(cfg["model"], 4, 2048) / (6 * 4 * 2048 * count)
    assert 1.0 < ratio < 1.1


def test_whisper_flops_as_the_issue_counts():
    """8.2e12 FLOPs a step at 40 x 448 (encoder over 1500 frames)."""
    _, cfg, mod = _load("whisper-tiny")
    assert 8.0e12 < mod.flops_per_step(cfg["model"], 40, 448) < 8.5e12


@pytest.mark.parametrize("name", sorted(CONFIGS))
def test_tiny_reference_matches_program_loss(name):
    """At its tiny size on the CPU, the plain reference's loss equals the
    program's on the same seeded weights and batch."""
    cell = bench_tiny.tiny_cell(
        name, "regtopk.b40x448", "whisper-tiny.regtopk.1chip")
    assert bench_tiny.reference_loss_gap(cell) < 1e-5
