"""One run of a cell, driven on the CPU at a tiny size past the look for a
chip: its result line, and that ``correct`` turns false when the timed
path is broken underneath or the control takes the program's place."""
import json
import os
import subprocess
import sys
import time

import bench_tiny
import jax
import jax.numpy as jnp
import pytest

from bench import compare, faults, reference
from bench import run as run_lib

LINE_KEYS = ["correct", "attempted", "failed", "metrics", "device", "checks"]


def _run(cell, fault=None, seed=2**33 + 5):
    return run_lib.run_cell(cell, seed, 0.2, False, jax.devices()[:1],
                            time.perf_counter(), fault=fault)


def _cell(config="whisper-tiny", traffic="regtopk.b40x448", chips=1):
    limits = {"whisper-tiny": "whisper-tiny.regtopk.1chip",
              "mamba2-780m-16L": "mamba2-780m-16L.regtopk.1chip"}[config]
    seq = 16 if config.startswith("mamba") else 8
    return bench_tiny.tiny_cell(config, traffic, limits, chips=chips, seq=seq)


@pytest.mark.parametrize("config,traffic", [
    ("whisper-tiny", "regtopk.b40x448"),
    ("whisper-tiny", "dense.b40x448"),
    ("mamba2-780m-16L", "regtopk.b6x2048"),
])
def test_sound_run_is_correct_and_its_line_has_the_contract_keys(
        config, traffic):
    out = _run(_cell(config, traffic))
    assert list(out) == LINE_KEYS
    assert out["correct"] is True, out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert set(out["metrics"]) == {"tokens_per_s", "peak_hbm_gb", "setup_s"}
    assert set(out["device"]) == {"platform", "kind", "count",
                                  "memory_peak_bytes"}
    assert all(c["value"] <= c["limit"] for c in out["checks"].values())
    json.dumps(out)


@pytest.mark.parametrize("fault", sorted(faults.FAULTS))
def test_fault_in_the_timed_path_is_not_correct(fault):
    out = _run(_cell(), fault=faults.FAULTS[fault])
    assert out["correct"] is False, out["checks"]


def test_control_is_not_correct():
    """The reference in bfloat16, put in the program's place, fails the
    cell's limits (at the cell's own size this is read on the chip with
    calibrate.py)."""
    cell = _cell()
    m = cell.config["model"]
    for seed in (1, 2, 3):
        ref = reference.run(cell.model, m, cell.traffic, seed, 1)
        ctl = reference.run(cell.model, m, cell.traffic, seed, 1,
                            dtype=jnp.bfloat16)
        ok, checks = compare.verdict(ctl, ref, cell.limits)
        assert not ok, checks


def test_no_exchange_between_chips_is_not_correct():
    """Four workers on four CPU devices: sound, then with the all-gather
    left out, in a child process that sees four devices."""
    code = (
        "import sys, time, json; sys.path.insert(0, sys.argv[1]);"
        "import bench_tiny, jax;"
        "from bench import run, faults;"
        "cell = bench_tiny.tiny_cell('whisper-tiny', 'regtopk.b40x448',"
        " 'whisper-tiny.regtopk.1chip', chips=4);"
        "ok = run.run_cell(cell, 9, 0.2, False, jax.devices()[:4])['correct'];"
        "ctx = faults.no_exchange(); ctx.__enter__();"
        "bad = run.run_cell(cell, 9, 0.2, False, jax.devices()[:4])['correct'];"
        "print(json.dumps([ok, bad]))")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    res = subprocess.run(
        [sys.executable, "-c", code, str(bench_tiny.BENCH / "tests")],
        capture_output=True, text=True, env=env, timeout=600,
        cwd=bench_tiny.ROOT)
    assert res.returncode == 0, res.stderr[-3000:]
    assert json.loads(res.stdout.strip().splitlines()[-1]) == [True, False]


def test_without_a_tpu_exits_nonzero_and_prints_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    res = subprocess.run(
        [sys.executable, "bench/run.py", "--workload",
         "whisper-tiny.regtopk.1chip", "--seed", "1", "--seconds", "1",
         "--trace", "0"],
        capture_output=True, text=True, env=env, timeout=300,
        cwd=bench_tiny.ROOT)
    assert res.returncode != 0
    assert res.stdout.strip() == ""
    assert "no TPU" in res.stderr
