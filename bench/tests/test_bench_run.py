"""One run of a cell, driven on the CPU at a tiny size past the look for a
chip: its result line, that ``correct`` turns false when the timed path
is broken underneath or the control takes the program's place, and that
the step is donated exactly as the program declares."""
import json
import os
import subprocess
import sys
import time
import types

import bench_tiny
import jax
import jax.numpy as jnp
import pytest

from bench import compare, faults, program, reference
from bench import run as run_lib

LINE_KEYS = ["correct", "attempted", "failed", "metrics", "device", "checks"]
SPEC = json.loads((bench_tiny.ROOT / "BENCHMARK.json").read_text())


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
        "cell = bench_tiny.tiny_cell('whisper-tiny', 'regtopk.b40x448.dp4',"
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


def _declare_donation(monkeypatch):
    """The program's ``assemble`` declares its state donated, as a program
    may, through a wrapped ``Assembled``; no program file changes."""
    assemble = program.assemble

    def declared(*args, **kwargs):
        asm = assemble(*args, **kwargs)
        return types.SimpleNamespace(**asm._asdict(), donate_argnums=(0, 1, 2))

    monkeypatch.setattr(program, "assemble", declared)


@pytest.fixture
def donating(monkeypatch):
    _declare_donation(monkeypatch)


def _tiny_program(cell):
    return program.build(cell.model, cell.config, cell.traffic,
                         jax.devices()[:1])


def test_declared_donation_runs_and_reads_as_undonated(monkeypatch):
    """With the state donated, set-up, the window and the reference touch
    no deleted array, the step consumes its state, and ``correct``'s
    numbers are the undonated run's, bit for bit, on the same seed."""
    cell = _cell()
    plain = _run(cell)
    _declare_donation(monkeypatch)
    donated = _run(cell)
    assert donated["correct"] is True, donated["checks"]
    assert donated["checks"] == plain["checks"]
    prog = _tiny_program(cell)
    assert prog.donate_argnums == (0, 1, 2)
    state = prog.init_state(5)
    batch = prog.batch(run_lib.feed.batches_key(5), 0)
    step = program.compile_step(prog, state, batch)
    assert step.memory_analysis().alias_size_in_bytes > 0
    step(*state, batch)
    assert all(x.is_deleted() for x in jax.tree.leaves(state))


def test_unchanged_state_fault_is_caught_when_donated(donating):
    """The fault's outputs are its donated inputs, handed back."""
    out = _run(_cell(), fault=faults.unchanged_state)
    assert out["correct"] is False, out["checks"]
    assert out["checks"]["delta_gap"]["value"] == pytest.approx(1.0)


def test_donated_initial_state_holds_each_buffer_once(donating):
    """A donated call consumes each buffer once, so no two leaves of the
    initial state may share one; a program whose init shares a buffer
    fails on its first donated step, and this test names it first."""
    prog = _tiny_program(_cell())
    buffers = [(s.device.id, s.data.unsafe_buffer_pointer())
               for x in jax.tree.leaves(prog.init_state(7))
               for s in x.addressable_shards]
    assert len(set(buffers)) == len(buffers)


@pytest.mark.parametrize("config", [c["name"] for c in SPEC["configs"]])
def test_undeclared_step_has_no_input_output_alias(config):
    """Where the program declares nothing, nothing is donated: the step
    compiles as the parent's did, with no argument aliased to an output."""
    traffic = {w["config"]: w["traffic"] for w in SPEC["workloads"]}[config]
    cell = _cell(config, traffic)
    prog = _tiny_program(cell)
    assert prog.donate_argnums == ()
    state = prog.init_state(1)
    step = program.compile_step(prog, state,
                                prog.batch(run_lib.feed.batches_key(1), 0))
    assert step.memory_analysis().alias_size_in_bytes == 0
    assert "input_output_alias" not in step.as_text().split("\n", 1)[0]
