"""Run one benchmark cell once on the chips of this machine.

    python bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Set-up builds the program's train step for the cell, makes its weights
and state on the device from the seed, compiles the step and drives it
through its first three steps (the ones the reference follows). The
window then dispatches steps back to back for ``--seconds`` and ends on
``block_until_ready``. ``--trace 1`` runs the window (at most
``TRACE_SECONDS`` of it) under the profiler and reports the per-layer
metrics instead of the end-to-end ones. Afterwards the plain reference
repeats the first three steps and decides ``correct``. The last line of
stdout is one JSON object; the last lines of stderr are the compared
numbers beside their limits. Without a TPU, or with fewer chips than the
cell asks for, it exits non-zero and prints no result.
"""
from __future__ import annotations

import time

T0 = time.perf_counter()  # process start, for setup_s

import argparse  # noqa: E402
import gc  # noqa: E402
import glob  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from types import ModuleType  # noqa: E402
from typing import Any, Dict, NamedTuple  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

import jax  # noqa: E402

from bench import cell as cell_lib  # noqa: E402
from bench import compare, feed, peaks, program, reference  # noqa: E402
from bench import trace as trace_lib  # noqa: E402

SETUP_STEPS = 3  # steps driven in set-up; the reference follows them
# A traced run's window is at most this long: reading the trace takes
# about 0.3 ms an event, and a step has thousands of events on each chip.
TRACE_SECONDS = 6.0


class LayerContext(NamedTuple):
    """What a per-layer metric reader is given."""

    trace: trace_lib.Reduction  # device s by layer, by named scope, by op
    steps: int  # steps in the traced window
    window_s: float
    chips: int
    flops_per_step: float
    peak_flops: float  # bf16 FLOP/s per chip
    hbm_bytes_per_s: float  # per chip
    wire_bytes: int
    model: ModuleType  # configs/<config>.py: reference, init, FLOPs
    sizes: Dict[str, Any]  # the configuration's "model" sizes, as run
    traffic: Dict[str, Any]  # traffic/<mix>.json


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def tpu_devices(chips: int):
    """The first ``chips`` TPU devices; raises where there are fewer."""
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise RuntimeError(f"no TPU: JAX found {devs[0].platform}")
    if len(devs) < chips:
        raise RuntimeError(f"the cell needs {chips} chips, JAX found "
                           f"{len(devs)}")
    return devs[:chips]


def peak_bytes(device) -> int:
    """The device's peak bytes: its buffers' peak in use plus the peak it
    reserved for compiled programs' temporaries, which the TPU runtime
    keeps apart from ``peak_bytes_in_use`` (0 where the runtime reports
    nothing, as the CPU's does)."""
    stats = device.memory_stats() or {}
    return (stats.get("peak_bytes_in_use", 0)
            + stats.get("peak_bytes_reserved", 0))


_MEMORY_KEYS = ("bytes_in_use", "bytes_reserved", "largest_free_block_bytes",
                "num_allocs", "largest_alloc_size")


def memory_state(device) -> Dict[str, int]:
    """The allocator's state, as far as the runtime reports it."""
    stats = device.memory_stats() or {}
    return {k: stats[k] for k in _MEMORY_KEYS if k in stats}


def _ms(seconds) -> list:
    return [round(1e3 * s, 3) for s in seconds]


def use_compile_cache() -> None:
    """Keep JAX's persistent compile cache, for every program, where
    ``JAX_COMPILATION_CACHE_DIR`` says, and otherwise in the checkout at a
    fixed path (the path is part of the cache's key)."""
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          str(ROOT / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)


def first_steps(cell: cell_lib.Cell, prog: program.Program, seed: int,
                step=None, train_step=None):
    """Make the state from ``seed``, compile the step where ``step`` is
    None, and drive it through the first ``SETUP_STEPS`` steps. Returns
    (step, state, the program's ``reference.Readings``)."""
    m, b1 = cell.config["model"], cell.traffic["optimizer"]["b1"]
    key = feed.batches_key(seed)
    state = prog.init_state(seed)
    if step is None:
        step = program.compile_step(prog, state, prog.batch(key, 0),
                                    train_step)
        mem = step.memory_analysis()
        log(f"compiled step bytes per device: args "
            f"{mem.argument_size_in_bytes} out {mem.output_size_in_bytes} "
            f"temp {mem.temp_size_in_bytes} alias {mem.alias_size_in_bytes}")
    names = reference.leaf_names(state[0])
    losses, grad_norms = [], None
    for t in range(SETUP_STEPS):
        *state, metrics = step(*state, prog.batch(key, t))
        losses.append(float(metrics["loss"]))
        if t == 0:  # Adam's first moment is (1 - b1) times its gradient
            grad_norms = [x / (1 - b1) for x in
                          _norms(state[1]["m"]).tolist()]
    start = jax.jit(lambda k: cell.model.init(k, m, prog.cfg.jdtype),
                    out_shardings=jax.tree.map(lambda x: x.sharding,
                                               state[0]))
    delta_norms = _delta_norms(state[0], start(feed.weights_key(seed)))
    readings = reference.Readings(
        losses, dict(zip(names, grad_norms, strict=True)),
        dict(zip(names, delta_norms.tolist(), strict=True)))
    return step, state, readings


@jax.jit
def _norms(tree):
    return reference.leaf_norms(jax.tree.leaves(tree))


@jax.jit
def _delta_norms(a, b):
    return reference.leaf_norms(
        [x - y for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b),
                               strict=True)])


def run_cell(cell: cell_lib.Cell, seed: int, seconds: float, trace: bool,
             devices, t0: float = T0, fault=None) -> Dict[str, Any]:
    """One run of ``cell``; returns the result line's object. ``fault``
    maps the program's step to a broken one (tests plant faults so)."""
    m, traffic = cell.config["model"], cell.traffic
    prog = program.build(cell.model, cell.config, traffic, devices)
    W = prog.n_workers
    key = feed.batches_key(seed)
    step, state, prog_readings = first_steps(
        cell, prog, seed, train_step=fault(prog.step) if fault else None)
    log(f"set-up losses {prog_readings.losses}")

    tmp = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    if trace:
        seconds = min(seconds, TRACE_SECONDS)
        jax.profiler.start_trace(tmp)
    step_losses = []
    # host clock, from the window's start, when each step's feed and
    # dispatch returned and when its loss was ready
    fed, sent, done = [], [], []
    t = SETUP_STEPS
    mem_before, gc_before = memory_state(devices[0]), gc.get_stats()
    setup_s = time.perf_counter() - t0
    w0 = time.perf_counter()
    with jax.profiler.TraceAnnotation("bench.window"):
        pending = None
        while True:
            with jax.profiler.StepTraceAnnotation("bench.step", step_num=t):
                with jax.profiler.TraceAnnotation("bench.feed"):
                    batch = prog.batch(key, t)
                fed.append(time.perf_counter() - w0)
                with jax.profiler.TraceAnnotation("bench.dispatch"):
                    *state, metrics = step(*state, batch)
                sent.append(time.perf_counter() - w0)
                if pending is not None:
                    with jax.profiler.TraceAnnotation("bench.wait"):
                        pending.block_until_ready()
                    done.append(time.perf_counter() - w0)
            pending = metrics["loss"]
            step_losses.append(pending)
            t += 1
            if time.perf_counter() - w0 >= seconds:
                break
        with jax.profiler.TraceAnnotation("bench.wait"):
            jax.block_until_ready(state)
    window_s = time.perf_counter() - w0
    done.append(window_s)
    n_steps = t - SETUP_STEPS
    peaks_in_use = [peak_bytes(d) for d in devices]
    peak = max(peaks_in_use)
    log(f"window {window_s:.4f} s, {n_steps} steps; peak bytes in use and "
        f"reserved per device {peaks_in_use}; device 0 memory_stats "
        f"{devices[0].memory_stats()}")
    log("window steps " + json.dumps({
        "fed": _ms(fed), "sent": _ms(sent), "done": _ms(done),
        "gc_collections": [a["collections"] - b["collections"] for a, b in
                           zip(gc.get_stats(), gc_before, strict=True)],
        "memory_before": mem_before,
        "memory_after": memory_state(devices[0])}))
    hlo = step.as_text() if trace else None
    failed = sum(not math.isfinite(float(x)) for x in step_losses)
    del state, metrics, pending, batch, step, step_losses
    gc.collect()

    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": peak}
    global_batch = traffic["batch_per_chip"] * W
    result: Dict[str, Any] = {}
    if trace:
        jax.profiler.stop_trace()
        pb = glob.glob(f"{tmp}/**/*.xplane.pb", recursive=True)
        ops, spans = trace_lib.read(pb[0])
        shutil.rmtree(tmp, ignore_errors=True)
        red = trace_lib.reduce(ops, spans, W, trace_lib.window_of(spans),
                               trace_lib.hlo_layers(hlo),
                               trace_lib.hlo_scopes(hlo))
        chip = peaks.chip_peaks(dev.device_kind)
        ctx = LayerContext(
            trace=red, steps=n_steps, window_s=window_s, chips=W,
            flops_per_step=cell.model.flops_per_step(
                m, global_batch, traffic["seq"]),
            peak_flops=chip.bf16_flops, hbm_bytes_per_s=chip.hbm_bytes_per_s,
            wire_bytes=prog.wire_bytes, model=cell.model, sizes=m,
            traffic=traffic)
        metrics_out = {}
        for spec in cell.per_layer:
            v = cell.readers[spec["name"]].read(ctx)
            if v is not None:
                metrics_out[spec["name"]] = {"value": v, "unit": spec["unit"]}
        device.update(busy_s=red.busy_s, window_s=window_s)
        result["breakdown"] = {"device_ops": red.top_ops,
                               "idle_gaps": red.idle_by_host}
    else:
        values = {
            "tokens_per_s": n_steps * global_batch * traffic["seq"] / window_s,
            "peak_hbm_gb": peak / 1e9,
            "setup_s": setup_s,
        }
        metrics_out = {s["name"]: {"value": values[s["name"]],
                                   "unit": s["unit"]}
                       for s in cell.end_to_end}

    ref = reference.run(cell.model, m, traffic, seed, W, SETUP_STEPS,
                        device=dev)
    correct, checks = compare.verdict(prog_readings, ref, cell.limits)
    correct = correct and failed == 0
    for k, c in checks.items():
        log(f"check {k}: {c['value']!r} (limit {c['limit']!r})")
    return {"correct": correct, "attempted": n_steps, "failed": failed,
            "metrics": metrics_out, "device": device, **result,
            "checks": checks}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=[0, 1], default=0)
    args = ap.parse_args(argv)
    cell = cell_lib.load_cell(args.workload)
    use_compile_cache()
    try:
        devices = tpu_devices(cell.chips)
    except RuntimeError as e:
        log(str(e))
        return 2
    out = run_cell(cell, args.seed, args.seconds, bool(args.trace), devices)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
