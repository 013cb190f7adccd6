"""Smoke check: sparsified data-parallel training runs on the TPU.

Drives ``repro.launch.train.main``, the trainer a user calls, on
whisper-tiny at its published widths and depth (4 encoder + 4 decoder
layers, d_model 384, 6 heads, d_ff 1536, vocab 51865, 1500 encoder
frames, 448 decoder tokens), with random weights from a fixed seed. Every
phase runs in this one process, a few steps each.

    python chip_smoke.py              # one chip: phases (a), (b), (c)
    python chip_smoke.py --chips 4    # four chips: data parallelism only

One chip:
  (a) regtopk, S = 0.01, sparse_allgather, fastpath off
  (b) the same with fastpath on (Pallas fused select->encode)
  (c) sparsifier none, the dense row
Four chips, mesh (4, 1), four data-parallel workers:
  (s) regtopk over sparse_allgather, fastpath off
  (d) the same selection aggregated by dense_allreduce
  (f) (s) with fastpath on

Step times printed here are smoke timings, not benchmark numbers. The
last line of stdout is one JSON object, ``{"ok": ..., "device": {...}}``;
the exit code is 0 only when every check held. Without a TPU the script
exits non-zero and prints no result.
"""
from __future__ import annotations

import argparse
import json
import math
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro.launch import mesh as meshlib  # noqa: E402
from repro.launch import train  # noqa: E402

STEPS = 6  # the first is warm-up; step times are over the other five
BATCH_PER_CHIP = 40  # largest with headroom in the step's memory analysis
MODEL = [
    "--arch", "whisper-tiny", "--seq", "448", "--sparsity", "0.01",
    "--log-every", "1000",
]
REGTOPK = ["--sparsifier", "regtopk"]
# Sparse and dense aggregation of one selection differ only in the order
# of the float sums, so their losses stay this close.
AGG_LOSS_TOL = 1e-4
# Fused vs unfused selection: the kernel's scores match XLA's bitwise, but
# the two steps are different programs, and XLA may compile their shared
# backward pass with another summation order. Gradients then differ in
# the last ulp, a near-tie at the k-th score can flip, and the runs drift.
FUSED_LOSS_TOL = 1e-3


def peak_bytes(device) -> int:
    return device.memory_stats()["peak_bytes_in_use"]


def phase(label: str, n_chips: int, *flags: str) -> train.TrainRun:
    run = train.main([
        *MODEL, "--steps", str(STEPS),
        "--global-batch", str(BATCH_PER_CHIP * n_chips), *flags,
    ])
    slots = run.n_fused * len(jax.devices()) * len(run.losses)
    share = sum(run.fallbacks) / slots if slots else 0.0
    peaks = [peak_bytes(d) for d in jax.devices()]
    mem = run.compiled.memory_analysis()
    print(
        f"[{label}] compile {run.compile_seconds:.2f} s | smoke step "
        f"{run.step_seconds:.4f} s (smoke timing, not a benchmark) | "
        f"process peak bytes per device {peaks} | compiled step bytes: "
        f"args {mem.argument_size_in_bytes} out {mem.output_size_in_bytes} "
        f"temp {mem.temp_size_in_bytes} | fused leaves "
        f"{run.n_fused}/{run.n_leaves} | certificate fallbacks "
        f"{sum(run.fallbacks):.0f}/{slots} ({share:.4f}) | losses "
        f"{run.losses}",
        flush=True,
    )
    return run


def gaps(x: train.TrainRun, y: train.TrainRun):
    """(max |d loss| over steps, max |d param| over every weight)."""
    dl = max(abs(a - b) for a, b in zip(x.losses, y.losses, strict=True))
    dp = max(
        float(jnp.max(jnp.abs(a - b)))
        for a, b in zip(
            jax.tree.leaves(x.params), jax.tree.leaves(y.params), strict=True
        )
    )
    return dl, dp


def check(checks: dict, name: str, held: bool) -> None:
    checks[name] = bool(held)
    print(f"check {name}: {'held' if held else 'FAILED'}", flush=True)


def trained(checks: dict, label: str, run: train.TrainRun) -> None:
    check(checks, f"{label} losses finite", all(map(math.isfinite, run.losses)))
    check(checks, f"{label} loss fell", run.losses[-1] < run.losses[0])


def fused_agrees(checks, label, unfused, fused) -> None:
    check(checks, f"{label} fused leaves > 0", fused.n_fused > 0)
    check(
        checks, f"{label} step holds tpu_custom_call",
        "tpu_custom_call" in fused.compiled.as_text(),
    )
    dl, dp = gaps(unfused, fused)
    print(
        f"fused vs unfused: max |d loss| {dl!r}, max |d param| {dp!r}, "
        f"bit-for-bit {dl == 0.0 and dp == 0.0}",
        flush=True,
    )
    check(checks, f"{label} fused agrees (|d loss| <= {FUSED_LOSS_TOL})",
          dl <= FUSED_LOSS_TOL)


def one_chip(checks: dict) -> None:
    a = phase("a regtopk fastpath off", 1, *REGTOPK,
              "--collective", "sparse_allgather", "--fastpath", "off")
    trained(checks, "a", a)
    b = phase("b regtopk fastpath on", 1, *REGTOPK,
              "--collective", "sparse_allgather", "--fastpath", "on")
    trained(checks, "b", b)
    fused_agrees(checks, "b", a, b)
    del b
    c = phase("c dense row", 1, "--sparsifier", "none")
    check(checks, "c losses finite", all(map(math.isfinite, c.losses)))


def four_chips(checks: dict) -> None:
    s = phase("s regtopk sparse_allgather", 4, *REGTOPK,
              "--collective", "sparse_allgather", "--fastpath", "off")
    trained(checks, "s", s)
    leaf = jax.tree.leaves(s.params)[0]
    eps = jax.tree.leaves(s.sp_state)[0]
    print(f"parameter leaf {leaf.shape}: {leaf.sharding}", flush=True)
    print(f"error accumulator leaf {eps.shape}: {eps.sharding}", flush=True)
    d = phase("d regtopk dense_allreduce", 4, *REGTOPK,
              "--collective", "dense_allreduce", "--fastpath", "off")
    trained(checks, "d", d)
    dl, dp = gaps(s, d)
    print(f"sparse vs dense aggregation: max |d loss| {dl!r}, "
          f"max |d param| {dp!r}", flush=True)
    check(checks, f"sparse == dense aggregation (|d loss| <= {AGG_LOSS_TOL})",
          dl <= AGG_LOSS_TOL)
    del d
    f = phase("f regtopk fastpath on", 4, *REGTOPK,
              "--collective", "sparse_allgather", "--fastpath", "on")
    trained(checks, "f", f)
    fused_agrees(checks, "f", s, f)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=[1, 4])
    args = ap.parse_args(argv)
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"no TPU: JAX found {dev.platform}", file=sys.stderr)
        return 1
    if len(jax.devices()) != args.chips:
        print(f"expected {args.chips} chips, JAX found {len(jax.devices())}",
              file=sys.stderr)
        return 1
    meshlib.enable_compile_cache()
    checks = {}
    (one_chip if args.chips == 1 else four_chips)(checks)
    ok = all(checks.values())
    print(json.dumps({"ok": ok, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices()),
    }}))
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
