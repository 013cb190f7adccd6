"""Threshold-count kernel for sort-free top-k (Pallas TPU).

Exact ``lax.top_k`` over a J-sized score is sort-bound (O(J log J), poor
VPU utilization). Gradient-compression systems (DGC, ScaleCom) instead
find a *threshold*: this kernel computes, in one streaming pass per
bisection step,

    count(tau)  = #{ j : score[j] >= tau }        (for the bisection)
    blockmax    = max over the whole vector       (for the initial bracket)

The grid walks (8, 1024) VMEM tiles; scalar results accumulate into a
(1, 1) SMEM output across sequential grid steps (TPU grid execution is
sequential, so read-modify-write accumulation is well-defined). Scalars
live in SMEM because Mosaic cannot store a scalar to VMEM.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 1024
SUBLANES = 8
BLOCK = (SUBLANES, LANES)
SCALAR = pl.BlockSpec(memory_space=pltpu.SMEM)


def _count_kernel(tau_ref, score_ref, count_ref):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        count_ref[0, 0] = jnp.int32(0)

    tau = tau_ref[0, 0]
    c = jnp.sum((score_ref[...] >= tau).astype(jnp.int32))
    count_ref[0, 0] += c


def _max_kernel(score_ref, max_ref):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        max_ref[0, 0] = jnp.float32(-jnp.inf)

    m = jnp.max(score_ref[...])
    max_ref[0, 0] = jnp.maximum(max_ref[0, 0], m)


def count_above(
    score: jax.Array, tau: jax.Array, *, interpret: bool = False
) -> jax.Array:
    rows, lanes = score.shape
    grid = (rows // SUBLANES,)
    return pl.pallas_call(
        _count_kernel,
        grid=grid,
        in_specs=[SCALAR, pl.BlockSpec(BLOCK, lambda i: (i, 0))],
        out_specs=SCALAR,
        out_shape=jax.ShapeDtypeStruct((1, 1), jnp.int32),
        interpret=interpret,
    )(tau.reshape(1, 1), score)[0, 0]


def global_max(score: jax.Array, *, interpret: bool = False) -> jax.Array:
    rows, lanes = score.shape
    grid = (rows // SUBLANES,)
    return pl.pallas_call(
        _max_kernel,
        grid=grid,
        in_specs=[pl.BlockSpec(BLOCK, lambda i: (i, 0))],
        out_specs=SCALAR,
        out_shape=jax.ShapeDtypeStruct((1, 1), jnp.float32),
        interpret=interpret,
    )(score)[0, 0]


def threshold_topk_mask(
    score: jax.Array,
    k: int,
    *,
    n_iters: int = 24,
    interpret: bool = False,
) -> jax.Array:
    """~k-cardinality mask via kernel-accelerated bisection.

    ``score`` [rows, 1024] non-negative. Matches
    ``repro.core.selectors.threshold_topk_mask`` semantics (mask contains
    the exact top-k, possibly a few extra on ties/unconverged brackets).
    """
    hi0 = global_max(score, interpret=interpret)
    lo0 = jnp.zeros_like(hi0)

    def body(_, carry):
        lo, hi = carry
        mid = 0.5 * (lo + hi)
        c = count_above(score, mid, interpret=interpret)
        ok = c >= k
        return jnp.where(ok, mid, lo), jnp.where(ok, hi, mid)

    lo, _ = jax.lax.fori_loop(0, n_iters, body, (lo0, hi0))
    # zero scores carry no gradient and are never selected — keeps the
    # all-zero-score round from collapsing to an all-ones mask (matches
    # the selectors.threshold_topk_mask fix).
    return ((score >= lo) & (score > 0)).astype(score.dtype)
