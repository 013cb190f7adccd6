"""Fused select→encode pipeline kernel (Pallas TPU).

The unfused hot path materializes three J-sized intermediates between the
score and the wire: the dense score (written by ``regtopk_score``, re-read
by the selector), the dense mask, and the dense masked gradient — plus a
separate gather of ``a[idx]`` for the payload. This kernel collapses the
chain into **one pass over the gradient leaf**:

    per (8, 1024) tile:  score = |a|^y * tanh(|1 + Delta| / mu)   (registers)
                         m rounds of masked max over the tile's score
                         → (score, a-value, flat index) candidate triples

The score never leaves VMEM: each tile emits its top-``m`` candidates
directly from the score-kernel registers — 4 J-sized reads and a
``(J/8192)·m``-triple write, versus the unfused 4 reads + 1 J-write
(score) + 1 J-read (selector) + gather. The host then runs the cheap
compaction: an exact top-k over the ~1000x smaller candidate set, whose
k-th value is the selection threshold tau, produces the compact
``(idx, val)`` wire payload — codec epilogues (e.g. ``coo_q8``'s
symmetric int8 quantization) operate on those k registers directly
(``Codec.encode_fused``).

Exactness: the candidate set provably contains the global top-k whenever
no tile hides more than ``m`` coordinates scoring at-or-above the k-th
selected value. :func:`select_from_candidates` returns an ``ok`` flag
implementing exactly that certificate (conservative under ties); callers
``lax.cond`` to the unfused path when it fails, so on the same inputs
the pipeline selects exactly what dense selection does — the
certificate only decides which path computed the answer. See
``repro.comm.fastpath`` for the policy layer and
``docs/comm.md#the-fused-fastpath`` for the fusability matrix.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.regtopk_score import score_chain

LANES = 1024
SUBLANES = 8
BLOCK = (SUBLANES, LANES)
TILE = SUBLANES * LANES


def _fused_kernel(
    a_ref, a_prev_ref, s_prev_ref, g_prev_ref,
    cs_ref, cv_ref, ci_ref, *, omega, mu, q, y, m,
):
    i = pl.program_id(0)
    a = a_ref[...]
    # --- scoring stage: the one shared op chain (regtopk_score.score_chain
    # — bit-for-bit parity with the unfused score is what makes the fused
    # payload provably equal to the unfused one).
    score = score_chain(
        a, a_prev_ref[...], s_prev_ref[...], g_prev_ref[...],
        omega=omega, mu=mu, q=q, y=y,
    )
    # --- selection stage: per-tile top-m by m rounds of masked max (the
    # block_topk scan), emitting the payload *value* a alongside the score
    # so no post-hoc gather over the dense gradient is needed.
    rowi = jax.lax.broadcasted_iota(jnp.int32, BLOCK, 0)
    colj = jax.lax.broadcasted_iota(jnp.int32, BLOCK, 1)
    flat = (i * SUBLANES + rowi) * LANES + colj
    # candidates are gathered into (1, m) rows in registers and stored
    # once per tile: Mosaic has no scalar stores to VMEM.
    slot = jax.lax.broadcasted_iota(jnp.int32, (1, m), 1)

    def round_(r, carry):
        s, row_s, row_v, row_i = carry
        cur = jnp.max(s)
        ismax = s == cur
        # first-match tie break: lowest flat index among maxima (matches
        # lax.top_k's stable ordering for the equivalence proof)
        cand = jnp.min(jnp.where(ismax, flat, jnp.iinfo(jnp.int32).max))
        onehot = flat == cand
        here = slot == r
        return (
            jnp.where(onehot, -jnp.inf, s),
            jnp.where(here, cur, row_s),
            jnp.where(here, jnp.sum(jnp.where(onehot, a, 0.0)), row_v),
            jnp.where(here, cand, row_i),
        )

    _, row_s, row_v, row_i = jax.lax.fori_loop(
        0, m, round_,
        (
            score,
            jnp.zeros((1, m), jnp.float32),
            jnp.zeros((1, m), jnp.float32),
            jnp.zeros((1, m), jnp.int32),
        ),
    )
    cs_ref[0] = row_s
    cv_ref[0] = row_v
    ci_ref[0] = row_i


def fused_candidates(
    a: jax.Array,
    a_prev: jax.Array,
    s_prev: jax.Array,
    g_prev: jax.Array,
    *,
    omega: float,
    mu: float,
    q: float = 1e9,
    y: float = 1.0,
    m: int = 16,
    interpret: bool = False,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """All inputs [rows, 1024] float32. Returns per-tile candidate triples
    ``(scores [nblk, m], values [nblk, m], flat idx [nblk, m])`` where
    ``nblk = rows // 8`` — the score is computed and consumed in-register,
    never written back dense. The kernel writes ``[nblk, 1, m]`` so each
    tile's output block spans the array's last two dimensions, as Mosaic
    requires of a block narrower than (8, 128)."""
    rows, lanes = a.shape
    if lanes != LANES:
        raise ValueError(f"expected lane dim {LANES}, got {lanes}")
    if rows % SUBLANES:
        raise ValueError(f"rows must be a multiple of {SUBLANES}")
    nblk = rows // SUBLANES
    spec = pl.BlockSpec(BLOCK, lambda i: (i, 0))
    cand = pl.BlockSpec((1, 1, m), lambda i: (i, 0, 0))
    kernel = functools.partial(
        _fused_kernel, omega=omega, mu=mu, q=q, y=y, m=m
    )
    cs, cv, ci = pl.pallas_call(
        kernel,
        grid=(nblk,),
        in_specs=[spec, spec, spec, spec],
        out_specs=(cand, cand, cand),
        out_shape=(
            jax.ShapeDtypeStruct((nblk, 1, m), jnp.float32),
            jax.ShapeDtypeStruct((nblk, 1, m), jnp.float32),
            jax.ShapeDtypeStruct((nblk, 1, m), jnp.int32),
        ),
        interpret=interpret,
    )(a, a_prev, s_prev, g_prev)
    return cs[:, 0], cv[:, 0], ci[:, 0]


def select_from_candidates(
    cand_score: jax.Array,
    cand_val: jax.Array,
    cand_idx: jax.Array,
    k: int,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Compact the ``[nblk, m]`` candidate triples into the fixed-k payload.

    Returns ``(vals [k], idx [k], ok)``. The top-k over the flattened
    candidate scores doubles as the threshold selection: the k-th selected
    score is the selection threshold tau, and candidate order (tile-major,
    rank-minor) equals flat-index order under ties, so the result is
    bit-for-bit ``lax.top_k`` over the dense score *provided* the
    exactness certificate ``ok`` holds:

        ok  :=  every tile's m-th (smallest kept) candidate  <  tau

    If a tile's m-th candidate reaches tau, coordinates hidden below its
    candidate budget could score at-or-above tau (or tie it), so the
    caller must fall back to dense selection. ``tau == 0`` (selection ran
    out of positive scores) always fails the certificate — zero scores
    are never selected on the fast path, which also keeps zero-padding
    flat indices (>= the true length) out of the payload.

    Single-tile refinement: with one tile the candidates *are* the exact
    top-m (m rounds of masked max), tie order included, so any positive
    tau certifies exactness — a hidden tie at tau necessarily carries a
    higher flat index than every selected tie (the masked max consumes
    equal values lowest-index first), which is precisely ``lax.top_k``'s
    ordering. Across tiles that argument breaks (a hidden tie in an early
    tile would outrank a selected tie in a later one), hence the strict
    inequality there."""
    nblk, m = cand_score.shape
    k = int(k)
    if k > nblk * m:
        raise ValueError(
            f"k={k} exceeds the candidate budget {nblk}x{m}; the caller "
            "should have routed this leaf to the unfused path"
        )
    top_s, pos = jax.lax.top_k(cand_score.reshape(-1), k)
    tau = top_s[k - 1]
    vals = cand_val.reshape(-1)[pos] * (top_s > 0)
    idx = cand_idx.reshape(-1)[pos]
    if nblk == 1:
        ok = tau > 0
    else:
        ok = jnp.all(cand_score[:, m - 1] < tau)
    return vals, idx, ok
