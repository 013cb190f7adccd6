"""Compact (memory-optimal) sparsifier state for the distributed runtime.

The simulator's dense ``SparsifierState`` stores eps, a_prev and s_prev —
3 full J-sized vectors per worker. At framework scale (mixtral: J = 47B,
J/16 per model shard) that is untenable. Observation (ours, beyond paper):
Algorithm 2 only ever reads

  * ``a^{t-1}`` and ``s^{t-1}`` at the k *sent* coordinates (everywhere
    else the likelihood is the constant C), and
  * ``g^{t-1}`` at those same coordinates (the posterior-distortion
    numerator).

So the exact per-worker state is: dense error ``eps [L]`` plus three
k-vectors ``(sent_vals, sent_g, sent_idx)`` — a 3x memory reduction with
bit-identical selection. This module implements Top-k / RegTop-k / cyclic
(coordinated) / none over flat local gradient shards with that layout.

All functions operate on the *local* view inside ``shard_map``:
one (worker × model-shard) flat vector of length L.
"""
from __future__ import annotations

import dataclasses
import math
from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp

from repro.core import selectors as sel_lib
from repro.core import stages
from repro.core.sparsify import SparsifierConfig


class CompactState(NamedTuple):
    eps: jax.Array  # [L]   dense sparsification error
    sent_vals: jax.Array  # [k]   a^{t-1} at sent coords
    sent_g: jax.Array  # [k]   g^{t-1} (aggregated) at sent coords
    sent_idx: jax.Array  # [k]   int32 coords sent at t-1
    # [k] per-coordinate sender mass den[j] the server divided by at the
    # sent coords (weighting="coordinate"); exactly 1.0 under worker
    # weighting, so omega / sent_w == omega bit-for-bit there.
    sent_w: jax.Array
    t: jax.Array  # []    round counter


def compact_init(length: int, k: int, dtype=jnp.float32) -> CompactState:
    # sent_w starts at 0 (matching the zeros-everywhere state init the
    # runtimes broadcast); compact_select guards it to 1 before dividing,
    # and round 0 scores plain Top-k anyway (t == 0).
    return CompactState(
        eps=jnp.zeros((length,), dtype),
        sent_vals=jnp.zeros((k,), dtype),
        sent_g=jnp.zeros((k,), dtype),
        sent_idx=jnp.zeros((k,), jnp.int32),
        sent_w=jnp.zeros((k,), dtype),
        t=jnp.zeros((), jnp.int32),
    )


def apply_k_dyn(a, vals, idx, k_dyn, capacity: int):
    """Keep only the first ``k_dyn`` of the descending-sorted payload.

    ``lax.top_k`` (and the bit-identical fused pipeline) returns values in
    descending score order, so masking the tail selects exactly the
    dynamic top-``k_dyn`` — the masked slots keep their real, distinct
    indices with value 0, the same no-op-under-scatter-add convention the
    static path uses for unfilled slots."""
    keep = (jnp.arange(capacity) < k_dyn).astype(vals.dtype)
    return a, vals * keep, idx


# Scores shorter than this select with one ``lax.top_k``. On the v5e the
# two-level path was faster at every length swept, 2^16 to 2^28 at S = 0.01,
# but each two-level leaf adds two sorts to the step, ~2.5 MB of device code
# each; from 2^20 up it saves 0.9 ms a call or more (the sweep in PERF.md).
TWO_LEVEL_MIN_LEN = 1 << 20
_LANES = 128  # a TPU vector row; groups are lane segments of one row


def top_k_group(length: int, k: int) -> int:
    """Group size G with which :func:`exact_top_k` selects ``k`` of a
    ``length``-element score: 1 is one ``lax.top_k`` over the whole score,
    G > 1 the two-level path over groups of G. G is the power of two
    nearest ``sqrt(length / k)`` (8 at S = 0.01), at most 128, taken only
    from ``TWO_LEVEL_MIN_LEN`` up and where the ``k·G`` candidates are at
    most half the score."""
    if length < TWO_LEVEL_MIN_LEN or k < 1:
        return 1
    g = 1 << max(0, math.floor(0.5 * math.log2(length / k) + 0.5))
    g = min(g, _LANES)
    return g if 2 * k * g <= length else 1


def exact_top_k(score: jax.Array, k: int) -> Tuple[jax.Array, jax.Array]:
    """``jax.lax.top_k(score, k)`` bit for bit (values, indices, order) for
    a flat score ``>= 0``, without sorting the whole score.

    Two levels over contiguous groups of G (:func:`top_k_group`): the top
    k of the group maxima (one ``lax.top_k`` over L/G elements), the k
    winning groups in ascending order, then the k·G scores of those groups,
    which lie in index order, sorted descending by a stable sort, as
    ``lax.top_k`` sorts. Exact, ties included: if x were in the top k but
    its group lost, each of the k groups ranked above it holds an element
    ahead of x (a larger score, or an equal one in a group of lower ids,
    so at a lower index): k elements ahead of x.
    """
    length = score.shape[0]
    g = top_k_group(length, k)
    if g == 1:
        return jax.lax.top_k(score, k)
    rows, per_row = -(-length // _LANES), _LANES // g
    # whole rows of 128 lanes, padded with -1, which every score beats: no
    # group wholly of padding wins, and no padding is among the k selected
    s = jnp.pad(score, (0, rows * _LANES - length), constant_values=-1)
    s = s.reshape(rows, _LANES)
    # group maxima in index order; through the transpose the lane segments
    # become sublanes, which the TPU reduces without a relayout
    gmax = s.T.reshape(per_row, g, rows).max(axis=1).T.reshape(-1)
    _, gid = jax.lax.top_k(gmax, k)
    gid = jnp.sort(gid)
    # each winning group's row (a row gather, not k·G element gathers),
    # then its segment of g lanes: the candidates in index order
    row, col = jax.lax.div(gid, per_row), jax.lax.rem(gid, per_row)
    seg = jnp.arange(per_row) == col[:, None]
    cand = s[row].reshape(k, per_row, g)
    cand = jnp.where(seg[..., None], cand, -1).max(axis=1).reshape(-1)
    idx = (gid[:, None] * g + jnp.arange(g, dtype=gid.dtype)).reshape(-1)
    # sorted with the indices beside them: no gather of positions after
    neg, idx = jax.lax.sort((-cand, idx), num_keys=1, is_stable=True)
    return -neg[:k], idx[:k]


def compact_select(
    cfg: SparsifierConfig,
    st: CompactState,
    g: jax.Array,
    k: int,
    *,
    k_dyn: jax.Array | None = None,
    fastpath: str | None = None,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Select coordinates. Returns (a, vals [k], idx [k]).

    ``a`` is the accumulated gradient; (vals, idx) the fixed-k payload.

    ``k_dyn`` (optional, *traced* int, ``<= k``) is the adaptive
    controller's per-round k: selection still runs at the static capacity
    ``k`` (payload shapes never change — no retrace), then payload values
    beyond ``k_dyn`` are zeroed. Only the magnitude-scored kinds under the
    ``"exact"`` selector support it; at ``k_dyn == k`` the result is
    bit-for-bit the static path.

    ``fastpath`` routes fusable configs through the Pallas fused
    select→encode pipeline (:mod:`repro.comm.fastpath`): ``"on"``/
    ``"auto"`` fuse when the (kind, selector, shape, f32 state) admits
    it — the selection is identical (a runtime exactness
    certificate falls back to this dense path otherwise; a non-f32 state
    would score in a different precision, so it never fuses) — while
    ``None``/``"off"`` is the historical dense selection. ``"auto"``
    additionally requires a TPU backend and the throughput table's
    blessing, mirroring ``DistConfig.resolved_fastpath``.

    The score is traced under the ``spa.score`` scope, top-k and the
    payload's gather under ``spa.select`` (:mod:`repro.core.stages`); the
    fused pipeline scores and selects in one kernel, so all of it is
    ``spa.select``.
    """
    L = g.shape[0]
    if k_dyn is not None and (
        cfg.kind not in ("topk", "regtopk") or cfg.selector != "exact"
    ):
        raise ValueError(
            "dynamic per-round k needs a magnitude-scored fixed-k kind "
            "('topk'/'regtopk') under selector='exact'; got kind="
            f"{cfg.kind!r} selector={cfg.selector!r}"
        )
    if fastpath not in (None, "off"):
        from repro.comm import fastpath as fp

        if fastpath not in fp.FASTPATH_MODES:
            raise ValueError(
                f"unknown fastpath {fastpath!r}; "
                f"available: {fp.FASTPATH_MODES}"
            )
        if (
            st.eps.dtype == jnp.float32
            and fp.config_fusable(cfg)[0]
            and fp.shape_fusable(L, k)[0]
            and (
                fastpath == "on"
                or (
                    fp.backend_supports()
                    and fp.ThroughputTable().prefers_fused(L, k)
                )
            )
        ):
            with jax.named_scope(stages.SELECT):
                a, vals, idx, _ = fp.fused_compact_select(cfg, st, g, k)
                if k_dyn is None:
                    return a, vals, idx
                return apply_k_dyn(a, vals, idx, k_dyn, k)
    with jax.named_scope(stages.SCORE):
        a = st.eps + g.astype(st.eps.dtype)
    if cfg.kind == "none":
        raise ValueError("'none' bypasses compact_select")
    if cfg.kind == "cyclic":
        # Beyond-paper coordinated round-robin (common across workers):
        # the mask is a pure function of (t, k, L) -> exact cancellation of
        # heterogeneous components (see EXPERIMENTS.md §Beyond).
        with jax.named_scope(stages.SELECT):
            start = (st.t * k) % L
            idx = (start + jnp.arange(k)) % L
            return a, a[idx], idx

    with jax.named_scope(stages.SCORE):
        amag = jnp.abs(a)
        if cfg.kind == "topk":
            score = amag
        elif cfg.kind == "regtopk":
            # Remark-4 prior exponent: the selection metric is |a|^y * reg. The
            # exponent must be applied *before* the sent-coordinate
            # regularization so sent scores are mag^y * reg, matching
            # RegTopK._score (t == 0 is plain Top-k — Alg. 2 line 2).
            mag = amag if cfg.y == 1.0 else amag**cfg.y
            # dense default: unsent coords carry likelihood C = tanh(Q/mu) -> 1.
            # Under coordinate weighting the server divided each sent coord by
            # its sender mass (sent_w), so this worker's effective omega there
            # was omega / sent_w; worker weighting records sent_w == 1, making
            # the division exact and the path bit-for-bit with the scalar form.
            w_safe = jnp.where(st.sent_w > 0, st.sent_w, 1.0)
            omega_vec = cfg.omega / w_safe
            denom = omega_vec * a[st.sent_idx]
            safe = jnp.where(denom == 0, 1.0, denom)
            delta = (st.sent_g - omega_vec * st.sent_vals) / safe
            reg = jnp.tanh(jnp.abs(1.0 + delta) / cfg.mu)
            sent_score = mag[st.sent_idx] * reg
            score = jnp.where(
                st.t == 0, amag, mag.at[st.sent_idx].set(sent_score)
            )
        else:
            raise ValueError(f"unsupported compact kind {cfg.kind!r}")
    with jax.named_scope(stages.SELECT):
        if cfg.selector == "exact":
            top, idx = exact_top_k(score, k)
            # zero scores are never selected (parity with exact_topk_mask):
            # unfilled slots keep their (distinct) top-k index but carry value
            # 0 — a no-op contribution on the wire, and no duplicate indices
            # for the scatter consumers downstream.
            vals = a[idx] * (top > 0)
            if k_dyn is None:
                return a, vals, idx
            return apply_k_dyn(a, vals, idx, k_dyn, k)
        if cfg.selector == "threshold":
            mask = sel_lib.threshold_topk_mask(score, k)
            vals, idx = sel_lib.mask_to_payload(mask, a, k)
            return a, vals, idx
    raise ValueError(
        f"compact_select does not support selector {cfg.selector!r}; "
        "available: 'exact', 'threshold'"
    )


def _sent_w_at(
    idx: jax.Array, den: jax.Array | None, dtype
) -> jax.Array:
    """Record the sender mass at the sent coords: ``den[idx]`` under
    coordinate weighting, exactly 1.0 under worker weighting (den=None)."""
    if den is None:
        return jnp.ones(idx.shape, dtype)
    return den[idx].astype(dtype)


def compact_finalize(
    st: CompactState,
    a: jax.Array,
    vals: jax.Array,
    idx: jax.Array,
    agg: jax.Array,
    den: jax.Array | None = None,
) -> CompactState:
    """Post-aggregation state update (needs the aggregated gradient to
    record sent_g for the next round's posterior distortion; ``den`` is
    the per-coordinate sender mass under coordinate weighting).

    ``eps' = a - scatter_add(vals, idx)``: exactly zero at genuinely sent
    coordinates (``vals == a[idx]`` there, and ``x - x == 0`` in floats),
    and — unlike an ``a.at[idx].set(0)`` — it keeps the full accumulated
    value at any *padding* slot (value 0 riding a real index, produced
    when fewer than k coordinates have nonzero score, or by
    ``mask_to_payload``'s (0, 0) pairs), so an unsent coordinate is never
    silently dropped from error feedback."""
    sent_dense = jnp.zeros_like(a).at[idx].add(vals)
    eps_new = a - sent_dense
    return CompactState(
        eps=eps_new,
        sent_vals=vals,
        sent_g=agg[idx].astype(vals.dtype),
        sent_idx=idx,
        sent_w=_sent_w_at(idx, den, st.sent_w.dtype),
        t=st.t + 1,
    )


def compact_finalize_sent(
    st: CompactState,
    a: jax.Array,
    sent_vals: jax.Array,
    sent_idx: jax.Array,
    sent_dense: jax.Array,
    agg: jax.Array,
    den: jax.Array | None = None,
) -> CompactState:
    """Codec-aware finalize: error feedback against what was *actually*
    transmitted. ``sent_dense`` is the decoded wire contribution, so
    ``eps' = a - sent_dense`` keeps any codec loss (e.g. ``coo_q8``
    quantization residual) in the accumulator; ``sent_vals``/``sent_idx``
    are the decoded payload — what the server saw — which is what RegTop-k's
    posterior distortion must condition on next round. Identical to
    :func:`compact_finalize` for lossless codecs."""
    return CompactState(
        eps=(a - sent_dense.astype(a.dtype)),
        sent_vals=sent_vals.astype(st.sent_vals.dtype),
        sent_g=agg[sent_idx].astype(st.sent_g.dtype),
        sent_idx=sent_idx,
        sent_w=_sent_w_at(sent_idx, den, st.sent_w.dtype),
        t=st.t + 1,
    )


# ---------------------------------------------------------------------------
# dense-state equivalence oracle (used by tests)
# ---------------------------------------------------------------------------
def reference_step(
    cfg: SparsifierConfig,
    st: CompactState,
    g: jax.Array,
    g_prev_dense: jax.Array,
    k: int,
    omega_prev: jax.Array | None = None,
):
    """Reconstruct the dense-state step for equivalence testing.

    ``omega_prev`` is the dense ``[L]`` sender mass under coordinate
    weighting (what the compact path records at the sent coords as
    ``sent_w``); None is the scalar worker-weighting oracle."""
    from repro.core.sparsify import SparsifierState, make_sparsifier

    L = g.shape[0]
    s_prev = jnp.zeros((L,)).at[st.sent_idx].set(
        jnp.where(st.t > 0, 1.0, 0.0)
    )
    a_prev = jnp.zeros((L,)).at[st.sent_idx].set(st.sent_vals)
    # test oracle: rebuilding the dense state from the compact layout is
    # the point of this function.
    dense = SparsifierState(  # reprolint: disable=RPL106
        eps=st.eps, a_prev=a_prev, s_prev=s_prev, t=st.t
    )
    sp = make_sparsifier(dataclasses.replace(cfg, sparsity=k / L, selector="exact"))
    return sp.step(dense, g, g_prev_dense, omega_prev=omega_prev)
