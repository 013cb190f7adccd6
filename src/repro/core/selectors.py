"""Top-k selection primitives.

All selectors operate on a 1-D non-negative ``score`` vector and return a
``{0,1}`` float mask (and optionally the selected values/indices as static
fixed-``k`` payloads, as required for TPU/XLA static shapes).

Two families:

* ``exact``      — ``jax.lax.top_k`` on the score (sort-bound, reference).
* ``threshold``  — iterative bisection for a threshold ``tau`` such that
  ``count(score >= tau) ~= k``; streaming / VPU-friendly, and the primitive
  that :mod:`repro.kernels.threshold_topk` implements as a Pallas kernel.
  The mask cardinality is approximately ``k`` (exactly ``k`` when there are
  no ties at ``tau`` and the bisection fully converges); callers that need a
  fixed-size payload combine it with :func:`fixed_k_payload`.
"""
from __future__ import annotations

import math
from typing import Tuple

import jax
import jax.numpy as jnp


def exact_topk_mask(score: jax.Array, k: int) -> jax.Array:
    """Exact top-k mask via ``lax.top_k`` (ties broken by index order).

    A zero score carries no gradient and is never selected (the same
    contract the PR-2 fix gave :func:`threshold_topk_mask`), so the mask
    cardinality is ``min(k, #nonzero scores)`` — fewer than ``k`` only
    when the score vector itself has fewer than ``k`` live entries.

    >>> import jax.numpy as jnp
    >>> exact_topk_mask(jnp.array([0.1, 3.0, 0.2, 2.0]), 2).tolist()
    [0.0, 1.0, 0.0, 1.0]
    >>> exact_topk_mask(jnp.array([0.0, 3.0, 0.0, 0.0]), 2).tolist()
    [0.0, 1.0, 0.0, 0.0]
    """
    if score.ndim != 1:
        raise ValueError(f"score must be 1-D, got {score.shape}")
    k = int(k)
    if k <= 0:
        return jnp.zeros_like(score)
    if k >= score.shape[0]:
        return (score > 0).astype(score.dtype)
    _, idx = jax.lax.top_k(score, k)
    mask = jnp.zeros_like(score).at[idx].set(1.0)
    return mask * (score > 0)


def exact_topk_mask_dynamic(
    score: jax.Array, k: jax.Array, capacity: int
) -> jax.Array:
    """Exact top-k mask with a *traced* k under a static ``capacity``.

    The adaptive controller varies k per round inside one compiled step;
    XLA needs static shapes, so selection runs ``lax.top_k`` at the static
    upper bound ``capacity`` (the controller's ``k_max``) and the mask
    keeps only the first ``k`` (dynamic, ``k <= capacity``) of the
    descending-sorted winners. At ``k == capacity`` this is bit-for-bit
    :func:`exact_topk_mask` (same ``lax.top_k``, same zero-score
    exclusion) — the off-switch equivalence the differential tests pin.

    >>> import jax.numpy as jnp
    >>> s = jnp.array([0.1, 3.0, 0.2, 2.0])
    >>> exact_topk_mask_dynamic(s, jnp.asarray(1), 3).tolist()
    [0.0, 1.0, 0.0, 0.0]
    >>> exact_topk_mask_dynamic(s, jnp.asarray(3), 3).tolist()
    [0.0, 1.0, 1.0, 1.0]
    """
    if score.ndim != 1:
        raise ValueError(f"score must be 1-D, got {score.shape}")
    capacity = int(min(capacity, score.shape[0]))
    if capacity <= 0:
        return jnp.zeros_like(score)
    vals, idx = jax.lax.top_k(score, capacity)
    keep = (jnp.arange(capacity) < k) & (vals > 0)
    return jnp.zeros_like(score).at[idx].set(keep.astype(score.dtype))


def threshold_topk_mask(
    score: jax.Array, k: int, *, n_iters: int = 24
) -> jax.Array:
    """Approximate top-k mask via bisection on the selection threshold.

    Finds ``tau`` in ``[0, max(score)]`` such that ``sum(score >= tau)`` is
    the smallest count ``>= k``, using ``n_iters`` halvings. Cost is
    ``O(n_iters * J)`` elementwise work with no sort — the pattern the
    Pallas ``threshold_topk`` kernel accelerates with one histogram pass.

    >>> import jax.numpy as jnp
    >>> threshold_topk_mask(jnp.array([0.1, 3.0, 0.2, 2.0]), 2).tolist()
    [0.0, 1.0, 0.0, 1.0]
    """
    if score.ndim != 1:
        raise ValueError(f"score must be 1-D, got {score.shape}")
    k = int(k)
    if k <= 0:
        return jnp.zeros_like(score)
    if k >= score.shape[0]:
        return (score > 0).astype(score.dtype)

    hi0 = jnp.max(score)
    lo0 = jnp.zeros_like(hi0)

    def body(_, carry):
        lo, hi = carry
        mid = 0.5 * (lo + hi)
        count = jnp.sum(score >= mid)
        # keep the invariant count(lo) >= k
        lo, hi = jnp.where(count >= k, mid, lo), jnp.where(count >= k, hi, mid)
        return lo, hi

    lo, _ = jax.lax.fori_loop(0, n_iters, body, (lo0, hi0))
    # count(score >= lo) >= k; possibly > k on ties / unconverged bisection.
    # When the bisection collapses to tau = 0 (all-zero score, or fewer than
    # k positive entries) ``score >= 0`` would select *everything*; a zero
    # score carries no gradient, so exclude it — the mask cardinality stays
    # <= max(k, ties at tau) instead of blowing up to L.
    return ((score >= lo) & (score > 0)).astype(score.dtype)


def fixed_k_payload(
    score: jax.Array, values: jax.Array, k: int
) -> Tuple[jax.Array, jax.Array]:
    """Return the fixed-size sparse payload ``(vals[k], idx[k])``.

    Selection is by ``score``; the payload carries ``values`` (which in
    RegTop-k differ from the score: the *accumulated gradient* is sent, the
    regularized score only ranks). Static ``k`` → static shapes for
    ``all_gather`` over the data-parallel axes.

    >>> import jax.numpy as jnp
    >>> score = jnp.array([0.1, 3.0, 0.2, 2.0])
    >>> vals, idx = fixed_k_payload(score, jnp.array([9., 8., 7., 6.]), 2)
    >>> vals.tolist(), idx.tolist()
    ([8.0, 6.0], [1, 3])
    """
    if score.ndim != 1:
        raise ValueError(f"score must be 1-D, got {score.shape}")
    k = int(k)
    _, idx = jax.lax.top_k(score, k)
    return values[idx], idx


def mask_to_payload(
    mask: jax.Array, values: jax.Array, k: int
) -> Tuple[jax.Array, jax.Array]:
    """Convert a ~k-cardinality mask into an exactly-k payload.

    Ranks masked entries by |value| (unmasked entries rank -inf); if the
    mask has fewer than ``k`` entries the payload is padded with (0, 0)
    pairs, which are no-ops under scatter-add aggregation.

    >>> import jax.numpy as jnp
    >>> mask = jnp.array([0.0, 1.0, 0.0, 0.0])
    >>> vals, idx = mask_to_payload(mask, jnp.array([9., -8., 7., 6.]), 2)
    >>> vals.tolist(), idx.tolist()  # second slot is (0, 0) padding
    ([-8.0, 0.0], [1, 0])
    """
    ranked = jnp.where(mask > 0, jnp.abs(values), -jnp.inf)
    _, idx = jax.lax.top_k(ranked, int(k))
    vals = values[idx] * (mask[idx] > 0)
    idx = jnp.where(mask[idx] > 0, idx, 0)
    return vals, idx


SELECTORS = {
    "exact": exact_topk_mask,
    "threshold": threshold_topk_mask,
}


def get_selector(name: str):
    """Look up a selector family by name.

    >>> get_selector("exact") is exact_topk_mask
    True
    """
    try:
        return SELECTORS[name]
    except KeyError:
        raise ValueError(
            f"unknown selector {name!r}; available: {sorted(SELECTORS)}"
        ) from None


def sparsity_to_k(length: int, sparsity: float) -> int:
    """Paper's S = k/J; returns k = ceil(S * J), clipped to [1, J].

    The ceil is epsilon-tolerant: ``S * J`` is computed in binary floating
    point, so nominally-integer products land a few ulps above the integer
    (``0.07 * 100 == 7.000000000000001``) and a naive ceil inflates k by one
    — inflating the compression ratio the paper defines as S = k/J.

    >>> sparsity_to_k(100, 0.07)
    7
    >>> sparsity_to_k(100, 0.071), sparsity_to_k(10, 0.0)
    (8, 1)
    """
    target = sparsity * length
    eps = 1e-9 * max(1.0, abs(target))
    k = math.ceil(target - eps)
    return max(1, min(length, k))
