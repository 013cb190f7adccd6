"""Fused select→encode fastpath: policy, pricing, and runtime routing.

The Pallas pipeline in ``repro.kernels.fused_encode`` produces the compact
``(idx, val)`` wire payload straight from the score-kernel registers — no
dense score write-back, no dense mask, no dense masked gradient, no
separate ``a[idx]`` gather. This module is everything *around* that
kernel:

* **fusability matrix** — :func:`fusable` and its factors
  (:func:`config_fusable` / :func:`wire_fusable` / :func:`shape_fusable`):
  which (sparsifier x selector x codec x collective x shape) combinations
  the fused pipeline selects exactly as the unfused one does. Everything
  else stays on the unfused path; routing is always a per-leaf decision,
  never a global switch.
* **pricing** — :class:`ThroughputTable`, the measured-throughput table
  behind ``fastpath="auto"``: analytic HBM-traffic defaults
  (:func:`fused_hbm_bytes` / :func:`unfused_hbm_bytes`, the same columns
  ``benchmarks/kernel_bench.py`` reports) with a :meth:`ThroughputTable.measure`
  refit from real kernel timings. ``repro.comm.autotune.choose_leaf``
  prices each candidate (codec x collective) pair's compute stage with it
  and records the per-leaf ``fused`` flag on its :class:`LeafDecision`.
* **runtime routing** — :func:`fused_compact_select`, the drop-in
  replacement for ``repro.core.compact.compact_select`` on fusable
  configs. The kernel's exactness certificate gates a ``lax.cond``
  fallback to the dense path, so on the same inputs the routed payload
  equals the unfused one; the certificate only decides which pipeline
  computed it. Whole training runs can still drift apart by ulps: the
  fused and unfused steps are different programs (docs/comm.md below).

``DistConfig.fastpath`` / ``DistributedSim(fastpath=...)`` / the train
CLI's ``--fastpath`` accept ``"off"`` (default, historical path),
``"on"`` (fuse every fusable leaf), and ``"auto"`` (fuse where the table
says the fused pipeline is faster; resolves to "off" off-TPU, where the
kernels run in interpret mode). See ``docs/comm.md#the-fused-fastpath``.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.comm.codec import Codec, get_codec

FASTPATH_MODES = ("off", "on", "auto")

# tanh(x) == 1.0 exactly in float32 for x >= ~8.7; with margin. Below this,
# the unsent-coordinate regularizer C = tanh((1 + Q)/mu) is < 1 and the
# fused score (which applies C explicitly) diverges from the unfused
# compact path (which leaves unsent scores untouched).
SATURATION_MIN = 12.0

# per-tile candidate budget bounds: the kernel unrolls m masked-max rounds,
# so m is capped; the floor keeps the certificate hit-rate high on tiny k.
MIN_M = 8
MAX_M = 128

_TILE = 8192  # repro.kernels layout contract: (8, 1024) f32 tiles


def _n_tiles(length: int) -> int:
    return max(1, -(-int(length) // _TILE))


def candidate_budget(length: int, k: int) -> int:
    """Per-tile candidate count ``m`` for a leaf: ~2.5x the expected
    per-tile winner count ``k / n_tiles`` plus slack, clamped to
    [MIN_M, MAX_M]. Oversampling keeps the exactness certificate's
    fast-path hit rate high on uniform-ish score mass.

    >>> candidate_budget(8192, 8)
    28
    >>> candidate_budget(10**6, 10)
    9
    """
    per_tile = k / _n_tiles(length)
    return max(MIN_M, min(MAX_M, math.ceil(2.5 * per_tile) + 8))


def config_fusable(scfg) -> Tuple[bool, str]:
    """Does this ``SparsifierConfig`` admit the fused pipeline?

    * kind must be ``topk``/``regtopk`` — the only kinds whose score the
      kernel computes (cyclic/coordtopk/dgc score from other state).
    * selector must be ``exact`` — the fused compaction reproduces
      ``lax.top_k`` ordering; the ``threshold`` selector's
      ``mask_to_payload`` ranks the payload by |value| instead.
    * ``y > 0`` — keeps the score chain well defined on zero magnitudes.
    * both kinds need ``tanh((1 + Q)/mu) == 1.0`` in f32
      (:data:`SATURATION_MIN`): the unfused path never scales unsent
      (topk: any) scores, the kernel multiplies them by that constant —
      and a constant *below* 1.0 can collapse 1-ulp-separated magnitudes
      into f32 ties, silently reordering the selection.

    Bit-for-bit subtlety the routing (not this predicate) handles: where
    the unfused path scores plain ``|a|`` (all of topk; regtopk's t == 0
    round) the kernel must not apply ``y != 1`` either — ``x^y`` is
    order-*preserving* but not tie-*preserving* in floats, so
    :func:`fused_compact_select` scores topk with ``y = 1`` and forces
    the dense fallback on regtopk's round 0 when ``y != 1``.
    """
    if scfg.kind not in ("topk", "regtopk"):
        return False, f"kind {scfg.kind!r} is not fusable"
    if scfg.selector != "exact":
        return False, f"selector {scfg.selector!r} is not fusable"
    if not scfg.y > 0:
        return False, f"y={scfg.y} breaks the score chain"
    if (1.0 + scfg.q_const) / scfg.mu < SATURATION_MIN:
        return False, (
            f"tanh((1+{scfg.q_const:g})/{scfg.mu:g}) does not saturate "
            "to 1.0 — scores would diverge from the unfused path"
        )
    return True, "ok"


def wire_fusable(codec, collective: str) -> Tuple[bool, str]:
    """Does this (codec, collective) pair consume the fused payload?

    * the codec must implement :meth:`Codec.encode_fused` — an epilogue
      over the k selected registers. ``bitmap_dense`` cannot: its wire
      format *is* a dense presence bitmap, the exact intermediate the
      fastpath never materializes.
    * the collective must move payloads; ``dense_allreduce`` scatters the
      dense vector regardless, so there is nothing to fuse into.

    >>> wire_fusable("coo_fp32", "sparse_allgather")[0]
    True
    >>> wire_fusable("bitmap_dense", "sparse_allgather")[0]
    False
    >>> wire_fusable("coo_q8", "dense_allreduce")[0]
    False
    """
    c = codec if isinstance(codec, Codec) else get_codec(codec)
    if not c.supports_fused:
        return False, f"codec {c.name!r} has no encode_fused epilogue"
    if collective == "dense_allreduce":
        return False, "dense_allreduce moves the dense vector, not payloads"
    return True, "ok"


def shape_fusable(length: int, k: int) -> Tuple[bool, str]:
    """Does the leaf shape fit the candidate budget? ``k`` must fit in
    ``n_tiles * m`` candidates with ``m <= MAX_M`` — at S = k/L beyond
    ~1.5% the per-tile budget overflows and selection stays unfused.

    >>> shape_fusable(65536, 64)[0]
    True
    >>> shape_fusable(8192, 1024)[0]
    False
    """
    m = candidate_budget(length, k)
    if k > _n_tiles(length) * m:
        return False, (
            f"k={k} exceeds the {_n_tiles(length)}x{m} candidate budget"
        )
    return True, "ok"


def fusable(
    scfg, codec, collective: str, length: int, k: int
) -> Tuple[bool, str]:
    """Full fusability matrix: config x wire x shape (see the factor
    functions for the individual rules)."""
    for ok, why in (
        config_fusable(scfg),
        wire_fusable(codec, collective),
        shape_fusable(length, k),
    ):
        if not ok:
            return False, why
    return True, "ok"


def backend_supports() -> bool:
    """Whether ``fastpath="auto"`` may fuse at all: off-TPU the Pallas
    kernels run in interpret mode, which is never faster than XLA's
    unfused path — "auto" resolves to "off" there ("on" still forces the
    fused path, e.g. for tests and parity benchmarks)."""
    return jax.default_backend() == "tpu"


# ---------------------------------------------------------------------------
# pricing: analytic HBM traffic + the measured-throughput table
# ---------------------------------------------------------------------------
def fused_hbm_bytes(length: int, k: int, m: Optional[int] = None) -> int:
    """Analytic HBM traffic of the fused pipeline: 4 J-sized f32 reads over
    the *padded* tiles plus the candidate triples and the k-payload write.
    The padding term is why tiny leaves price *worse* fused — one 8192
    tile dwarfs a 100-element leaf — and "auto" correctly leaves them
    unfused.

    >>> fused_hbm_bytes(65536, 64) < unfused_hbm_bytes(65536, 64)
    True
    >>> fused_hbm_bytes(100, 4) > unfused_hbm_bytes(100, 4)
    True
    """
    tiles = _n_tiles(length)
    m = candidate_budget(length, k) if m is None else m
    return 16 * tiles * _TILE + 12 * tiles * m + 8 * k


def unfused_hbm_bytes(length: int, k: int) -> int:
    """Analytic HBM traffic of the unfused chain: the score kernel's
    4 reads + 1 dense write, the selector's dense re-read, and the
    payload gather — 24 bytes/element + 8 bytes/coordinate."""
    return 24 * length + 8 * k


@dataclasses.dataclass(frozen=True)
class ThroughputTable:
    """Measured-throughput table pricing the select→encode compute stage.

    ``seconds(length, k, fused)`` divides the analytic HBM traffic by the
    per-path effective throughput. Defaults assume both paths stream at
    the same HBM rate (the kernel_bench roofline constant), under which
    the fused pipeline wins wherever its traffic is lower; refit from
    real kernel timings with :meth:`measure` — on CPU interpret mode that
    measurement correctly prices the fused path *slower* and "auto"
    declines it.
    """

    fused_bps: float = 819e9
    unfused_bps: float = 819e9

    def seconds(self, length: int, k: int, fused: bool) -> float:
        if fused:
            return fused_hbm_bytes(length, k) / self.fused_bps
        return unfused_hbm_bytes(length, k) / self.unfused_bps

    def prefers_fused(self, length: int, k: int) -> bool:
        return self.seconds(length, k, True) < self.seconds(length, k, False)

    @classmethod
    def measure(
        cls, length: int = 1 << 16, k: int = 64, iters: int = 3,
        interpret: Optional[bool] = None,
    ) -> "ThroughputTable":
        """Fit effective per-path throughput from real timings of the
        fused pipeline vs the unfused score→top_k→gather chain on a
        representative leaf."""
        import time

        from repro.kernels import ops

        key = jax.random.PRNGKey(0)
        ks = jax.random.split(key, 4)
        a, a_prev, g_prev = (
            3.0 * jax.random.normal(kk, (length,)) for kk in ks[:3]
        )
        s_prev = (jax.random.uniform(ks[3], (length,)) > 0.5).astype(
            jnp.float32
        )

        def fused_fn(x):
            return ops.fused_select_encode(
                x, a_prev, s_prev, g_prev, k=k, omega=0.05, mu=1.0,
                interpret=interpret,
            )

        @jax.jit
        def unfused_fn(x):
            from repro.kernels import ref

            return ref.fused_select_encode_ref(
                x, a_prev, s_prev, g_prev, k, omega=0.05, mu=1.0
            )

        def med_seconds(fn):
            jax.block_until_ready(fn(a))
            ts = []
            for _ in range(iters):
                t0 = time.perf_counter()
                jax.block_until_ready(fn(a))
                ts.append(time.perf_counter() - t0)
            ts.sort()
            return max(ts[len(ts) // 2], 1e-9)

        return cls(
            fused_bps=fused_hbm_bytes(length, k) / med_seconds(fused_fn),
            unfused_bps=unfused_hbm_bytes(length, k) / med_seconds(unfused_fn),
        )


def leaf_fused(
    mode: str,
    codec,
    collective: str,
    length: int,
    k: int,
    table: Optional[ThroughputTable] = None,
    scfg=None,
) -> bool:
    """One leaf's fused flag under ``mode``: never for non-fusable wire or
    shape (or config, when ``scfg`` is given); always for ``"on"``;
    table-priced for ``"auto"``.

    >>> leaf_fused("on", "coo_fp32", "sparse_allgather", 65536, 64)
    True
    >>> leaf_fused("auto", "coo_fp32", "sparse_allgather", 100, 4)
    False
    >>> leaf_fused("on", "bitmap_dense", "sparse_allgather", 65536, 64)
    False
    """
    if mode not in FASTPATH_MODES:
        raise ValueError(
            f"unknown fastpath mode {mode!r}; available: {FASTPATH_MODES}"
        )
    if mode == "off":
        return False
    if scfg is not None and not config_fusable(scfg)[0]:
        return False
    if not (wire_fusable(codec, collective)[0] and shape_fusable(length, k)[0]):
        return False
    if mode == "on":
        return True
    return (table or ThroughputTable()).prefers_fused(length, k)


# ---------------------------------------------------------------------------
# runtime routing
# ---------------------------------------------------------------------------
def fused_compact_select(scfg, st, g, k: int, *, interpret=None):
    """Fused replacement for ``compact.compact_select`` on fusable configs.

    Returns ``(a, vals [k], idx [k], fell_back)``: the same triple as the
    dense path plus a bool scalar saying whether the exactness certificate
    failed and the dense path computed the payload. The compact posterior
    statistics are scattered to the dense layout the kernel reads (state
    inputs, not the mask/masked-gradient intermediates the fusion
    eliminates), the pipeline emits the payload from score registers, and
    the certificate ``lax.cond``s to the dense path whenever the candidate
    budget cannot prove the selection exact. Callers must have checked
    :func:`config_fusable` and :func:`shape_fusable`."""
    from repro.core import compact as C
    from repro.kernels import ops

    a = st.eps + g.astype(st.eps.dtype)
    L = a.shape[0]
    zeros = jnp.zeros((L,), a.dtype)
    y = scfg.y
    if scfg.kind == "regtopk":
        # t == 0 scatters an all-zero s_prev: every coordinate takes the
        # unsent branch and the score degrades to |a|^y — matching the
        # unfused plain-Top-k round 0 only when y == 1 (x^y preserves
        # order but can collapse 1-ulp-separated magnitudes into f32
        # ties); y != 1 forces the dense fallback on round 0 below.
        live = jnp.where(st.t > 0, 1.0, 0.0).astype(a.dtype)
        s_prev = zeros.at[st.sent_idx].set(live)
        a_prev = zeros.at[st.sent_idx].set(st.sent_vals)
        g_prev = zeros.at[st.sent_idx].set(st.sent_g)
    else:  # topk scores plain |a| whatever cfg.y says — so must we:
        # with s_prev all-zero and a saturated regularizer the kernel
        # score is exactly |a| * 1.0
        s_prev = a_prev = g_prev = zeros
        y = 1.0
    vals, idx, ok = ops.fused_select_encode(
        a, a_prev, s_prev, g_prev,
        k=k, omega=scfg.omega, mu=scfg.mu, q=scfg.q_const, y=y,
        m=candidate_budget(L, k), interpret=interpret,
    )
    if scfg.kind == "regtopk" and y != 1.0:
        ok = ok & (st.t > 0)
    vals = vals.astype(a.dtype)

    def _dense(_):
        _a, v, i = C.compact_select(scfg, st, g, k)
        return v.astype(a.dtype), i

    vals, idx = jax.lax.cond(ok, lambda _: (vals, idx), _dense, None)
    return a, vals, idx, jnp.logical_not(ok)


def make_score_fn(interpret: Optional[bool] = None):
    """``SparsifierConfig.score_fn`` adapter: the fused Pallas score
    kernel in the dense-state simulator. The simulator's vmapped,
    dense-state step only fuses the *scoring* stage (4 reads + 1 write
    instead of ~9 streams); the full select→encode fusion needs the
    compact state layout and lives in the shard_map runtime."""
    from repro.kernels import ops

    def score_fn(a, a_prev, s_prev, g_prev, cfg):
        return ops.regtopk_score(
            a, a_prev, s_prev, g_prev,
            omega=cfg.omega, mu=cfg.mu, q=cfg.q_const, y=cfg.y,
            interpret=interpret,
        )

    return score_fn
