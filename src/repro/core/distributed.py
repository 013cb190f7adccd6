"""Distributed training runtime: sparsified data-parallel x tensor-parallel.

The paper's communication pattern, mapped to a TPU mesh (DESIGN.md §2):

  1. per-worker local gradients — ``jax.vmap(value_and_grad)`` over a
     ``[W, ...]`` batch with params broadcast; the leading worker axis is
     sharded over the data-parallel mesh axes so each device holds exactly
     its own worker's (model-sharded) gradient. Optional microbatch
     accumulation (``lax.scan``) bounds activation memory.
  2. sparsify + aggregate — a fully-manual ``jax.shard_map`` over the whole
     mesh: each (worker, model-shard) runs the compact sparsifier on its
     flat local gradient shard, then the workers aggregate over the dp
     axes via either
       * ``dense_allreduce``  — psum of the sparse-but-dense vector
         (numerics-exact simulation / uncompressed baseline), or
       * ``sparse_allgather`` — all_gather of the fixed-k (value, index)
         payloads + local scatter-add: 2·N·k words instead of N·J on the
         wire — the paper's compression, with XLA-static shapes.
  3. optimizer update — pjit-auto, params/optimizer state sharded by the
     logical rules.

Per-(leaf x model-shard) top-k budgets (k = ceil(S * local_len)) follow
DGC/ScaleCom layer-wise practice; see DESIGN.md §Assumption-changes.

Wire formats and collectives are chosen *per leaf*: ``LeafPlan`` carries an
optional (codec, collective) pair, filled by the alpha–beta planner
(:mod:`repro.comm.autotune`) when ``DistConfig.codec`` / ``.collective`` is
``"auto"``, and falling back to the global ``DistConfig`` choice otherwise.
"""
from __future__ import annotations

import contextlib
import dataclasses
from typing import Any, Callable, Dict, List, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro import comm
from repro.core import compact as C
from repro.core import stages
from repro.core.selectors import sparsity_to_k
from repro.core.sparsify import SparsifierConfig
from repro.models.config import ModelConfig
from repro.nn import sharding as shlib
from repro.optim import OptConfig, make_optimizer

_DT = {"float32": jnp.float32, "bfloat16": jnp.bfloat16}


@dataclasses.dataclass(frozen=True)
class DistConfig:
    sparsifier: SparsifierConfig = SparsifierConfig(
        kind="regtopk", sparsity=0.001
    )
    optimizer: OptConfig = OptConfig(kind="adam", learning_rate=1e-4)
    aggregation: str = "sparse_allgather"  # legacy alias for ``collective``
    codec: str = "coo_fp32"  # repro.comm wire codec, or "auto" (per-leaf)
    collective: Optional[str] = None  # repro.comm strategy, "auto", or None
    microbatches: int = 1
    dp_axes: Tuple[str, ...] = ("data",)
    state_dtype: str = "float32"  # eps dtype ("bfloat16" for the big archs)
    rules: Optional[Dict[str, Optional[str]]] = None
    # alpha-beta link model driving codec/collective="auto" planning; None
    # uses comm.AlphaBeta() defaults (see comm.calibrate to fit one).
    link_model: Optional[comm.AlphaBeta] = None
    # per-dp-axis link topology (one AlphaBeta per axis in dp_axes order,
    # outermost/slowest first) — takes precedence over the scalar
    # link_model. Fit one with comm.calibrate_topo, or parse a CLI spec
    # with comm.parse_link_topo (train.py's --link-topo). A heterogeneous
    # topology is what makes collective="hierarchical" plannable: under a
    # uniform model it never strictly beats min(dense, allgather).
    link_topo: Optional[comm.LinkTopo] = None
    # partial-participation round schedule over the flat dp worker group
    # (comm.Participation; train.py's --participation). None (or a "full"
    # schedule) is the historical all-workers path, bit-for-bit. Dropping
    # schedules (bernoulli / round_robin) run in this shard_map runtime;
    # bounded-staleness ("stale") delivery needs the server-side pending
    # buffer and is simulator-only for now (DistributedSim).
    participation: Optional[comm.Participation] = None
    # fused select→encode fastpath (repro.comm.fastpath; train.py's
    # --fastpath): "off" (default) is the historical dense-selection path;
    # "on" routes every fusable leaf through the Pallas fused pipeline
    # (the same selection — a runtime exactness certificate falls back
    # per call otherwise); "auto" fuses the leaves the measured-throughput
    # table prices faster, and resolves to "off" off-TPU where the kernels
    # run in interpret mode.
    fastpath: str = "off"
    # error-budget-driven per-round k (comm.AdaptiveKController; train.py's
    # --adaptive-k). None is the historical static-k path, bit-for-bit.
    # When set, every leaf's payload capacity is its k_max bound, the
    # controller's per-leaf k rides the round as a dynamic operand (no
    # retrace), and make_sparsify_aggregate threads a per-leaf
    # ControllerState tree alongside the sparsifier state.
    adaptive_k: Optional[comm.AdaptiveKController] = None
    # aggregation weighting axis ("worker" | "coordinate",
    # comm.collectives; train.py's --coord-weights). "coordinate"
    # renormalizes each coordinate by the mass of the workers that
    # actually sent it and records that mass in the compact state
    # (sent_w), which RegTop-k's posterior then conditions on; "worker"
    # is the historical per-worker Eq. (8) reduction, bit-for-bit.
    weighting: str = "worker"
    # bucketed overlap schedule ("off" | "buckets:B", comm.overlap;
    # train.py's --overlap). "buckets:B" splits the leaf tree into B
    # size-balanced launch buckets (greedy bin-pack on predicted per-axis
    # wire seconds) so each bucket's collective launches as soon as its
    # backward slice is done and hierarchical's slow inter-axis stage
    # pipelines behind the next bucket's intra-axis work. Numerics are
    # untouched (bucketing only reorders independent per-leaf rounds):
    # "off" and any B are bit-for-bit identical; what changes is the
    # predicted round timeline (comm_round_timeline) and the profiler
    # annotation structure (a jax.named_scope per bucket around the
    # round's stage scopes).
    overlap: str = "off"

    def resolved_collective(self) -> str:
        return self.collective or self.aggregation

    def resolved_weighting(self) -> str:
        """The effective weighting axis, with the config gates applied:
        kind='none' sends every coordinate (sender mass uniformly 1), so
        coordinate weighting would silently degenerate — reject it."""
        comm.check_weighting(self.weighting)
        if self.weighting == "coordinate" and self.sparsifier.kind == "none":
            raise ValueError(
                "weighting='coordinate' needs sparse payloads; kind='none' "
                "sends every coordinate, so the sender mass is uniformly 1 "
                "and coordinate weighting degenerates to the worker "
                "reduction — use weighting='worker'"
            )
        return self.weighting

    def resolved_fastpath(self) -> str:
        """The effective fastpath mode, with the environment gates applied:
        "auto" needs a TPU backend (interpret mode never wins), and the
        fused kernels score in f32 — a bf16 ``state_dtype`` scores in bf16
        on the unfused path, so fusing would not be bit-for-bit ("on"
        raises; "auto" declines)."""
        if self.fastpath not in comm.FASTPATH_MODES:
            raise ValueError(
                f"unknown fastpath {self.fastpath!r}; "
                f"available: {comm.FASTPATH_MODES}"
            )
        if self.fastpath == "off":
            return "off"
        if self.weighting == "coordinate" and self.sparsifier.kind == "regtopk":
            # the fused kernel scores with a *scalar* omega baked into the
            # pipeline; coordinate weighting scores with omega / sent_w.
            if self.fastpath == "on":
                raise ValueError(
                    "fastpath='on' cannot fuse regtopk under "
                    "weighting='coordinate': the fused score kernel bakes "
                    "a scalar omega, but coordinate weighting conditions "
                    "on the per-coordinate sender mass (sent_w) — use "
                    "fastpath='off'/'auto'"
                )
            return "off"
        if self.state_dtype != "float32":
            if self.fastpath == "on":
                raise ValueError(
                    "fastpath='on' requires state_dtype='float32': the "
                    "fused pipeline scores in f32 while the unfused path "
                    f"scores in {self.state_dtype} — selection would not "
                    "be bit-for-bit"
                )
            return "off"
        if self.fastpath == "auto" and not comm.fastpath.backend_supports():
            return "off"
        return self.fastpath

    def resolved_participation(self) -> Optional[comm.Participation]:
        """The active (non-full) schedule, or None when every round is
        full — callers skip participation logic entirely on None."""
        if self.participation is None or self.participation.is_full:
            return None
        return self.participation

    def resolved_link_model(self) -> comm.LinkModel:
        """The link model auto-planning scores with: the per-axis topology
        when given, else the scalar model, else comm.AlphaBeta() defaults."""
        if self.link_topo is not None:
            return self.link_topo
        return self.link_model or comm.AlphaBeta()

    def resolved_overlap(self) -> Optional[comm.OverlapConfig]:
        """The active bucketed-overlap config, or None when "off" —
        callers skip bucket scheduling entirely on None. The spec is
        validated here (unknown specs / non-positive bucket counts
        raise)."""
        return comm.parse_overlap(self.overlap)

    def resolved_adaptive_k(self) -> Optional[comm.AdaptiveKController]:
        """The active controller, with the config gates applied: adaptive
        k drives the magnitude-scored fixed-k kinds under the exact
        selector — anything else has no dynamic-k selection path."""
        if self.adaptive_k is None:
            return None
        if self.sparsifier.kind not in ("topk", "regtopk"):
            raise ValueError(
                "adaptive_k drives magnitude-scored fixed-k kinds "
                f"('topk'/'regtopk'); got {self.sparsifier.kind!r}"
            )
        if self.sparsifier.selector != "exact":
            raise ValueError(
                "adaptive_k requires selector='exact' (the capacity-"
                f"bounded lax.top_k path); got {self.sparsifier.selector!r}"
            )
        return self.adaptive_k


class LeafPlan(NamedTuple):
    global_shape: Tuple[int, ...]
    local_shape: Tuple[int, ...]
    local_len: int
    k: int
    spec: P
    # per-leaf wire choices; None defers to DistConfig's global setting.
    # build_plan(..., dist=...) fills them when codec/collective is "auto".
    codec: Optional[str] = None
    collective: Optional[str] = None
    # per-leaf fused select→encode flag; None defers to resolving
    # DistConfig.fastpath at aggregation-build time (leaf_fastpath).
    # build_plan(..., dist=...) fills it whenever fastpath != "off".
    fused: Optional[bool] = None


def _is_plan(x):
    return isinstance(x, LeafPlan)


def leaf_wire(p: LeafPlan, dist: DistConfig) -> Tuple[str, str]:
    """Resolve one leaf's (codec, collective): the leaf's own plan entry
    wins; otherwise the global DistConfig choice. "auto" must have been
    resolved at plan-build time (``build_plan(..., dist=...)``)."""
    codec = p.codec or dist.codec
    coll = p.collective or dist.resolved_collective()
    if codec == "auto" or coll == "auto":
        raise ValueError(
            "codec/collective='auto' requires a plan built with "
            "build_plan(..., dist=dist) so per-leaf choices are resolved"
        )
    return codec, coll


def leaf_fastpath(p: LeafPlan, dist: DistConfig) -> bool:
    """Resolve one leaf's fused select→encode flag: the plan's own entry
    wins (filled by ``build_plan(..., dist=...)``); otherwise the flag is
    derived here from ``dist.resolved_fastpath()`` and the fusability
    matrix — so plans built without ``dist`` still honor a fastpath set
    on the config afterwards."""
    mode = dist.resolved_fastpath()
    if mode == "off":
        return False
    if p.fused is not None:
        return p.fused
    if not comm.fastpath.config_fusable(dist.sparsifier)[0]:
        return False
    cname, coll = leaf_wire(p, dist)
    return comm.fastpath.leaf_fused(
        mode, cname, coll, p.local_len, p.k, scfg=dist.sparsifier
    )


def _local_shape(shape, spec: P, mesh) -> Tuple[int, ...]:
    out = []
    for dim, size in enumerate(shape):
        ax = spec[dim] if dim < len(spec) else None
        if ax is None:
            out.append(size)
            continue
        axes = ax if isinstance(ax, tuple) else (ax,)
        div = int(np.prod([mesh.shape[a] for a in axes]))
        out.append(size // div)
    return tuple(out)


def build_plan(params_shape, specs, mesh, sparsity: float,
               dist: Optional[DistConfig] = None):
    """Per-leaf static sparsification plan.

    With ``dist`` given and ``dist.codec`` / ``dist.collective`` set to
    ``"auto"``, each leaf additionally gets a (codec, collective) pair
    picked by the alpha–beta planner (:mod:`repro.comm.autotune`) on the
    leaf's *local* shard length — tiny biases and dense-ish embedding
    shards end up on different wire formats. Fixed (non-"auto") choices
    leave the leaf fields ``None`` (global resolution via ``leaf_wire``).

    With ``dist.fastpath != "off"`` each leaf also gets its fused
    select→encode flag: under "auto" planning the planner prices the
    compute stage per candidate pair; under fixed wire choices the flag
    is the fusability matrix (+ throughput table for mode "auto") applied
    to the global (codec, collective).
    """
    from repro.comm import autotune, fastpath as fp_lib

    auto = dist is not None and (
        dist.codec == "auto" or (dist.collective or "") == "auto"
    )
    fp_mode = "off" if dist is None else dist.resolved_fastpath()
    if fp_mode != "off" and not fp_lib.config_fusable(dist.sparsifier)[0]:
        fp_mode = "off"
    if auto:
        dp_sizes = [mesh.shape[a] for a in dist.dp_axes]
        model = dist.resolved_link_model()
        word_bytes = jnp.dtype(_DT[dist.state_dtype]).itemsize
        participants = _dist_participants(dist, mesh)
        codecs = None if dist.codec == "auto" else [dist.codec]
        if dist.sparsifier.kind in ("none", "hard_threshold"):
            # no fixed-k payload exists: a *free* collective axis can only
            # resolve to the dense wire. An explicitly requested payload
            # collective is kept — downstream guards own that error.
            collectives = (
                ["dense_allreduce"] if dist.collective == "auto"
                else [dist.resolved_collective()]
            )
        else:
            collectives = (
                None if dist.collective == "auto"
                else [dist.resolved_collective()]
            )
        # a free codec axis stays lossless (auto must not change numerics);
        # an explicitly-fixed lossy codec is the user's call.
        allow_lossy = dist.codec != "auto"

    ctrl = None if dist is None else dist.resolved_adaptive_k()

    def mk(leaf, spec):
        ls = _local_shape(leaf.shape, spec, mesh)
        ll = int(np.prod(ls)) if ls else 1
        # adaptive leaves allocate (and get planned at) the controller's
        # k_max bound — the static payload capacity the rounds ship.
        k = sparsity_to_k(ll, sparsity) if ctrl is None else ctrl.bounds(ll)[1]
        if not auto:
            fused = None
            if fp_mode != "off":
                fused = fp_lib.leaf_fused(
                    fp_mode, dist.codec, dist.resolved_collective(), ll, k
                )
            return LeafPlan(
                tuple(leaf.shape), ls, ll, k, spec, fused=fused
            )
        d = autotune.choose_leaf(
            ll, k, dp_sizes, model,
            codecs=codecs, collectives=collectives,
            allow_lossy=allow_lossy, word_bytes=word_bytes,
            participants=participants, fastpath=fp_mode,
        )
        return LeafPlan(
            tuple(leaf.shape), ls, ll, k, spec, d.codec, d.collective,
            d.fused,
        )

    return jax.tree.map(mk, params_shape, specs)


def apply_plan_decisions(plan, comm_plan):
    """Graft a :class:`repro.comm.autotune.CommPlan`'s per-leaf (codec,
    collective, fused) decisions onto a ``LeafPlan`` tree — the bridge
    from ``comm.replan`` (measured-sample re-planning at runtime) back to
    the static plan ``make_sparsify_aggregate`` consumes. Capacities
    (``k``) are untouched, so sparsifier/controller state shapes survive
    the swap and training resumes without reinitialization. Accepts the
    ``CommPlan`` itself or its ``decisions`` tree."""
    decisions = getattr(comm_plan, "decisions", comm_plan)
    return jax.tree.map(
        lambda p, d: p._replace(
            codec=d.codec, collective=d.collective, fused=d.fused
        ),
        plan,
        decisions,
        is_leaf=_is_plan,
    )


# ---------------------------------------------------------------------------
# sparsifier state (compact, worker-major)
# ---------------------------------------------------------------------------
def sparsifier_state_shapes(plan, W: int, mesh, dp_axes, dtype):
    """(ShapeDtypeStruct state tree, PartitionSpec tree). Worker axis over
    dp; per-model-shard payload vectors carry an explicit shard axis."""
    M = mesh.shape["model"]
    dp = tuple(dp_axes) if len(dp_axes) > 1 else dp_axes[0]

    def mk_shape(p: LeafPlan):
        return C.CompactState(
            eps=jax.ShapeDtypeStruct((W,) + p.global_shape, dtype),
            sent_vals=jax.ShapeDtypeStruct((W, M, p.k), dtype),
            sent_g=jax.ShapeDtypeStruct((W, M, p.k), dtype),
            sent_idx=jax.ShapeDtypeStruct((W, M, p.k), jnp.int32),
            sent_w=jax.ShapeDtypeStruct((W, M, p.k), dtype),
            t=jax.ShapeDtypeStruct((W,), jnp.int32),
        )

    def mk_spec(p: LeafPlan):
        return C.CompactState(
            eps=P(dp, *tuple(p.spec)),
            sent_vals=P(dp, "model", None),
            sent_g=P(dp, "model", None),
            sent_idx=P(dp, "model", None),
            sent_w=P(dp, "model", None),
            t=P(dp),
        )

    shapes = jax.tree.map(mk_shape, plan, is_leaf=_is_plan)
    specs = jax.tree.map(mk_spec, plan, is_leaf=_is_plan)
    return shapes, specs


def init_sparsifier_state(plan, W: int, mesh, dp_axes, dtype):
    """Zero state, allocated in place under its specs — each worker's
    error accumulator lands on that worker's devices, and the jitted step
    sees the same input sharding on its first call as on every later one
    (no second trace)."""
    shapes, specs = sparsifier_state_shapes(plan, W, mesh, dp_axes, dtype)

    def mk(s, spec):
        return jnp.zeros(s.shape, s.dtype, device=NamedSharding(mesh, spec))

    return jax.tree.map(mk, shapes, specs), specs


# ---------------------------------------------------------------------------
# adaptive-k controller state (per-leaf scalars, replicated)
# ---------------------------------------------------------------------------
def controller_state_specs(plan):
    """PartitionSpec tree for the per-leaf ``ControllerState`` scalars —
    replicated everywhere (each shard derives the identical update from
    psum'd norms, so replication is self-consistent)."""
    return jax.tree.map(
        lambda p: comm.ControllerState(P(), P(), P()), plan, is_leaf=_is_plan
    )


def init_controller_state(plan, dist: DistConfig, mesh):
    """(ControllerState tree mirroring ``plan``, PartitionSpec tree).

    Each leaf starts at the static-sparsity k clipped into the
    controller's per-leaf bounds; the plan must have been built with
    ``dist`` so leaf capacities already sit at ``k_max``. The scalars are
    replicated over ``mesh`` up front, as the round returns them, so the
    jitted round does not trace again on its second call."""
    ctrl = dist.resolved_adaptive_k()
    if ctrl is None:
        raise ValueError("init_controller_state needs dist.adaptive_k")

    def mk(p: LeafPlan):
        lo, hi = ctrl.bounds(p.local_len)
        return ctrl.init(
            sparsity_to_k(p.local_len, dist.sparsifier.sparsity), lo, hi
        )

    return (
        jax.device_put(
            jax.tree.map(mk, plan, is_leaf=_is_plan),
            NamedSharding(mesh, P()),
        ),
        controller_state_specs(plan),
    )


def _ctrl_update(ctrl_cfg, ctrl_leaf, new_st, agg, p: LeafPlan, dp_axes,
                 model_axes, lo: int, hi: int):
    """Fold one leaf's round into its controller state (inside shard_map).

    Norms are assembled from the local shards: sum-of-squares psum'd over
    the non-dp (model) axes, then the per-worker eps norms pmean'd over
    dp. A leaf *replicated* over a model axis double-counts by the axis
    size — identically for eps and g_agg, so the ratio the budget
    regulates is unaffected."""
    eps = new_st.eps[0].reshape(p.local_len).astype(jnp.float32)
    eps_sq = jnp.sum(eps * eps)
    ag = agg.reshape(p.local_len).astype(jnp.float32)
    g_sq = jnp.sum(ag * ag)
    if model_axes:
        eps_sq = jax.lax.psum(eps_sq, model_axes)
        g_sq = jax.lax.psum(g_sq, model_axes)
    eps_norm = jax.lax.pmean(jnp.sqrt(eps_sq), dp_axes)
    return ctrl_cfg.observe(
        ctrl_leaf, eps_norm, jnp.sqrt(g_sq), k_min=lo, k_max=hi
    )


# ---------------------------------------------------------------------------
# the sparsify+aggregate shard_map stage
# ---------------------------------------------------------------------------
def _spa_leaf(g, st, p: LeafPlan, scfg, codec, collective, dp_axes,
              part_ctx=None, fused=False, k_dyn=None, weighting="worker"):
    """Local (worker x model-shard) view: g [1, *local], st with leading
    [1(,1)] axes. Returns (agg local shard [*local], new state, fell_back)
    where ``fell_back`` is 1.0 when a fused leaf's exactness certificate
    failed this round and the dense path picked the payload, else 0.0.

    All aggregation routes through :mod:`repro.comm`: the ``dense_allreduce``
    strategy psums the sparse-but-dense vector (uncompressed, exact); payload
    strategies encode the fixed-k payload with ``codec``, run the collective,
    and error-feed back against the *decoded* contribution so lossy codecs
    (``coo_q8``) keep their residual in ``eps``. Each stage is traced under
    its scope of :mod:`repro.core.stages`: ``spa.score`` and ``spa.select``
    (inside ``compact_select``; a fused leaf is all ``spa.select``),
    ``spa.encode``, ``spa.exchange`` and ``spa.feedback``.

    ``fused`` routes selection through the Pallas fused select→encode
    pipeline (``comm.fastpath.fused_compact_select`` +
    ``codec.encode_fused`` — no dense score/mask/masked-gradient
    intermediates) — callers only set it on leaves the fusability matrix
    admits (see ``leaf_fastpath``).

    ``part_ctx`` (``(m, w_part)``, computed once per round by
    ``make_sparsify_aggregate`` from the shared schedule) makes the round
    partial: ``m`` is this worker's ``{0,1}`` mask entry and ``w_part``
    the renormalized participant weight ``1/|P_t|``. Participants
    aggregate with ``w_part``; a dropped worker keeps its whole
    accumulated gradient in ``eps`` with its posterior statistics
    (``sent_*``) frozen at the last round the server actually saw it —
    error feedback covers non-participation exactly like sparsification.
    ``part_ctx=None`` is the historical full round, bit-for-bit.

    ``k_dyn`` (traced int, adaptive-k rounds only) caps the effective
    payload cardinality below the static capacity ``p.k`` — see
    ``compact_select``; ``None`` is the historical static-k selection.

    ``weighting="coordinate"`` renormalizes each coordinate by the sender
    mass of the workers that actually sent it (``shard_coord`` /
    presence-psum) and records that mass at the sent coords in the state's
    ``sent_w``, which the next round's RegTop-k posterior conditions on;
    ``"worker"`` records 1.0 there and is bit-for-bit the historical path.
    """
    gl = g[0].reshape(p.local_len)
    stl = C.CompactState(
        eps=st.eps[0].reshape(p.local_len),
        sent_vals=st.sent_vals[0, 0],
        sent_g=st.sent_g[0, 0],
        sent_idx=st.sent_idx[0, 0],
        sent_w=st.sent_w[0, 0],
        t=st.t[0],
    )
    if part_ctx is not None:
        m, w_part = part_ctx
    fell_back = jnp.zeros((), jnp.float32)
    if scfg.kind == "none":
        with jax.named_scope(stages.EXCHANGE):
            if part_ctx is None:
                agg = jax.lax.pmean(
                    gl.astype(jnp.float32), dp_axes
                ).astype(gl.dtype)
            else:
                # no error state: a dropped worker's gradient is simply lost
                agg = jax.lax.psum(
                    gl.astype(jnp.float32) * (m * w_part), dp_axes
                ).astype(gl.dtype)
        new = stl._replace(t=stl.t + 1)
    else:
        if fused:
            with jax.named_scope(stages.SELECT):
                a, vals, idx, fb = comm.fastpath.fused_compact_select(
                    scfg, stl, gl, p.k
                )
                fell_back = fb.astype(jnp.float32)
                if k_dyn is not None:
                    a, vals, idx = C.apply_k_dyn(a, vals, idx, k_dyn, p.k)
        else:
            a, vals, idx = C.compact_select(scfg, stl, gl, p.k, k_dyn=k_dyn)
        omega = scfg.omega if part_ctx is None else w_part
        shard_mask = None if part_ctx is None else m
        coord = weighting == "coordinate"
        den = None  # per-coordinate sender mass (coordinate weighting)
        if collective == "dense_allreduce":
            with jax.named_scope(stages.EXCHANGE):
                # scatter-ADD: payload padding (value 0 on a real or duplicate
                # index) must be a no-op, never overwrite a live contribution
                ghat = jnp.zeros_like(a).at[idx].add(vals)
                w = omega if part_ctx is None else omega * m
                if coord:
                    # presence from the dense contribution (mirrors
                    # DenseAllreduce.shard_coord): padding slots carry value 0
                    # and contribute no sender mass.
                    presence = (ghat != 0).astype(ghat.dtype)
                    num = jax.lax.psum(ghat * w, dp_axes)
                    den = jax.lax.psum(presence * w, dp_axes)
                    agg = num / jnp.maximum(den, jnp.finfo(den.dtype).tiny)
                else:
                    agg = jax.lax.psum(ghat * w, dp_axes)
            with jax.named_scope(stages.FEEDBACK):
                new = C.compact_finalize(stl, a, vals, idx, agg, den=den)
        else:
            with jax.named_scope(stages.ENCODE):
                payload = (
                    codec.encode_fused(vals, idx, p.local_len)
                    if fused
                    else codec.encode(vals, idx, p.local_len)
                )
            with jax.named_scope(stages.FEEDBACK):
                dvals, didx = codec.decode(payload, p.local_len)
                sent_dense = (
                    jnp.zeros_like(a).at[didx].add(dvals.astype(a.dtype))
                )
            strategy = comm.get_collective(collective)
            with jax.named_scope(stages.EXCHANGE):
                if coord:
                    agg, den = strategy.shard_coord(
                        codec, payload, p.local_len, dp_axes, omega,
                        participation=shard_mask,
                    )
                    agg = agg.astype(a.dtype)
                    den = den.astype(a.dtype)
                else:
                    agg = strategy.shard(
                        codec, payload, p.local_len, dp_axes, omega,
                        participation=shard_mask,
                    ).astype(a.dtype)
            with jax.named_scope(stages.FEEDBACK):
                new = C.compact_finalize_sent(
                    stl, a, dvals, didx, sent_dense, agg, den=den
                )
        if part_ctx is not None:
            with jax.named_scope(stages.FEEDBACK):
                dropped = C.CompactState(
                    eps=a,
                    sent_vals=stl.sent_vals,
                    sent_g=stl.sent_g,
                    sent_idx=stl.sent_idx,
                    sent_w=stl.sent_w,
                    t=stl.t + 1,
                )
                new = jax.tree.map(
                    lambda live, gone: jnp.where(m > 0, live, gone), new, dropped
                )
    new_out = C.CompactState(
        eps=new.eps.reshape((1,) + p.local_shape),
        sent_vals=new.sent_vals[None, None],
        sent_g=new.sent_g[None, None],
        sent_idx=new.sent_idx[None, None],
        sent_w=new.sent_w[None, None],
        t=new.t[None],
    )
    return agg.reshape(p.local_shape).astype(g.dtype), new_out, fell_back


def make_sparsify_aggregate(
    mesh, plan, param_specs, state_specs, dist: DistConfig, n_workers: int
):
    """The sparsify+aggregate round as one ``shard_map`` over the mesh:
    ``(grads, state[, ctrl]) -> (agg, state[, ctrl], fallbacks)``.
    ``fallbacks`` counts the fused-leaf selections (leaf x device) whose
    exactness certificate failed this round, so the fused path's dense
    fallback is never silent (0 without fused leaves)."""
    dp = tuple(dist.dp_axes)
    dp_spec = dp if len(dp) > 1 else dp[0]
    dp_sizes = tuple(int(mesh.shape[a]) for a in dp)
    part = dist.resolved_participation()
    if part is not None:
        part.validate(n_workers)
        if part.delays_payloads:
            raise ValueError(
                "participation kind 'stale' (bounded-staleness delivery) "
                "needs the server-side pending buffer and is simulator-only "
                "for now — use DistributedSim(participation=...), or a "
                "dropping schedule ('bernoulli'/'round_robin') here"
            )
    # RegTop-k's posterior distortion subtracts this worker's own
    # contribution omega*a_prev from the broadcast; under a partial
    # schedule the server aggregated it with the schedule's effective
    # weight (renormalized 1/|P_t| — exact for fixed-size schedules,
    # expected for bernoulli; 1/S for client sampling), so that is the
    # omega the posterior must condition on. Under coordinate weighting
    # this is the *base* per-worker mass; the per-coordinate divisor
    # rides the state as sent_w.
    omega = (
        1.0 / n_workers
        if part is None
        else part.effective_omega(n_workers)
    )
    scfg = dataclasses.replace(dist.sparsifier, omega=omega)
    weighting = dist.resolved_weighting()
    plan_flat, plan_def = jax.tree.flatten(plan, is_leaf=_is_plan)
    # per-leaf wire choices (one global pair when the plan carries none);
    # resolve + validate every distinct pair up front — fail fast.
    wires = [leaf_wire(p, dist) for p in plan_flat]
    for cname, sname in set(wires):
        comm.get_codec(cname)
        comm.get_collective(sname)
    leaf_codecs = [comm.get_codec(c) for c, _ in wires]
    # per-leaf fused select→encode flags; a fused leaf must actually be
    # fusable end to end (a stale plan flag on a non-fusable wire would
    # call a missing encode_fused deep inside shard_map — fail fast here).
    fused_flags = [leaf_fastpath(p, dist) for p in plan_flat]
    for p, (cname, sname), fval in zip(plan_flat, wires, fused_flags, strict=True):
        if not fval:
            continue
        ok, why = comm.fusable(
            dist.sparsifier, cname, sname, p.local_len, p.k
        )
        if not ok:
            raise ValueError(
                f"plan marks a {p.local_len}-element leaf fused but the "
                f"({cname}, {sname}) pair is not fusable: {why}"
            )

    ctrl_cfg = dist.resolved_adaptive_k()
    if ctrl_cfg is not None:
        model_axes = tuple(a for a in mesh.axis_names if a not in dp)
        leaf_bounds = [ctrl_cfg.bounds(p.local_len) for p in plan_flat]
        for p, (_, hi) in zip(plan_flat, leaf_bounds, strict=True):
            if p.k != hi:
                raise ValueError(
                    f"adaptive-k plan capacity mismatch: a {p.local_len}-"
                    f"element leaf carries k={p.k} but the controller's "
                    f"k_max bound is {hi} — build the plan with "
                    "build_plan(..., dist=dist) so capacities sit at k_max"
                )

    # bucketed overlap: precompute the leaf launch order (and the profiler
    # scope names) at trace time. Off keeps the flat single-group order —
    # the historical program, bit-for-bit; buckets only *reorder* the
    # independent per-leaf rounds and annotate them with jax.named_scope,
    # so the math is identical either way.
    ocfg = dist.resolved_overlap()
    bucket_order: List[Tuple[int, ...]] = [tuple(range(len(plan_flat)))]
    bucket_scopes: List[Optional[str]] = [None]
    if ocfg is not None and plan_flat:
        bplan = comm.bucketize(_leaf_overlap_costs(plan, dist, mesh), ocfg)
        bucket_order = [b.leaves for b in bplan.buckets]
        bucket_scopes = [
            f"spa_bucket{i:03d}" for i in range(len(bucket_order))
        ]

    def rounds(grads, state, ctrl=None):
        g_flat = plan_def.flatten_up_to(grads)
        s_flat = plan_def.flatten_up_to(state)
        part_ctx = None
        if part is not None:
            # one mask per round, shared by every leaf (all leaf round
            # counters advance in lockstep): this worker's mask entry and
            # the common renormalized participant weight 1/|P_t| (the
            # runtime's omega is uniform, so w*m/sum(w*m) reduces to it).
            pmask = part.round_mask(s_flat[0].t[0], n_workers)
            m = pmask[comm.worker_index(dp, dp_sizes)]
            part_ctx = (m, 1.0 / jnp.maximum(pmask.sum(), 1.0))
        c_flat = (
            plan_def.flatten_up_to(ctrl) if ctrl is not None
            else [None] * len(plan_flat)
        )
        outs: List = [None] * len(plan_flat)
        for scope, leaves in zip(bucket_scopes, bucket_order, strict=True):
            ctx = (
                jax.named_scope(scope) if scope
                else contextlib.nullcontext()
            )
            with ctx:
                for i in leaves:
                    c = c_flat[i]
                    outs[i] = _spa_leaf(
                        g_flat[i], s_flat[i], plan_flat[i], scfg,
                        leaf_codecs[i], wires[i][1], dp, part_ctx,
                        fused_flags[i],
                        k_dyn=None if c is None else c.k,
                        weighting=weighting,
                    )
        agg = jax.tree.unflatten(plan_def, [o[0] for o in outs])
        new_state = jax.tree.unflatten(plan_def, [o[1] for o in outs])
        out = (agg, new_state)
        if ctrl is not None:
            out += (jax.tree.unflatten(plan_def, [
                _ctrl_update(
                    ctrl_cfg, c, o[1], o[0], p, dp, model_axes, lo, hi
                )
                for o, c, p, (lo, hi) in zip(
                    outs, c_flat, plan_flat, leaf_bounds, strict=True
                )
            ]),)
        fb = jnp.zeros((), jnp.float32)
        if any(fused_flags):
            fb = jax.lax.psum(sum(o[2] for o in outs), tuple(mesh.axis_names))
        return (*out, fb)

    grads_in_specs = jax.tree.map(lambda s: P(dp_spec, *tuple(s)), param_specs)
    in_specs = (grads_in_specs, state_specs)
    out_specs = (param_specs, state_specs)
    if ctrl_cfg is not None:
        ctrl_specs = controller_state_specs(plan)
        in_specs += (ctrl_specs,)
        out_specs += (ctrl_specs,)
    out_specs += (P(),)
    return jax.shard_map(
        rounds, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
        check_vma=False,
    )


# ---------------------------------------------------------------------------
# communication accounting (repro.comm.cost over the per-leaf plan)
# ---------------------------------------------------------------------------
def _dist_participants(dist: DistConfig, mesh) -> Optional[float]:
    """Expected on-time workers per round under ``dist.participation`` —
    what partial-round cost accounting and auto-planning price with; None
    when every round is full."""
    part = dist.resolved_participation()
    if part is None:
        return None
    W = int(np.prod([mesh.shape[a] for a in dist.dp_axes]))
    return part.validate(W).expected_participants(W)


def _leaf_wire_patterns(plan, dist: DistConfig):
    """Yield ``(leaf, codec, effective_collective, word_bytes, dense_wire)``
    with the word-sizing rules shared by byte and cost accounting: the
    sparsified dense psum carries the state-dtype vector (bf16 halves it),
    the kind="none" pmean upcasts to f32 first (see ``_spa_leaf``), and
    payload strategies decode to f32 before any intra-axis psum
    (hierarchical), so their dense terms stay 4-byte words."""
    dense_word = (
        4
        if dist.sparsifier.kind == "none"
        else jnp.dtype(_DT[dist.state_dtype]).itemsize
    )
    for p in jax.tree.leaves(plan, is_leaf=_is_plan):
        cname, collective = leaf_wire(p, dist)
        dense_wire = dist.sparsifier.kind == "none" or (
            collective == "dense_allreduce"
        )
        yield (
            p,
            comm.get_codec(cname),
            "dense_allreduce" if dense_wire else collective,
            dense_word if dense_wire else comm.cost.WORD_BYTES,
            dense_wire,
        )


def comm_round_bytes(plan, dist: DistConfig, mesh) -> Tuple[int, int]:
    """(predicted, measured) bytes-on-wire per worker per round, summed over
    leaves — each with its *own* (codec, collective) when the plan carries
    per-leaf choices. Predicted comes from the codec's bit accounting;
    measured from the actual encoded buffer shapes (via ``jax.eval_shape``
    — exact, since payload shapes are static).

    Under a partial-participation schedule the *predicted* side prices the
    idealized partial round (only participants' payloads move — what a
    straggler-aware transport would ship), while the *measured* side stays
    the full round: the SPMD runtime still gathers every worker's
    (zero-masked) full-size buffer, so that is what actually crosses the
    wire. ``measured - predicted`` is the transport headroom a
    sparse-membership collective would recover."""
    dp_sizes = [mesh.shape[a] for a in dist.dp_axes]
    participants = _dist_participants(dist, mesh)
    pred = meas = 0
    for p, codec, coll, wb, dense_wire in _leaf_wire_patterns(plan, dist):
        pred += comm.predicted_bytes(
            codec, coll, p.local_len, p.k, dp_sizes, word_bytes=wb,
            participants=participants,
        )
        payload_shape = {} if dense_wire else jax.eval_shape(
            lambda v, i, c=codec, L=p.local_len: c.encode(v, i, L),
            jax.ShapeDtypeStruct((p.k,), jnp.float32),
            jax.ShapeDtypeStruct((p.k,), jnp.int32),
        )
        meas += comm.measured_bytes(
            coll, p.local_len, payload_shape, dp_sizes, word_bytes=wb
        )
    return pred, meas


def comm_round_cost(plan, dist: DistConfig, mesh) -> comm.CostEstimate:
    """Predicted per-worker alpha–beta cost of one full round, summed over
    leaves under ``dist``'s resolved link model — the per-axis
    :class:`~repro.comm.cost.LinkTopo` when configured, so a slow outer
    axis shows up in the round seconds exactly as the planner scored it.
    Word sizing is shared with :func:`comm_round_bytes` via
    ``_leaf_wire_patterns``; a partial-participation schedule prices the
    expected partial round (strictly cheaper than full on any charged
    axis with more than one worker)."""
    dp_sizes = [mesh.shape[a] for a in dist.dp_axes]
    model = dist.resolved_link_model()
    participants = _dist_participants(dist, mesh)
    total_bytes = total_msgs = 0
    total_seconds = 0.0
    for p, codec, coll, wb, _ in _leaf_wire_patterns(plan, dist):
        est = comm.predict(
            codec, coll, p.local_len, p.k, dp_sizes, model, word_bytes=wb,
            participants=participants,
        )
        total_bytes += est.bytes_on_wire
        total_msgs += est.n_messages
        total_seconds += est.seconds
    return comm.CostEstimate(
        bytes_on_wire=total_bytes,
        n_messages=total_msgs,
        seconds=total_seconds,
    )


def _leaf_overlap_costs(plan, dist: DistConfig, mesh):
    """Per-leaf :class:`repro.comm.LeafCost` rows (bytes + per-axis stage
    seconds) in flat plan order, under ``dist``'s resolved link model —
    the :func:`repro.comm.bucketize` input. Word sizing and collective
    resolution are shared with byte/cost accounting via
    ``_leaf_wire_patterns``, so the bucket schedule prices exactly the
    wire the round runs."""
    dp_sizes = [mesh.shape[a] for a in dist.dp_axes]
    model = dist.resolved_link_model()
    participants = _dist_participants(dist, mesh)
    return [
        comm.leaf_cost(
            codec, coll, p.local_len, p.k, dp_sizes, model,
            word_bytes=wb, participants=participants,
        )
        for p, codec, coll, wb, _ in _leaf_wire_patterns(plan, dist)
    ]


def comm_round_timeline(
    plan, dist: DistConfig, mesh, compute_seconds=None
) -> Tuple[comm.BucketPlan, comm.Timeline]:
    """The bucket schedule and predicted overlapped timeline of one round
    under ``dist.resolved_overlap()`` (raises when overlap is "off" —
    there is no schedule to report). ``compute_seconds`` optionally
    threads per-bucket backward-slice times into the launch stamps;
    ``timeline.sync_seconds`` matches :func:`comm_round_cost`'s
    ``seconds`` to fp summation order, and ``timeline.seconds`` never
    exceeds it."""
    ocfg = dist.resolved_overlap()
    if ocfg is None:
        raise ValueError(
            "comm_round_timeline needs DistConfig.overlap != 'off' "
            "(e.g. overlap='buckets:4')"
        )
    bplan = comm.bucketize(_leaf_overlap_costs(plan, dist, mesh), ocfg)
    return bplan, comm.overlap_timeline(bplan, compute_seconds)


# ---------------------------------------------------------------------------
# train step
# ---------------------------------------------------------------------------
def make_train_step(
    model_mod,
    cfg: ModelConfig,
    dist: DistConfig,
    mesh,
    param_specs,
    plan,
    state_specs,
):
    """train_step(params, opt_state, sp_state, batch) ->
    (params, opt_state, sp_state, metrics)

    ``metrics["fastpath_fallbacks"]`` counts the fused-leaf selections
    (leaf x device) that fell back to dense selection this step. The
    step's stages run under the named scopes of :mod:`repro.core.stages`
    (``train.grads``, ``train.round``, ``train.optimizer``, and the
    round's ``spa.*``), so a profiler trace can be split by stage. The
    wire bytes and the overlap schedule are compile-time constants: the
    host has them from :func:`comm_round_bytes` and
    :func:`comm_round_timeline`.

    With ``dist.adaptive_k`` set, ``sp_state`` is the *pair*
    ``(compact_state_tree, controller_state_tree)`` (see
    :func:`init_controller_state`) and metrics gain ``"adaptive_k"``, the
    mean effective per-leaf k the round just used."""
    opt = make_optimizer(dist.optimizer)
    adaptive = dist.resolved_adaptive_k() is not None
    W = int(np.prod([mesh.shape[a] for a in dist.dp_axes]))
    spa = make_sparsify_aggregate(
        mesh, plan, param_specs, state_specs, dist, W
    )
    n_mb = dist.microbatches
    dp_spec = (
        tuple(dist.dp_axes) if len(dist.dp_axes) > 1 else dist.dp_axes[0]
    )
    acc_dt = _DT[dist.state_dtype]

    def worker_grads(params, wbatch):
        def gfn(mb):
            return jax.value_and_grad(
                lambda p: model_mod.loss_fn(p, cfg, mb)[0]
            )(params)

        if n_mb == 1:
            return gfn(wbatch)
        mbatch = jax.tree.map(
            lambda x: x.reshape((n_mb, x.shape[0] // n_mb) + x.shape[1:]),
            wbatch,
        )

        def body(carry, mb):
            loss_acc, g_acc = carry
            loss, g = gfn(mb)
            return (
                loss_acc + loss / n_mb,
                jax.tree.map(
                    lambda ac, gg: ac + (gg / n_mb).astype(acc_dt), g_acc, g
                ),
            ), None

        zero = jax.tree.map(lambda p: jnp.zeros(p.shape, acc_dt), params)
        (loss, grads), _ = jax.lax.scan(
            body, (jnp.zeros(()), zero), mbatch
        )
        return loss, grads

    def train_step(params, opt_state, sp_state, batch):
        with jax.named_scope(stages.GRADS):
            wb = jax.tree.map(
                lambda x: jax.lax.with_sharding_constraint(
                    x.reshape((W, x.shape[0] // W) + x.shape[1:]),
                    NamedSharding(mesh, P(dp_spec)),
                ),
                batch,
            )
            losses, grads_w = jax.vmap(worker_grads, in_axes=(None, 0))(
                params, wb
            )
            grads_w = jax.tree.map(
                lambda g: g.astype(_DT[dist.state_dtype]), grads_w
            )
        with jax.named_scope(stages.ROUND):
            if adaptive:
                cp_state, ctrl_state = sp_state
                agg, new_cp, new_ctrl, fallbacks = spa(
                    grads_w, cp_state, ctrl_state
                )
                new_sp = (new_cp, new_ctrl)
            else:
                agg, new_sp, fallbacks = spa(grads_w, sp_state)
        with jax.named_scope(stages.OPTIMIZER):
            new_params, new_opt = opt.update(agg, opt_state, params)
        metrics = {"loss": losses.mean(), "fastpath_fallbacks": fallbacks}
        if adaptive:
            # the k each leaf *used* this round (ctrl carries next round's)
            ks = [
                c.k for c in jax.tree.leaves(
                    ctrl_state,
                    is_leaf=lambda x: isinstance(x, comm.ControllerState),
                )
            ]
            metrics["adaptive_k"] = (
                jnp.stack([jnp.asarray(k, jnp.float32) for k in ks]).mean()
            )
        return new_params, new_opt, new_sp, metrics

    return train_step


# ---------------------------------------------------------------------------
# assembly (shapes only — safe for dry runs; allocation helpers for tests)
# ---------------------------------------------------------------------------
class Assembled(NamedTuple):
    train_step: Callable
    params_shape: Any
    axes: Any
    param_specs: Any
    state_shapes: Any
    state_specs: Any
    plan: Any


def shapes_and_axes(model_mod, cfg: ModelConfig):
    """Abstract init: parameter ShapeDtypeStructs + logical axes, no
    allocation (axes captured through a side cell during tracing)."""
    cell = {}

    def f():
        p, a = model_mod.init(jax.random.PRNGKey(0), cfg)
        cell["axes"] = a
        return p

    shapes = jax.eval_shape(f)
    return shapes, cell["axes"]


def assemble(model_mod, cfg: ModelConfig, dist: DistConfig, mesh) -> Assembled:
    params_shape, axes = shapes_and_axes(model_mod, cfg)
    param_specs = shlib.tree_specs(
        params_shape, axes, mesh, rules=dist.rules, dp_axes=dist.dp_axes
    )
    plan = build_plan(
        params_shape, param_specs, mesh, dist.sparsifier.sparsity, dist
    )
    W = int(np.prod([mesh.shape[a] for a in dist.dp_axes]))
    state_shapes, state_specs = sparsifier_state_shapes(
        plan, W, mesh, dist.dp_axes, _DT[dist.state_dtype]
    )
    step = make_train_step(
        model_mod, cfg, dist, mesh, param_specs, plan, state_specs
    )
    return Assembled(
        step, params_shape, axes, param_specs, state_shapes, state_specs, plan
    )
