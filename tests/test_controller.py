"""Property + differential tests for the adaptive-k controller (ISSUE 8).

Covers, per the issue's satellite checklist:

* control-law properties (hypothesis via ``_hyp``): planned k always lands
  in the resolved ``[k_min, k_max]``; k is monotone **non-decreasing** in
  the smoothed error ratio — equivalently non-increasing in the
  error-budget slack ``budget - err_ratio``; the hysteresis dead band
  keeps k still;
* ``parse_adaptive_k`` accepts/rejects the documented CLI grammar;
* the off-switch differential: a *pinned* controller
  (``k_min == k_max == static k``) is bit-for-bit the historical static-k
  trajectory, in both :class:`repro.core.DistributedSim` and the
  ``make_sparsify_aggregate`` shard_map runtime, over randomized configs;
* retrace guards (the ``test_guards`` counting idiom): the adaptive round
  compiles exactly once even while k moves — k is a dynamic operand, the
  payload capacity is the static shape.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hyp import given, settings, st
from jax.sharding import PartitionSpec as P

from repro import comm
from repro.comm import AdaptiveKController, parse_adaptive_k
from repro.core import (
    DistributedSim,
    SparsifierConfig,
    exact_topk_mask,
    exact_topk_mask_dynamic,
    sparsity_to_k,
)

N, J = 4, 64
BOUNDS = (2, 32)


def _ctrl(**kw):
    kw.setdefault("budget", 0.1)
    return AdaptiveKController(**kw)


def _grad_fn(seed: int):
    """Deterministic heterogeneous quadratic: worker w's gradient of
    0.5 * ||sqrt(A_w) theta - b_w||^2 elementwise."""
    key = jax.random.PRNGKey(seed)
    A = jax.random.uniform(key, (N, J), minval=0.5, maxval=1.5)
    b = jax.random.normal(jax.random.fold_in(key, 1), (N, J))

    def gf(theta, w):
        return A[w] * theta - b[w]

    return gf


# ---------------------------------------------------------------------------
# control-law properties
# ---------------------------------------------------------------------------
@settings(max_examples=30, deadline=None)
@given(
    length=st.integers(min_value=1, max_value=5000),
    lo=st.floats(min_value=1e-3, max_value=0.4),
    span=st.floats(min_value=0.0, max_value=0.5),
)
def test_bounds_fractions_resolve_ordered_within_length(length, lo, span):
    c = _ctrl(k_min=lo, k_max=min(lo + span, 0.999))
    kmin, kmax = c.bounds(length)
    assert 1 <= kmin <= kmax <= length


@settings(max_examples=30, deadline=None)
@given(
    length=st.integers(min_value=1, max_value=5000),
    lo=st.integers(min_value=1, max_value=64),
    span=st.integers(min_value=0, max_value=512),
)
def test_bounds_absolute_clip_to_length(length, lo, span):
    c = _ctrl(k_min=lo, k_max=lo + span)
    kmin, kmax = c.bounds(length)
    assert 1 <= kmin <= kmax <= length
    assert kmax <= lo + span


@settings(max_examples=40, deadline=None)
@given(
    budget=st.floats(min_value=1e-3, max_value=10.0),
    ratio=st.floats(min_value=0.0, max_value=100.0),
    k0=st.integers(min_value=BOUNDS[0], max_value=BOUNDS[1]),
    hyst=st.floats(min_value=0.0, max_value=1.0),
    gain=st.floats(min_value=1.01, max_value=8.0),
)
def test_plan_k_always_within_bounds(budget, ratio, k0, hyst, gain):
    c = _ctrl(budget=budget, hysteresis=hyst, gain=gain)
    k = int(c.plan_k(jnp.asarray(ratio), jnp.asarray(k0), *BOUNDS))
    assert BOUNDS[0] <= k <= BOUNDS[1]


@settings(max_examples=40, deadline=None)
@given(
    budget=st.floats(min_value=1e-2, max_value=5.0),
    r1=st.floats(min_value=0.0, max_value=20.0),
    r2=st.floats(min_value=0.0, max_value=20.0),
    k0=st.integers(min_value=BOUNDS[0], max_value=BOUNDS[1]),
    hyst=st.floats(min_value=0.0, max_value=0.5),
)
def test_plan_k_monotone_in_budget_slack(budget, r1, r2, k0, hyst):
    """More error-budget slack (budget - ratio) never *raises* k: the
    planned k is monotone non-decreasing in the error ratio."""
    c = _ctrl(budget=budget, hysteresis=hyst)
    lo_r, hi_r = sorted((r1, r2))
    k_lo = int(c.plan_k(jnp.asarray(lo_r), jnp.asarray(k0), *BOUNDS))
    k_hi = int(c.plan_k(jnp.asarray(hi_r), jnp.asarray(k0), *BOUNDS))
    assert k_lo <= k_hi


@settings(max_examples=30, deadline=None)
@given(
    budget=st.floats(min_value=1e-2, max_value=5.0),
    hyst=st.floats(min_value=1e-3, max_value=0.5),
    k0=st.integers(min_value=BOUNDS[0], max_value=BOUNDS[1]),
    u=st.floats(min_value=-1.0, max_value=1.0),
)
def test_hysteresis_dead_band_keeps_k(budget, hyst, k0, u):
    """Any pressure inside [1 - h, 1 + h] keeps the previous k."""
    c = _ctrl(budget=budget, hysteresis=hyst)
    ratio = budget * (1.0 + 0.999 * hyst * u)
    k = int(c.plan_k(jnp.asarray(ratio), jnp.asarray(k0), *BOUNDS))
    assert k == k0


def test_observe_seeds_then_discounts():
    c = _ctrl(budget=1.0, momentum=0.8, hysteresis=0.0)
    s = c.init(8, *BOUNDS)
    s = c.observe(s, jnp.asarray(3.0), jnp.asarray(1.0), k_min=2, k_max=32)
    assert float(s.err_ratio) == pytest.approx(3.0)  # t == 0 seeds raw
    s = c.observe(s, jnp.asarray(1.0), jnp.asarray(1.0), k_min=2, k_max=32)
    assert float(s.err_ratio) == pytest.approx(0.8 * 3.0 + 0.2 * 1.0)
    assert int(s.t) == 2


def test_config_validation():
    for bad in (
        dict(budget=0.0),
        dict(budget=1.0, momentum=1.0),
        dict(budget=1.0, hysteresis=-0.1),
        dict(budget=1.0, gain=1.0),
        dict(budget=1.0, k_min=0.0),
        dict(budget=1.0, k_min=0.5, k_max=0.25),
        dict(budget=1.0, k_min=64, k_max=8),
    ):
        with pytest.raises(ValueError):
            AdaptiveKController(**bad)
    # mixed-kind bounds resolve per leaf; ordering is checked there
    c = AdaptiveKController(budget=1.0, k_min=0.5, k_max=4)
    with pytest.raises(ValueError):
        c.bounds(100)  # 50 > 4


def test_parse_adaptive_k():
    c = parse_adaptive_k("0.25")
    assert c.budget == 0.25
    c = parse_adaptive_k(" 0.1 , 4 , 0.5 ")
    assert (c.budget, c.k_min, c.k_max) == (0.1, 4.0, 0.5)
    for bad in ("", "0.1,4", "0.1,4,8,16", "abc", "0.1,x,8"):
        with pytest.raises(ValueError):
            parse_adaptive_k(bad)


@settings(max_examples=20, deadline=None)
@given(
    seed=st.integers(min_value=0, max_value=2**31 - 1),
    k=st.integers(min_value=0, max_value=J),
)
def test_dynamic_mask_matches_static_at_capacity(seed, k):
    score = jnp.abs(
        jax.random.normal(jax.random.PRNGKey(seed), (J,))
    ) * (jax.random.uniform(jax.random.PRNGKey(seed + 1), (J,)) > 0.2)
    static = exact_topk_mask(score, k)
    dyn = exact_topk_mask_dynamic(score, jnp.asarray(k), k)
    assert np.array_equal(np.asarray(static), np.asarray(dyn))
    # below capacity: cardinality is min(k_dyn, live entries), a subset
    # of the capacity winners
    if k >= 2:
        part = exact_topk_mask_dynamic(score, jnp.asarray(k // 2), k)
        assert float(part.sum()) <= min(k // 2, int((score > 0).sum()))
        assert bool(jnp.all(static - part >= 0))


# ---------------------------------------------------------------------------
# off-switch differential: pinned controller == static path, bit-for-bit
# ---------------------------------------------------------------------------
def _run_sim(seed, kind, sparsity, collective, codec, adaptive, steps=4):
    cfg = SparsifierConfig(kind=kind, sparsity=sparsity, mu=4.0)
    sim = DistributedSim(
        _grad_fn(seed), N, J, cfg, learning_rate=1e-2,
        collective=collective, codec=codec, adaptive_k=adaptive,
    )
    final, trace = sim.run(jnp.ones(J), steps)
    return final, np.asarray(trace)


@settings(max_examples=10, deadline=None)
@given(
    seed=st.integers(min_value=0, max_value=10_000),
    kind=st.sampled_from(["topk", "regtopk"]),
    sparsity=st.sampled_from([0.05, 0.125, 0.3]),
    collective=st.sampled_from(["dense_allreduce", "sparse_allgather"]),
    codec=st.sampled_from(["coo_fp32", "coo_q8"]),
)
def test_sim_disabled_controller_is_bit_for_bit(
    seed, kind, sparsity, collective, codec
):
    """adaptive_k=None vs a pinned controller (k_min == k_max == the
    static k, budget huge): the dynamic-k machinery must be a no-op —
    every SimState leaf identical, every round."""
    k_st = sparsity_to_k(J, sparsity)
    pinned = AdaptiveKController(
        budget=1e9, k_min=k_st, k_max=k_st, hysteresis=0.0
    )
    f0, tr0 = _run_sim(seed, kind, sparsity, collective, codec, None)
    f1, tr1 = _run_sim(seed, kind, sparsity, collective, codec, pinned)
    assert np.array_equal(tr0, tr1)
    for a, b in zip(
        jax.tree.leaves(f0._replace(ctrl=None)),
        jax.tree.leaves(f1._replace(ctrl=None)),
        strict=True,
    ):
        assert np.array_equal(np.asarray(a), np.asarray(b))
    assert int(f1.ctrl.k) == k_st  # pinned: never moved


@pytest.mark.parametrize("kind", ["topk", "regtopk"])
def test_spa_disabled_controller_is_bit_for_bit(kind):
    """Same differential through the shard_map runtime (single-device
    mesh in-process; the multi-worker mesh variant rides tier1-slow)."""
    from repro.compat import make_mesh
    from repro.core.distributed import (
        DistConfig,
        LeafPlan,
        init_controller_state,
        init_sparsifier_state,
        make_sparsify_aggregate,
    )

    mesh = make_mesh((1, 1), ("data", "model"))
    k_st = sparsity_to_k(J, 0.125)
    grads = {"w": jnp.linspace(-1.0, 1.0, J).reshape(1, J)}
    plan = {"w": LeafPlan((J,), (J,), J, k_st, P(None), fused=False)}

    def rollout(adaptive):
        dist = DistConfig(
            sparsifier=SparsifierConfig(kind=kind, sparsity=0.125, mu=4.0),
            codec="coo_fp32", collective="sparse_allgather",
            dp_axes=("data",), adaptive_k=adaptive,
        )
        state, specs = init_sparsifier_state(
            plan, 1, mesh, ("data",), jnp.float32
        )
        spa = make_sparsify_aggregate(
            mesh, plan, {"w": P(None)}, specs, dist, 1
        )
        aggs = []
        step = jax.jit(spa)
        with mesh:
            if adaptive is None:
                for _ in range(4):
                    agg, state, _ = step(grads, state)
                    aggs.append(np.asarray(agg["w"]))
            else:
                ctrl, _ = init_controller_state(plan, dist, mesh)
                for _ in range(4):
                    agg, state, ctrl, _ = step(grads, state, ctrl)
                    aggs.append(np.asarray(agg["w"]))
        return aggs, state

    pinned = AdaptiveKController(
        budget=1e9, k_min=k_st, k_max=k_st, hysteresis=0.0
    )
    aggs0, st0 = rollout(None)
    aggs1, st1 = rollout(pinned)
    for a, b in zip(aggs0, aggs1, strict=True):
        assert np.array_equal(a, b)
    for a, b in zip(
        jax.tree.leaves(st0), jax.tree.leaves(st1), strict=True
    ):
        assert np.array_equal(np.asarray(a), np.asarray(b))


# ---------------------------------------------------------------------------
# retrace guards: k moves, the compiled round does not
# ---------------------------------------------------------------------------
def _counting(fn):
    calls = {"n": 0}

    def wrapper(*args, **kwargs):
        calls["n"] += 1
        return fn(*args, **kwargs)

    return wrapper, calls


def test_adaptive_sim_round_compiles_once():
    cfg = SparsifierConfig(kind="regtopk", sparsity=0.125, mu=4.0)
    sim = DistributedSim(
        _grad_fn(3), N, J, cfg, learning_rate=1e-2,
        collective="sparse_allgather",
        adaptive_k=AdaptiveKController(budget=0.01, k_min=2, k_max=32),
    )
    counted, calls = _counting(sim.step_fn)
    step = jax.jit(counted)
    state = sim.init(jnp.ones(J))
    ks = []
    for _ in range(6):
        state, _ = step(state)
        ks.append(int(state.ctrl.k))
    assert calls["n"] == 1, f"adaptive round retraced: {calls['n']} traces"
    assert len(set(ks)) > 1, f"controller never moved k: {ks}"


def test_adaptive_spa_round_compiles_once():
    from repro.compat import make_mesh
    from repro.core.distributed import (
        DistConfig,
        LeafPlan,
        init_controller_state,
        init_sparsifier_state,
        make_sparsify_aggregate,
    )

    mesh = make_mesh((1, 1), ("data", "model"))
    dist = DistConfig(
        sparsifier=SparsifierConfig(kind="regtopk", sparsity=0.125, mu=4.0),
        codec="coo_fp32", collective="sparse_allgather",
        dp_axes=("data",),
        adaptive_k=AdaptiveKController(budget=0.01, k_min=2, k_max=32),
    )
    plan = {"w": LeafPlan((J,), (J,), J, 32, P(None), fused=False)}
    state, specs = init_sparsifier_state(plan, 1, mesh, ("data",), jnp.float32)
    ctrl, _ = init_controller_state(plan, dist, mesh)
    spa = make_sparsify_aggregate(mesh, plan, {"w": P(None)}, specs, dist, 1)
    counted, calls = _counting(spa)
    step = jax.jit(counted)
    grads = {"w": jnp.linspace(-1.0, 1.0, J).reshape(1, J)}
    ks = []
    with mesh:
        for _ in range(6):
            agg, state, ctrl, _ = step(grads, state, ctrl)
            ks.append(int(ctrl["w"].k))
    jax.block_until_ready(agg)
    assert calls["n"] == 1, f"adaptive shard_map retraced: {calls['n']}"
    assert len(set(ks)) > 1, f"controller never moved k: {ks}"


def test_capacity_mismatch_fails_fast():
    """A plan whose leaf capacity is not the controller's k_max must be
    rejected at build time, not deep inside the traced round."""
    from repro.compat import make_mesh
    from repro.core.distributed import (
        DistConfig,
        LeafPlan,
        init_sparsifier_state,
        make_sparsify_aggregate,
    )

    mesh = make_mesh((1, 1), ("data", "model"))
    dist = DistConfig(
        sparsifier=SparsifierConfig(kind="regtopk", sparsity=0.125),
        dp_axes=("data",),
        adaptive_k=AdaptiveKController(budget=0.1, k_min=2, k_max=32),
    )
    plan = {"w": LeafPlan((J,), (J,), J, 8, P(None), fused=False)}  # k != 32
    _, specs = init_sparsifier_state(plan, 1, mesh, ("data",), jnp.float32)
    with pytest.raises(ValueError, match="capacity mismatch"):
        make_sparsify_aggregate(mesh, plan, {"w": P(None)}, specs, dist, 1)


def test_adaptive_rejects_unsupported_kinds():
    cfg = SparsifierConfig(kind="dgc", sparsity=0.125)
    with pytest.raises(ValueError, match="topk"):
        DistributedSim(
            _grad_fn(0), N, J, cfg,
            adaptive_k=AdaptiveKController(budget=0.1),
        )
    cfg = SparsifierConfig(kind="regtopk", sparsity=0.125,
                           selector="threshold")
    with pytest.raises(ValueError, match="exact"):
        DistributedSim(
            _grad_fn(0), N, J, cfg,
            adaptive_k=AdaptiveKController(budget=0.1),
        )
