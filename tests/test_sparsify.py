"""Unit + property tests for the sparsification core (paper Algorithms 1–2)."""
from typing import ClassVar

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _hyp import given, settings, st
from repro.core import (
    DistributedSim,
    SparsifierConfig,
    dense_mean,
    exact_topk_mask,
    fixed_k_payload,
    make_sparsifier,
    mask_to_payload,
    scatter_add_payloads,
    sparsity_to_k,
    threshold_topk_mask,
)
from repro.core import compact

jax.config.update("jax_enable_x64", False)


def _live(score) -> np.ndarray:
    """Which scores a selector must treat as nonzero. XLA flushes
    subnormal floats to zero on the CPU and the TPU, so a subnormal score
    is a zero score to every selector; numpy's ``> 0`` would count it."""
    return np.asarray(score) >= np.finfo(np.float32).tiny


# ---------------------------------------------------------------------------
# selectors
# ---------------------------------------------------------------------------
def test_exact_topk_mask_selects_largest():
    x = jnp.array([0.1, -5.0, 3.0, 0.0, -2.0])
    m = exact_topk_mask(jnp.abs(x), 2)
    np.testing.assert_array_equal(m, [0, 1, 1, 0, 0])


def test_exact_topk_edge_cases():
    """k <= 0 selects nothing; k >= J selects every *live* (nonzero-score)
    entry — a zero score carries no gradient and is never selected, the
    same contract the PR-2 fix gave the threshold selector."""
    x = jnp.arange(4.0)  # score 0.0 at index 0
    np.testing.assert_array_equal(exact_topk_mask(x, 0), jnp.zeros(4))
    np.testing.assert_array_equal(exact_topk_mask(x, 4), [0, 1, 1, 1])
    np.testing.assert_array_equal(exact_topk_mask(x, 9), [0, 1, 1, 1])
    np.testing.assert_array_equal(exact_topk_mask(jnp.zeros(4), 2), 0.0)


@settings(max_examples=50, deadline=None)
@given(
    st.lists(
        st.floats(-1e3, 1e3, allow_nan=False, width=32), min_size=2, max_size=64
    ),
    st.integers(1, 64),
)
def test_exact_topk_cardinality_and_dominance(vals, k):
    """Selector invariant net (ISSUE 4 satellite): cardinality is exactly
    min(k, #nonzero scores) — never above k — zero scores are never
    selected, and every selected score dominates every unselected one."""
    x = jnp.asarray(vals, jnp.float32)
    k = min(k, x.shape[0])
    score = jnp.abs(x)
    m = np.asarray(exact_topk_mask(score, k))
    n_live = int(_live(score).sum())
    assert int(m.sum()) == min(k, n_live)
    assert int(m.sum()) <= k
    assert not np.any(np.asarray(score)[m > 0] == 0.0)
    # every selected score >= every unselected score
    sel = np.asarray(score)[m > 0]
    unsel = np.asarray(score)[m == 0]
    if len(sel) and len(unsel):
        assert sel.min() >= unsel.max() - 1e-6


@settings(max_examples=50, deadline=None)
@given(
    st.lists(
        st.floats(0, 1e3, allow_nan=False, width=32), min_size=4, max_size=128
    ),
    st.integers(1, 128),
)
def test_threshold_topk_superset_of_k(vals, k):
    score = jnp.asarray(vals, jnp.float32)
    k = min(k, score.shape[0])
    m = np.asarray(threshold_topk_mask(score, k, n_iters=30))
    # bisection invariant: at least min(k, #positive) selected (zero scores
    # carry no gradient and are never selected — see the zero-round test),
    # and the selected set contains the exact positive top-k (threshold <=
    # k-th largest value)
    n_pos = int(_live(score).sum())
    assert int(m.sum()) >= min(k, n_pos)
    assert not np.any(np.asarray(score)[m > 0] == 0.0)
    # threshold <= k-th largest (meaningful only when k positives exist)
    if n_pos >= k:
        kth = np.sort(np.asarray(score))[-k]
        assert np.asarray(score)[m > 0].min() <= kth + 1e-6
    # cardinality stays at k whenever the bisection can separate the k-th
    # and (k+1)-th scores (ties / sub-resolution gaps legitimately exceed
    # k, so only assert when the gap clears the bisection's resolution)
    # (the bisection runs in float32, so its resolution bottoms out near
    # the f32 ulp of max(score) — demand a comfortably larger gap)
    s = np.sort(np.asarray(score))[::-1]
    if n_pos >= k and (len(s) == k or s[k - 1] - s[k] > s[0] * 2.0**-18):
        assert int(m.sum()) == k


def test_threshold_topk_zero_gradient_round():
    """Regression: an all-zero score collapsed the bisection to tau = 0 and
    ``score >= 0`` selected *every* coordinate — a zero gradient round
    would ship the whole (zero) vector. Cardinality must stay
    <= max(k, ties): here 0, and min(k, #positive) when a few coordinates
    are live."""
    assert float(threshold_topk_mask(jnp.zeros(64), 8).sum()) == 0.0
    # fewer positives than k: select exactly the positives, nothing else
    score = jnp.zeros(64).at[jnp.array([3, 17])].set(jnp.array([2.0, 5.0]))
    m = np.asarray(threshold_topk_mask(score, 8))
    np.testing.assert_array_equal(np.nonzero(m)[0], [3, 17])


def test_threshold_matches_exact_when_distinct():
    score = jnp.array([5.0, 1.0, 4.0, 2.0, 3.0])
    m_t = threshold_topk_mask(score, 2, n_iters=40)
    m_e = exact_topk_mask(score, 2)
    np.testing.assert_array_equal(m_t, m_e)


@pytest.mark.parametrize(
    "case,L,k,G",
    [
        ("random", 4096, 37, 8),
        ("ties", 4096, 37, 8),
        ("mostly_zero", 4096, 200, 4),  # 122 positive scores, k = 200
        ("zeros", 4096, 37, 8),
        ("random", 999, 10, 8),  # 999 = 124·8 + 7 = 7·128 + 103
        ("ties", 1200, 300, 2),  # k·G = L/2 exactly
        ("ties", 1199, 300, 1),  # k·G just over L/2: one lax.top_k
        ("random", 30000, 3, 128),  # a group is a whole row of lanes
    ],
)
def test_exact_top_k_equals_lax_top_k(monkeypatch, case, L, k, G):
    """The two-level top-k returns lax.top_k's values and indices, in its
    order, bit for bit: ties go to the lower index, zero scores fill the
    slots past the positives as lax.top_k fills them."""
    monkeypatch.setattr(compact, "TWO_LEVEL_MIN_LEN", 16)
    assert compact.top_k_group(L, k) == G
    rng = np.random.default_rng(L + k)
    x = rng.random(L, dtype=np.float32)
    if case == "ties":
        x = np.round(x * 4) / 4
    elif case == "mostly_zero":
        x[rng.permutation(L)[int(0.03 * L):]] = 0
    elif case == "zeros":
        x[:] = 0
    x = jnp.asarray(x)
    want = jax.lax.top_k(x, k)
    got = jax.jit(compact.exact_top_k, static_argnums=1)(x, k)
    for w, g in zip(want, got):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


@pytest.mark.parametrize("dynamic", [False, True])
def test_compact_select_two_level_leaf_matches_lax_top_k(monkeypatch, dynamic):
    """On a leaf long enough for the two-level path, RegTop-k after round
    0 (posterior-scored coordinates), with and without the adaptive k,
    selects as one lax.top_k over the leaf does, and as the dense-state
    oracle does."""
    L = compact.TWO_LEVEL_MIN_LEN + 5
    k = sparsity_to_k(L, 0.01)
    assert compact.top_k_group(L, k) > 1
    cfg = SparsifierConfig(kind="regtopk", sparsity=k / L, mu=1.5, omega=0.1)
    k_dyn = jnp.int32(k // 2) if dynamic else None
    g0, g1 = jax.random.normal(jax.random.PRNGKey(0), (2, L))
    st = compact.compact_init(L, k)
    a, vals, idx = compact.compact_select(cfg, st, g0, k)
    agg = 0.1 * jnp.zeros(L).at[idx].add(vals)
    st = compact.compact_finalize(st, a, vals, idx, agg)

    def select(st, g):
        return compact.compact_select(cfg, st, g, k, k_dyn=k_dyn)

    got = jax.jit(select)(st, g1)
    monkeypatch.setattr(compact, "TWO_LEVEL_MIN_LEN", L + 1)
    want = jax.jit(lambda st, g: select(st, g))(st, g1)
    for w, g in zip(want, got):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
    ghat_ref, _, _ = compact.reference_step(
        cfg, st, g1, agg, k // 2 if dynamic else k
    )
    ghat = jnp.zeros(L).at[got[2]].add(got[1])
    np.testing.assert_allclose(
        np.asarray(ghat), np.asarray(ghat_ref), rtol=1e-5, atol=1e-6
    )


def test_fixed_k_payload_roundtrip():
    vals = jnp.array([1.0, -9.0, 3.0, 0.5])
    score = jnp.abs(vals)
    pv, pi = fixed_k_payload(score, vals, 2)
    dense = scatter_add_payloads(pv[None], pi[None], jnp.ones(1), 4)
    np.testing.assert_allclose(dense, [0, -9.0, 3.0, 0])


def test_mask_to_payload_pads_with_noops():
    vals = jnp.array([1.0, -9.0, 3.0, 0.5])
    mask = jnp.array([0.0, 1.0, 0.0, 0.0])  # cardinality 1 < k=3
    pv, pi = mask_to_payload(mask, vals, 3)
    dense = scatter_add_payloads(pv[None], pi[None], jnp.ones(1), 4)
    np.testing.assert_allclose(dense, [0, -9.0, 0, 0])


def test_sparsity_to_k():
    assert sparsity_to_k(100, 0.01) == 1
    assert sparsity_to_k(100, 0.015) == 2
    assert sparsity_to_k(100, 1.0) == 100
    assert sparsity_to_k(100, 0.0) == 1  # floor at 1
    assert sparsity_to_k(10, 0.5) == 5


def test_sparsity_to_k_float_ceil_regression():
    """S * J computed in binary floating point lands ulps above the exact
    integer product (0.07 * 100 == 7.000000000000001); a naive ceil then
    inflates k — and with it the paper's compression ratio S = k/J
    (regression: sparsity_to_k(100, 0.07) returned 8)."""
    assert sparsity_to_k(100, 0.07) == 7
    # exhaustive S x J sweep over the paper's grid + decimal fractions:
    # k must equal the exact ceil of the rational product
    import fractions

    grid_S = (0.1, 0.01, 0.001, 0.07, 0.02, 0.05, 0.2, 0.5, 0.3)
    grid_J = (10, 100, 1000, 4096, 65536, 100_000)
    for S in grid_S:
        frac = fractions.Fraction(str(S))
        for J in grid_J:
            exact = max(1, min(J, -((-frac * J) // 1)))
            assert sparsity_to_k(J, S) == exact, (S, J)


@settings(max_examples=60, deadline=None)
@given(
    st.integers(1, 100_000),
    st.floats(0.0, 1.0, allow_nan=False),
    st.integers(1, 100_000),
    st.floats(0.0, 1.0, allow_nan=False),
)
def test_sparsity_to_k_monotone_in_both_arguments(J1, S1, J2, S2):
    """k = ceil(S*J) clipped to [1, J] is monotone in the sparsity at
    fixed length and in the length at fixed sparsity (ISSUE 4 satellite —
    property net over the PR-2 epsilon-tolerant ceil)."""
    lo_S, hi_S = sorted((S1, S2))
    assert sparsity_to_k(J1, lo_S) <= sparsity_to_k(J1, hi_S)
    lo_J, hi_J = sorted((J1, J2))
    assert sparsity_to_k(lo_J, S1) <= sparsity_to_k(hi_J, S1)
    # range invariant
    k = sparsity_to_k(J1, S1)
    assert 1 <= k <= J1


@settings(max_examples=60, deadline=None)
@given(st.integers(1, 50_000), st.integers(1, 50_000))
def test_sparsity_to_k_exact_on_representable_products(J, k0):
    """For S computed as k0/J (the only way real configs produce nominally
    integer products), the epsilon-tolerant ceil must recover exactly k0 —
    never the k0+1 a naive ceil gives when float rounding lands S*J a few
    ulps above the integer."""
    k0 = min(k0, J)
    S = k0 / J
    assert sparsity_to_k(J, S) == k0


@settings(max_examples=30, deadline=None)
@given(
    st.lists(
        st.floats(0, 1e3, allow_nan=False, width=32), min_size=4, max_size=64
    ),
    st.integers(1, 64),
)
def test_all_selectors_never_select_zero_scores(vals, k):
    """Cross-selector invariant (regression net for the PR-2 zero-score
    fixes): no registered selector ever selects a zero-score coordinate,
    and the exact selector never exceeds cardinality k."""
    from repro.core.selectors import SELECTORS

    score = jnp.asarray(vals, jnp.float32)
    k = min(k, score.shape[0])
    for name, select in SELECTORS.items():
        m = np.asarray(select(score, k))
        assert set(np.unique(m)) <= {0.0, 1.0}, name
        assert not np.any(np.asarray(score)[m > 0] == 0.0), name
        if name == "exact":
            assert int(m.sum()) <= k


def test_sparsity_to_k_shifts_leaf_plan_and_wire_bytes():
    """The off-by-one propagated into LeafPlan.k and the byte accounting:
    at S=0.07, J=100 each coo_fp32 payload is 8 B/coordinate — one
    phantom coordinate per leaf per gather hop."""
    from jax.sharding import PartitionSpec as P

    from repro.core.distributed import (
        DistConfig,
        build_plan,
        comm_round_bytes,
    )

    class _Mesh:
        shape: ClassVar[dict] = {"data": 4}

    shapes = {"w": jax.ShapeDtypeStruct((100,), jnp.float32)}
    plan = build_plan(shapes, {"w": P(None)}, _Mesh(), 0.07)
    assert plan["w"].k == 7
    dist = DistConfig(codec="coo_fp32", collective="sparse_allgather")
    pred, meas = comm_round_bytes(plan, dist, _Mesh())
    # (N-1) gather hops x k coordinates x 8 B — not k=8's 192 B
    assert pred == meas == 3 * 7 * 8


# ---------------------------------------------------------------------------
# sparsifier algebra (paper Algorithm 1 / 2 invariants)
# ---------------------------------------------------------------------------
def _step(kind, g, state=None, g_prev=None, **kw):
    cfg = SparsifierConfig(kind=kind, **kw)
    sp = make_sparsifier(cfg)
    if state is None:
        state = sp.init(g.shape[0])
    if g_prev is None:
        g_prev = jnp.zeros_like(g)
    return sp, sp.step(state, g, g_prev)


@settings(max_examples=40, deadline=None)
@given(
    st.lists(
        st.floats(-100, 100, allow_nan=False, width=32), min_size=4, max_size=64
    ),
    st.floats(0.05, 1.0),
)
def test_error_conservation(vals, S):
    """eps' + ghat == a == eps + g  (Alg. 1 lines 3/6; Alg. 2 lines 7/12)."""
    g = jnp.asarray(vals, jnp.float32)
    for kind in ("topk", "regtopk", "hard_threshold"):
        sp, (ghat, mask, ns) = _step(kind, g, sparsity=S, threshold=1.0)
        np.testing.assert_allclose(ns.eps + ghat, g, rtol=1e-6, atol=1e-6)


@settings(max_examples=40, deadline=None)
@given(
    st.lists(
        st.floats(-100, 100, allow_nan=False, width=32), min_size=4, max_size=64
    )
)
def test_mask_cardinality_topk(vals):
    g = jnp.asarray(vals, jnp.float32)
    k = sparsity_to_k(g.shape[0], 0.25)
    n_live = int((np.abs(np.asarray(g)) > 0).sum())
    sp, (ghat, mask, ns) = _step("topk", g, sparsity=0.25)
    assert int(np.asarray(mask).sum()) == min(k, n_live)
    assert int((np.asarray(ghat) != 0).sum()) <= k


def test_round0_regtopk_equals_topk():
    """Alg. 2 line 2: round 0 of RegTop-k is plain Top-k."""
    g = jnp.array([3.0, -1.0, 0.5, -7.0, 2.0])
    _, (gh_t, m_t, _) = _step("topk", g, sparsity=0.4)
    _, (gh_r, m_r, _) = _step("regtopk", g, sparsity=0.4, mu=1.0)
    np.testing.assert_array_equal(m_t, m_r)
    np.testing.assert_allclose(gh_t, gh_r)


def test_mu_to_zero_recovers_topk_after_round0():
    """Sec. 4 case (1): mu -> 0 makes the regularizer -> 1 (Top-k)."""
    key = jax.random.PRNGKey(0)
    g0 = jax.random.normal(key, (32,))
    g1 = jax.random.normal(jax.random.fold_in(key, 1), (32,))
    g_agg = 0.5 * g0  # arbitrary broadcast value

    def run(kind, mu):
        cfg = SparsifierConfig(kind=kind, sparsity=0.25, mu=mu, omega=1.0)
        sp = make_sparsifier(cfg)
        st_ = sp.init(32)
        _, _, st_ = sp.step(st_, g0, jnp.zeros(32))
        ghat, mask, _ = sp.step(st_, g1, g_agg)
        return np.asarray(mask)

    np.testing.assert_array_equal(run("regtopk", 1e-9), run("topk", 1e9))


def test_regtopk_damps_cancelling_entry():
    """Sec. 4 case (2): if entries cancel at the server, Delta = -1 and the
    coordinate is damped to ~0 score -> never selected next round."""
    # worker sees a large first coordinate that cancelled: g_agg_prev[0] = 0
    cfg = SparsifierConfig(kind="regtopk", sparsity=0.5, mu=1.0, omega=0.5)
    sp = make_sparsifier(cfg)
    state = sp.init(2)
    g0 = jnp.array([100.0, 1.0])
    ghat, mask, state = sp.step(state, g0, jnp.zeros(2))  # round0: picks idx0
    np.testing.assert_array_equal(mask, [1.0, 0.0])
    g_agg = jnp.array([0.0, 0.0])  # the big entry cancelled at the server
    g1 = jnp.array([100.0, 1.0])
    ghat, mask, state = sp.step(state, g1, g_agg)
    # accumulated a = [100, 2]; Delta[0] = (0 - .5*100)/(.5*100) = -1
    # -> score[0] = 100 * tanh(0) = 0 < score[1] -> picks idx1
    np.testing.assert_array_equal(mask, [0.0, 1.0])


def test_posterior_distortion_formula():
    """Check Delta against Alg. 2 line 8 by hand."""
    cfg = SparsifierConfig(kind="regtopk", sparsity=0.5, mu=2.0, omega=0.25)
    sp = make_sparsifier(cfg)
    state = sp.init(4)
    g0 = jnp.array([4.0, -3.0, 2.0, 1.0])
    _, m0, state = sp.step(state, g0, jnp.zeros(4))  # selects idx 0,1
    g_agg = jnp.array([2.0, -1.0, 0.3, 0.2])
    g1 = jnp.array([1.0, 1.0, 1.0, 1.0])
    a1 = state.eps + g1  # = [0,0,2,1] + [1,1,1,1] = [1,1,3,2]
    np.testing.assert_allclose(a1, [1.0, 1.0, 3.0, 2.0])
    # Delta_sent = (g_agg - w*a_prev)/(w*a1), sent = {0,1}
    d0 = (2.0 - 0.25 * 4.0) / (0.25 * 1.0)  # = 4
    d1 = (-1.0 - 0.25 * -3.0) / (0.25 * 1.0)  # = -1
    score_expected = np.abs(np.asarray(a1)) * np.tanh(
        np.abs(1 + np.array([d0, d1, cfg.q_const, cfg.q_const])) / 2.0
    )
    score = np.asarray(sp._score(state, a1, g_agg))
    np.testing.assert_allclose(score, score_expected, rtol=1e-6)


def test_hard_threshold_variable_k():
    g = jnp.array([0.5, 2.0, -3.0, 0.1])
    _, (ghat, mask, _) = _step("hard_threshold", g, threshold=1.0)
    np.testing.assert_array_equal(mask, [0, 1, 1, 0])


def test_none_sparsifier_identity():
    g = jnp.array([1.0, -2.0, 3.0])
    _, (ghat, mask, ns) = _step("none", g)
    np.testing.assert_allclose(ghat, g)
    np.testing.assert_allclose(ns.eps, 0.0)


def test_zero_accumulated_gradient_no_nan():
    cfg = SparsifierConfig(kind="regtopk", sparsity=0.5, mu=1.0)
    sp = make_sparsifier(cfg)
    state = sp.init(4)
    _, _, state = sp.step(state, jnp.zeros(4), jnp.zeros(4))
    ghat, mask, state = sp.step(state, jnp.zeros(4), jnp.zeros(4))
    assert not np.any(np.isnan(np.asarray(ghat)))
    assert not np.any(np.isnan(np.asarray(state.eps)))


def test_y_exponent_changes_ranking():
    """Remark 4: y < 1 flattens the prior; ranking can change."""
    cfg1 = SparsifierConfig(kind="regtopk", sparsity=0.5, mu=1.0, y=1.0)
    cfg2 = SparsifierConfig(kind="regtopk", sparsity=0.5, mu=1.0, y=0.1)
    sp1, sp2 = make_sparsifier(cfg1), make_sparsifier(cfg2)
    a = jnp.array([10.0, 1.0])
    st1 = sp1.init(2)._replace(  # reprolint: disable=RPL106 (test setup)
        s_prev=jnp.array([1.0, 1.0]),
        a_prev=jnp.array([10.0, 1.0]),
        t=jnp.ones((), jnp.int32),
    )
    g_prev = jnp.array([1.0, 1.2])  # idx0 mostly cancelled, idx1 reinforced
    s1 = np.asarray(sp1._score(st1, a, g_prev))
    s2 = np.asarray(sp2._score(st1, a, g_prev))
    # with y=0.1 the regularizer dominates -> ranking flips toward idx1
    assert (s1[0] > s1[1]) != (s2[0] > s2[1]) or s2[1] > s2[0]


# ---------------------------------------------------------------------------
# aggregation equivalence
# ---------------------------------------------------------------------------
@settings(max_examples=25, deadline=None)
@given(st.integers(0, 2**31 - 1))
def test_dense_vs_sparse_aggregation_equivalence(seed):
    key = jax.random.PRNGKey(seed)
    N, L, k = 4, 32, 8
    ghat = jax.random.normal(key, (N, L))
    # sparsify each row to exactly k nonzeros
    masks = jax.vmap(lambda r: exact_topk_mask(jnp.abs(r), k))(ghat)
    ghat = ghat * masks
    w = jnp.full((N,), 1.0 / N)
    dense = dense_mean(ghat, w)
    vals, idx = jax.vmap(lambda m, v: mask_to_payload(m, v, k))(masks, ghat)
    sparse = scatter_add_payloads(vals, idx, w, L)
    np.testing.assert_allclose(dense, sparse, rtol=1e-5, atol=1e-6)


# ---------------------------------------------------------------------------
# end-to-end simulator behaviour (paper Fig. 1 toy, exact numbers)
# ---------------------------------------------------------------------------
def _toy_sim(kind, mu=1.0, steps=60):
    x = jnp.array([[100.0, 1.0], [-100.0, 1.0]])

    def grad_fn(theta, n):
        xn = x[n]
        e = jnp.exp(-jnp.dot(theta, xn))
        return -e * xn / (1 + e)

    def loss(theta):
        return jnp.mean(jnp.log(1 + jnp.exp(-x @ theta)))

    cfg = SparsifierConfig(kind=kind, sparsity=0.5, mu=mu)
    sim = DistributedSim(
        grad_fn, n_workers=2, length=2, sparsifier_cfg=cfg, learning_rate=0.9
    )
    fin, trace = sim.run(jnp.array([0.0, 1.0]), steps, trace_fn=loss)
    return np.asarray(trace)


def test_fig1_topk_stuck_regtopk_tracks():
    """Paper Fig. 1: Top-1 makes no progress for ~50 iters; RegTop-1 tracks
    centralized training."""
    t_topk = _toy_sim("topk")
    t_reg = _toy_sim("regtopk")
    t_none = _toy_sim("none")
    assert t_topk[49] == pytest.approx(t_topk[0])  # stuck
    assert t_reg[49] < 0.05  # converging
    assert abs(t_reg[49] - t_none[49]) < 0.01  # tracks ideal


def test_simulator_sparse_aggregation_matches_dense():
    x = jnp.array([[100.0, 1.0], [-100.0, 1.0]])

    def grad_fn(theta, n):
        xn = x[n]
        e = jnp.exp(-jnp.dot(theta, xn))
        return -e * xn / (1 + e)

    cfg = SparsifierConfig(kind="regtopk", sparsity=0.5, mu=1.0)
    out = {}
    for agg in ("dense_allreduce", "sparse_allgather"):
        sim = DistributedSim(
            grad_fn, 2, 2, cfg, learning_rate=0.9, aggregation=agg
        )
        fin, _ = sim.run(jnp.array([0.0, 1.0]), 30)
        out[agg] = np.asarray(fin.theta)
    np.testing.assert_allclose(
        out["dense_allreduce"], out["sparse_allgather"], rtol=1e-5
    )


def test_training_equivalence_dense_vs_fused_fastpath():
    """ISSUE 5: a full training run with the fused Pallas fastpath must
    track the dense path exactly. The simulator fuses the scoring stage
    (SparsifierConfig.score_fn → the regtopk score kernel, interpret mode
    on CPU); the score kernel replays the same f32 op chain, so the
    trajectories match to float tolerance — and selection (discrete)
    never diverges."""
    from repro.data.pipeline import linreg_grad_fn, make_linreg

    data = make_linreg(3, 4, 64, 100)
    grad_fn = linreg_grad_fn(data)
    cfg = SparsifierConfig(kind="regtopk", sparsity=0.1, mu=1.0)
    out = {}
    for fp in ("off", "on"):
        sim = DistributedSim(
            grad_fn, 4, 64, cfg, learning_rate=1e-2, fastpath=fp
        )
        assert (sim.sparsifier.cfg.score_fn is not None) == (fp == "on")
        fin, _ = sim.run(jnp.zeros(64), 40)
        out[fp] = np.asarray(fin.theta)
    np.testing.assert_allclose(out["off"], out["on"], rtol=1e-6, atol=1e-7)


def test_sim_fastpath_auto_declines_off_tpu():
    """'auto' must resolve to the unfused path off-TPU (interpret-mode
    Pallas never beats XLA), leaving score_fn unset; unknown modes raise."""
    from repro.data.pipeline import linreg_grad_fn, make_linreg

    grad_fn = linreg_grad_fn(make_linreg(3, 2, 16, 50))
    cfg = SparsifierConfig(kind="regtopk", sparsity=0.25)
    sim = DistributedSim(grad_fn, 2, 16, cfg, fastpath="auto")
    if jax.default_backend() != "tpu":
        assert sim.sparsifier.cfg.score_fn is None
    with pytest.raises(ValueError, match="fastpath"):
        DistributedSim(grad_fn, 2, 16, cfg, fastpath="bogus")


def test_dgc_momentum_correction():
    """DGC: velocity conservation + momentum masking (Lin et al. [26])."""
    cfg = SparsifierConfig(kind="dgc", sparsity=0.5)
    sp = make_sparsifier(cfg)
    state = sp.init(4)
    g = jnp.array([4.0, -3.0, 1.0, 0.5])
    ghat, mask, s1 = sp.step(state, g, jnp.zeros(4))
    # round 0: u = g, v = g -> top-2 = idx 0,1
    np.testing.assert_array_equal(mask, [1, 1, 0, 0])
    np.testing.assert_allclose(s1.eps + ghat, g)  # v conserved
    # momentum zeroed where sent
    np.testing.assert_allclose(np.asarray(s1.a_prev)[:2], 0.0)
    np.testing.assert_allclose(np.asarray(s1.a_prev)[2:], [1.0, 0.5])
    # round 1: u = 0.9*u_prev + g
    g2 = jnp.array([0.0, 0.0, 1.0, 0.0])
    ghat2, mask2, s2 = sp.step(s1, g2, jnp.zeros(4))
    # v = eps + u = [0,0,1,0.5] + [0,0,1.9,0.45] = [0,0,2.9,0.95]
    np.testing.assert_allclose(np.asarray(ghat2), [0, 0, 2.9, 0.95], rtol=1e-6)


def test_dgc_toy_example_progresses():
    t = _toy_sim("dgc")
    assert np.isfinite(t).all()


@settings(max_examples=20, deadline=None)
@given(st.integers(0, 2**31 - 1), st.integers(2, 8))
def test_coordinated_kinds_produce_identical_masks(seed, n_workers):
    """coordtopk/cyclic invariant: given identical common inputs (broadcast
    aggregate + synchronized state), every worker selects the same mask
    regardless of its private gradient."""
    key = jax.random.PRNGKey(seed)
    L, S = 24, 0.25
    grads = jax.random.normal(key, (n_workers, L))  # heterogeneous
    g_prev = jax.random.normal(jax.random.fold_in(key, 1), (L,))
    for kind in ("coordtopk",):
        cfg = SparsifierConfig(kind=kind, sparsity=S)
        sp = make_sparsifier(cfg)
        st_ = sp.init(L)
        stacked = jax.tree.map(
            lambda x: jnp.broadcast_to(x, (n_workers,) + x.shape), st_
        )
        for _ in range(3):
            ghat, masks, stacked = jax.vmap(
                sp.step, in_axes=(0, 0, None)
            )(stacked, grads, g_prev)
            m = np.asarray(masks)
            assert (m == m[0]).all(), f"{kind}: masks diverged"


def test_coordtopk_linreg_converges_where_topk_plateaus():
    """The §Beyond headline in miniature: S=0.3, N=8 heterogeneous linreg."""
    from repro.data.pipeline import linreg_grad_fn, make_linreg

    data = make_linreg(5, 8, 32, 100)
    grad_fn = linreg_grad_fn(data)
    out = {}
    for kind in ("topk", "coordtopk"):
        cfg = SparsifierConfig(kind=kind, sparsity=0.3)
        sim = DistributedSim(grad_fn, 8, 32, cfg, learning_rate=1e-2)
        _, tr = sim.run(
            jnp.zeros(32), 3000,
            trace_fn=lambda th: jnp.linalg.norm(th - data.theta_star),
        )
        out[kind] = float(np.asarray(tr)[-1])
    assert out["coordtopk"] < 1e-4
    assert out["topk"] > 10 * out["coordtopk"]
