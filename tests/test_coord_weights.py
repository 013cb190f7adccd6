"""Per-coordinate aggregation weights + S-of-N client sampling (ISSUE 9).

Covers the coordinate-weighting reduction (conservation over actual
senders, cross-strategy agreement, the worker-mode off-switch), the
``sampled`` participation schedule, the effective-omega fixes (stale
late mass, dtype-derived renormalization floor), and the kind-specific
dropped-worker delivery semantics (DGC momentum, CoordTopK staleness)
against an independent python delivery model — the mirror tests fail on
the pre-hook simulator rewrite, which is re-created here by forcing the
base-class ``on_dropped`` onto the kind.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import comm
from repro.core import DistributedSim, SparsifierConfig
from repro.core.sparsify import CoordTopK, DGC, Sparsifier, make_sparsifier

jax.config.update("jax_platform_name", "cpu")

CODEC_NAMES = ("coo_fp32", "coo_q8")
STRATEGIES = ("dense_allreduce", "sparse_allgather", "hierarchical")


def _payload_case(W, L, k, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), W)
    vals, idxs = [], []
    for kk in ks:
        kv, ki = jax.random.split(kk)
        idx = jnp.sort(jax.random.permutation(ki, L)[:k])
        sign = jnp.sign(jax.random.normal(kv, (k,)))
        mag = 0.5 + jax.random.uniform(kv, (k,))
        vals.append(jnp.where(sign == 0, 1.0, sign) * mag)
        idxs.append(idx)
    return jnp.stack(vals), jnp.stack(idxs).astype(jnp.int32)


# ---------------------------------------------------------------------------
# the coordinate reduction: conservation + agreement + off-switch
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("cname", CODEC_NAMES)
def test_coordinate_weights_conserve_mass(cname):
    """The effective per-worker weight at coordinate j is w_n / den_j —
    summed over the workers that actually sent j it is exactly one, for
    any (non-uniform) base weights and any codec (presence is read off
    the *decoded* values, so lossy codecs conserve too)."""
    W, L, k = 5, 96, 9
    codec = comm.get_codec(cname)
    vals, idx = _payload_case(W, L, k)
    payloads = jax.vmap(lambda v, i: codec.encode(v, i, L))(vals, idx)
    w = jnp.asarray([0.4, 0.1, 0.2, 0.15, 0.15])
    agg, den = comm.get_collective("sparse_allgather").reference_coord(
        codec, payloads, w, L
    )
    dv, di = jax.vmap(lambda p: codec.decode(p, L))(payloads)
    presence = np.zeros((W, L))
    for n in range(W):
        for v, j in zip(np.asarray(dv[n]), np.asarray(di[n])):
            if v != 0:
                presence[n, j] = 1.0
    den_np = np.asarray(den)
    sent = presence.sum(axis=0) > 0
    eff = (np.asarray(w)[:, None] * presence) / np.where(
        den_np > 0, den_np, 1.0
    )
    np.testing.assert_allclose(eff.sum(axis=0)[sent], 1.0, rtol=1e-6)
    assert (den_np[~sent] == 0).all()
    assert np.asarray(jnp.isfinite(agg)).all()
    # uniform weights: den is the sender count over the round mass
    _, den_u = comm.get_collective("sparse_allgather").reference_coord(
        codec, payloads, jnp.full((W,), 1.0 / W), L
    )
    np.testing.assert_allclose(
        np.asarray(den_u), presence.sum(axis=0) / W, rtol=1e-6
    )


@pytest.mark.parametrize("cname", CODEC_NAMES)
def test_reference_coord_agrees_across_strategies(cname):
    W, L, k = 6, 64, 7
    codec = comm.get_codec(cname)
    vals, idx = _payload_case(W, L, k, seed=1)
    payloads = jax.vmap(lambda v, i: codec.encode(v, i, L))(vals, idx)
    w = jnp.full((W,), 1.0 / W)
    outs = {
        s: comm.get_collective(s).reference_coord(codec, payloads, w, L)
        for s in STRATEGIES
    }
    base_agg, base_den = outs["sparse_allgather"]
    # hierarchical's reference form is the identical flat reduction
    assert (outs["hierarchical"][0] == base_agg).all()
    assert (outs["hierarchical"][1] == base_den).all()
    np.testing.assert_allclose(
        np.asarray(outs["dense_allreduce"][0]), np.asarray(base_agg),
        rtol=1e-6, atol=1e-7,
    )
    np.testing.assert_allclose(
        np.asarray(outs["dense_allreduce"][1]), np.asarray(base_den),
        rtol=1e-6, atol=1e-7,
    )


@pytest.mark.parametrize("cname", CODEC_NAMES)
@pytest.mark.parametrize("sname", STRATEGIES)
def test_shard_coord_matches_reference_single_device(cname, sname):
    """shard_coord == reference_coord on an in-process 1-device mesh
    (the 8-device subprocess bit-for-bit check lives in
    tests/test_distributed.py)."""
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    from repro.compat import make_mesh

    L, k = 96, 8
    codec = comm.get_codec(cname)
    strategy = comm.get_collective(sname)
    vals, idx = _payload_case(1, L, k, seed=2)
    payload = codec.encode(vals[0], idx[0], L)
    stacked = jax.tree.map(lambda x: x[None], payload)
    ref_agg, ref_den = strategy.reference_coord(
        codec, stacked, jnp.ones((1,)), L
    )
    mesh = make_mesh((1,), ("data",))
    in_specs = jax.tree.map(
        lambda x: P(*(("data",) + (None,) * x.ndim)), payload
    )

    def body(p):
        local = jax.tree.map(lambda x: x[0], p)
        return strategy.shard_coord(codec, local, L, ("data",), 1.0)

    with mesh:
        got_agg, got_den = shard_map(
            body, mesh=mesh, in_specs=(in_specs,),
            out_specs=(P(None), P(None)), check_vma=False,
        )(stacked)
    np.testing.assert_allclose(
        np.asarray(got_agg), np.asarray(ref_agg), rtol=1e-6, atol=1e-7
    )
    np.testing.assert_allclose(
        np.asarray(got_den), np.asarray(ref_den), rtol=1e-6, atol=1e-7
    )


def test_worker_mode_omega_prev_ones_is_identity():
    """The off-switch argument: under worker weighting the threaded
    denominator is exactly 1.0, and dividing omega by 1.0 is the
    identity in floats — step(omega_prev=ones) is bit-for-bit
    step(omega_prev=None)."""
    J = 64
    cfg = SparsifierConfig(kind="regtopk", sparsity=0.1, mu=1.0, omega=0.25)
    sp = make_sparsifier(cfg)
    st = sp.init(J)
    g0 = jax.random.normal(jax.random.PRNGKey(0), (J,))
    _, _, st = sp.step(st, g0, jnp.zeros(J))  # past round 0 (plain top-k)
    g1 = jax.random.normal(jax.random.PRNGKey(1), (J,))
    gp = jax.random.normal(jax.random.PRNGKey(2), (J,)) * 0.1
    ghat_a, mask_a, st_a = sp.step(st, g1, gp)
    ghat_b, mask_b, st_b = sp.step(st, g1, gp, omega_prev=jnp.ones(J))
    assert (ghat_a == ghat_b).all() and (mask_a == mask_b).all()
    for x, y in zip(st_a, st_b):
        assert (x == y).all()


def test_coordinate_weighting_changes_the_aggregate():
    codec = comm.get_codec("coo_fp32")
    W, L, k = 4, 32, 4
    vals, idx = _payload_case(W, L, k, seed=3)
    payloads = jax.vmap(lambda v, i: codec.encode(v, i, L))(vals, idx)
    w = jnp.full((W,), 1.0 / W)
    strat = comm.get_collective("sparse_allgather")
    worker = strat.reference(codec, payloads, w, L)
    coord, den = strat.reference_coord(codec, payloads, w, L)
    # masks are (generically) not identical, so some coordinate has
    # den < 1 and coordinate weighting rescales it
    assert float(jnp.abs(coord - worker).max()) > 0
    # at every sent coordinate: coord = worker / den (same numerator)
    sent = np.asarray(den) > 0
    np.testing.assert_allclose(
        np.asarray(coord)[sent],
        np.asarray(worker)[sent] / np.asarray(den)[sent],
        rtol=1e-6,
    )


def test_simulator_threads_den_into_posterior():
    """Coordinate mode: SimState.w_agg_prev after a round is the den the
    server divided by, and the invalid pairings fast-fail."""
    N, J = 4, 32
    b = jax.random.normal(jax.random.PRNGKey(0), (N, J))
    grad_fn = lambda th, n: th - b[n]
    sim = DistributedSim(
        grad_fn, N, J, SparsifierConfig(kind="regtopk", sparsity=0.2),
        collective="sparse_allgather", weighting="coordinate",
    )
    state = sim.init(jnp.zeros(J))
    assert state.w_agg_prev is not None and (state.w_agg_prev == 1.0).all()
    state, _ = jax.jit(lambda s: sim.step_fn(s))(state)
    den = np.asarray(state.w_agg_prev)
    assert ((den >= 0) & (den <= 1.0 + 1e-6)).all()
    assert (den > 0).any() and (den < 1.0).any()  # partial sender sets
    # den is a multiple of 1/N (uniform weights: sender_count / N)
    np.testing.assert_allclose(den * N, np.round(den * N), atol=1e-5)
    with pytest.raises(ValueError, match="weighting"):
        DistributedSim(
            grad_fn, N, J, SparsifierConfig(kind="none"),
            weighting="coordinate",
        )
    with pytest.raises(ValueError, match="stale"):
        DistributedSim(
            grad_fn, N, J, SparsifierConfig(kind="regtopk", sparsity=0.2),
            weighting="coordinate",
            participation=comm.Participation(
                "stale", n_stragglers=1, staleness=2
            ),
        )


# ---------------------------------------------------------------------------
# the sampled schedule
# ---------------------------------------------------------------------------
def test_round_participants_common_knowledge():
    p = comm.Participation(kind="sampled", n_sampled=4, seed=3)
    seen = set()
    for r in range(6):
        w = np.asarray(p.round_participants(r, 10))
        assert w.shape == (4,) and w.dtype == np.int32
        assert (np.diff(w) > 0).all()  # sorted, no repeats
        assert w.min() >= 0 and w.max() < 10
        np.testing.assert_array_equal(
            w, np.asarray(p.round_participants(r, 10))
        )
        seen.add(tuple(w.tolist()))
    assert len(seen) > 1  # fresh subset per round
    assert p.expected_participants(10) == 4.0
    assert p.effective_omega(10) == pytest.approx(0.25)
    with pytest.raises(ValueError, match="sampled"):
        comm.Participation(
            "round_robin", n_stragglers=1
        ).round_participants(0, 10)


def test_sampled_parse_and_validate():
    p = comm.parse_participation("sampled:32,7")
    assert p.kind == "sampled" and p.n_sampled == 32 and p.seed == 7
    with pytest.raises(ValueError):
        p.validate(8)  # S > N
    comm.parse_participation("sampled:4").validate(8)


def test_effective_omega_values():
    """Regression (PR-4 omega bug): under ``stale`` a worker's expected
    accepted mass is the on-time renormalized 1/N *plus* the discounted
    late deliveries — n_s rounds out of N it lands late at discount/N."""
    N = 8
    assert comm.Participation("full").effective_omega(N) == pytest.approx(
        1 / N
    )
    assert comm.Participation(
        "sampled", n_sampled=2
    ).effective_omega(N) == pytest.approx(0.5)
    bern = comm.Participation("bernoulli", drop_rate=0.25)
    assert bern.effective_omega(N) == pytest.approx(
        1.0 / bern.expected_participants(N)
    )
    st = comm.Participation(
        "stale", n_stragglers=2, staleness=2, discount=0.5
    )
    assert st.effective_omega(N) == pytest.approx(
        1.0 / N + 2 * 0.5 / N**2
    )


# ---------------------------------------------------------------------------
# renormalize_weights dtype floor
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("dtype", ["float32", "bfloat16", "float16"])
def test_renormalize_weights_preserves_dtype(dtype):
    """Regression: the zero-mass floor was hardcoded
    ``finfo(float32).tiny`` — a non-weak f32 scalar that promoted the
    half-precision weight vectors to f32 on the way through."""
    dt = jnp.dtype(dtype)
    w = jnp.asarray([0.5, 0.125, 0.25, 0.125], dt)
    out = comm.renormalize_weights(w, jnp.asarray([1, 0, 1, 1], dt))
    assert out.dtype == dt
    np.testing.assert_allclose(
        float(out.astype(jnp.float32).sum()), 1.0, rtol=1e-2
    )
    zero = comm.renormalize_weights(w, jnp.zeros((4,), dt))
    assert zero.dtype == dt
    assert np.isfinite(np.asarray(zero.astype(jnp.float32))).all()


# ---------------------------------------------------------------------------
# kind-specific dropped-worker delivery vs an independent python model
# ---------------------------------------------------------------------------
def _topk_mask_np(score, k):
    k = min(int(k), score.shape[0])
    if k <= 0:
        return np.zeros_like(score)
    idx = np.argsort(-score, kind="stable")[:k]
    m = np.zeros_like(score)
    m[idx] = 1.0
    return m * (score > 0)


def _mirror_run(kind, part, b, steps, lr, k, momentum):
    """Round-by-round python delivery model: each worker runs its kind's
    local recursion; a dropped worker's send is simply lost — eps keeps
    the whole pre-send accumulator, while DGC's velocity and CoordTopK's
    common staleness counters advance exactly as the recursion says."""
    N, J = b.shape
    theta = np.zeros(J, np.float64)
    eps = np.zeros((N, J))
    slot = np.zeros((N, J))  # u for dgc; staleness counter for coordtopk
    g_prev = np.zeros(J)
    out = []
    for r in range(steps):
        m = np.asarray(part.round_mask(r, N), np.float64)
        w = m * (1.0 / N)
        w = w / w.sum()
        g_agg = np.zeros(J)
        for n in range(N):
            g = theta - b[n]
            if kind == "dgc":
                u = momentum * slot[n] + g
                v = eps[n] + u
                mask = _topk_mask_np(np.abs(v), k)
                ghat = mask * v
                slot[n] = (1.0 - mask) * u
                eps[n] = (v - ghat) if m[n] > 0 else v
            else:  # coordtopk
                a = eps[n] + g
                gmag = np.abs(g_prev)
                gn = gmag / max(gmag.max(), 1e-30)
                mask = _topk_mask_np(slot[n] + gn, k)
                ghat = mask * a
                slot[n] = np.where(mask > 0, 0.0, slot[n] + 1.0)
                eps[n] = (a - ghat) if m[n] > 0 else a
            if m[n] > 0:
                g_agg = g_agg + w[n] * ghat
        theta = theta - lr * g_agg
        g_prev = g_agg
        out.append(theta.copy())
    return np.stack(out)


def _sim_thetas(kind, part, b, steps, lr, sparsity, momentum):
    N, J = b.shape
    bj = jnp.asarray(b, jnp.float32)
    sim = DistributedSim(
        lambda th, n: th - bj[n], N, J,
        SparsifierConfig(kind=kind, sparsity=sparsity, momentum=momentum),
        learning_rate=lr, collective="dense_allreduce",
        participation=part,
    )
    _, tr = sim.run(jnp.zeros(J), steps, trace_fn=lambda th: th)
    return np.asarray(tr)


@pytest.mark.parametrize("kind", ["dgc", "coordtopk"])
@pytest.mark.parametrize(
    "schedule",
    [
        comm.Participation("bernoulli", drop_rate=0.4, seed=5),
        comm.Participation("round_robin", n_stragglers=1),
    ],
    ids=["bernoulli", "round_robin"],
)
def test_dropped_state_semantics_match_python_model(kind, schedule):
    """Regression (the ISSUE-9 bugfix): the simulator's dropped-worker
    rewrite assumed RegTop-k's slot layout — freezing DGC's momentum
    (re-applying velocity already folded into v) and CoordTopK's common
    staleness counters (desynchronizing the fleet's mask agreement).
    The kind-dispatched ``on_dropped`` must track the independent python
    delivery model; the pre-fix rewrite (re-created via the base-class
    hook below) must not."""
    N, J, steps, lr, k, mom = 3, 16, 8, 0.3, 3, 0.9
    rng = np.random.default_rng(0)
    b = rng.normal(size=(N, J))
    want = _mirror_run(kind, schedule, b, steps, lr, k, mom)
    got = _sim_thetas(kind, schedule, b, steps, lr, k / J, mom)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)

    # the pre-fix behavior: the generic eps/a_prev/s_prev freeze, which
    # is correct for (reg)topk but corrupts this kind's a_prev slot
    cls = {"dgc": DGC, "coordtopk": CoordTopK}[kind]
    orig = cls.on_dropped
    cls.on_dropped = Sparsifier.on_dropped
    try:
        buggy = _sim_thetas(kind, schedule, b, steps, lr, k / J, mom)
    finally:
        cls.on_dropped = orig
    assert np.abs(buggy - want).max() > 1e-3
