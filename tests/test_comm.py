"""``repro.comm`` subsystem tests (ISSUE 1 tentpole).

Round-trip property tests per codec, (codec x strategy) aggregation
equivalence against ``dense_allreduce`` in both the simulator and the
``shard_map`` runtime (subprocess CPU mesh), cost-model consistency
(measured <= predicted x 1.05), and the hard_threshold payload guard.
"""
import dataclasses
import textwrap
from typing import ClassVar

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _hyp import example, given, settings, st
from repro import comm
from repro.core import DistributedSim, SparsifierConfig, make_sparsifier
from repro.core.selectors import sparsity_to_k

CODEC_NAMES = sorted(comm.CODECS)
PAYLOAD_STRATEGIES = ["sparse_allgather", "hierarchical"]


def _payload_case(seed: int, L: int, k: int):
    """Random fixed-k payload with distinct indices + (0,0) padding tail."""
    key = jax.random.PRNGKey(seed)
    vals = jax.random.normal(key, (k,))
    idx = jax.random.choice(
        jax.random.fold_in(key, 1), L, (k,), replace=False
    ).astype(jnp.int32)
    n_pad = seed % max(k // 2, 1)
    if n_pad:
        vals = vals.at[-n_pad:].set(0.0)
        idx = idx.at[-n_pad:].set(0)
    return vals, idx


# ---------------------------------------------------------------------------
# codec round-trips (property-based, ISSUE 4 satellite: random shapes x
# sparsities x dtypes replace the old fixed-seed spot checks)
# ---------------------------------------------------------------------------
def _random_payload(seed, L, sparsity, dtype):
    """Fixed-k payload over random data: distinct indices, a (0, 0)
    padding tail, values in the requested dtype."""
    from repro.core.selectors import sparsity_to_k

    k = sparsity_to_k(L, sparsity)
    key = jax.random.PRNGKey(seed)
    vals = (
        3.0 * jax.random.normal(key, (k,), jnp.float32)
    ).astype(dtype)
    idx = jax.random.choice(
        jax.random.fold_in(key, 1), L, (k,), replace=False
    ).astype(jnp.int32)
    n_pad = seed % max(k // 2, 1)
    if n_pad:
        vals = vals.at[-n_pad:].set(0)
        idx = idx.at[-n_pad:].set(0)
    return vals, idx, k


LOSSLESS_NAMES = [n for n in CODEC_NAMES if comm.get_codec(n).lossless]


@pytest.mark.parametrize("name", LOSSLESS_NAMES)
@settings(max_examples=20, deadline=None)
@given(
    st.integers(0, 2**31 - 1),
    st.integers(2, 4096),
    st.floats(0.001, 0.9),
    st.sampled_from(["float32", "bfloat16"]),
)
def test_lossless_codec_roundtrip_is_exact(name, seed, L, sparsity, dtype):
    """encode -> decode preserves the scattered contribution *exactly* for
    every lossless codec, over random lengths, sparsities and value
    dtypes. Decode may reorder coordinates and merge (0, 0) padding slots;
    neither changes the scatter-add result by even one ulp (adding 0.0 is
    exact, and distinct indices never collide)."""
    vals, idx, k = _random_payload(seed, L, sparsity, jnp.dtype(dtype))
    codec = comm.get_codec(name)
    # the wire carries f32 values: the reference is the f32-cast scatter
    ref = jnp.zeros(L).at[idx].add(vals.astype(jnp.float32))
    dv, di = codec.decode(codec.encode(vals, idx, L), L)
    assert dv.dtype == jnp.float32
    got = jnp.zeros(L).at[di].add(dv)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))


@settings(max_examples=20, deadline=None)
@given(
    st.integers(0, 2**31 - 1),
    st.integers(2, 4096),
    st.floats(0.001, 0.9),
    st.sampled_from(["float32", "bfloat16"]),
)
# a bfloat16 payload whose scale, rounded down in bfloat16, clipped the
# largest value past 127 steps
@example(195, 91, 0.5, "bfloat16")
def test_q8_roundtrip_error_bounded_by_quantization_step(
    seed, L, sparsity, dtype
):
    """coo_q8's per-coordinate round-trip error is bounded by half its
    quantization step (scale = max|v| / 127 in float32, symmetric
    round-to-nearest), and the indices come back exactly."""
    vals, idx, k = _random_payload(seed, L, sparsity, jnp.dtype(dtype))
    c = comm.get_codec("coo_q8")
    p = c.encode(vals, idx, L)
    dv, di = c.decode(p, L)
    np.testing.assert_array_equal(np.asarray(di), np.asarray(idx))
    v32 = np.asarray(vals.astype(jnp.float32))
    amax = float(np.max(np.abs(v32)))
    step = (amax / 127.0) if amax > 0 else 1.0
    err = np.max(np.abs(np.asarray(dv) - v32))
    assert err <= step / 2 + 1e-7 * max(amax, 1.0)


@pytest.mark.parametrize("name", CODEC_NAMES)
def test_codec_static_shapes_and_bit_accounting(name):
    """Payload shapes/dtypes depend only on (L, k), and the wire_bits
    accounting matches the actual encoded buffer sizes exactly."""
    L, k = 200, 16
    codec = comm.get_codec(name)
    shapes = set()
    for seed in range(3):
        vals, idx = _payload_case(seed, L, k)
        p = codec.encode(vals, idx, L)
        shapes.add(
            tuple((kk, v.shape, str(v.dtype)) for kk, v in sorted(p.items()))
        )
        assert comm.payload_nbytes(p) * 8 == codec.wire_bits(L, k)
    assert len(shapes) == 1  # data-independent (XLA-static) layout
    # eval_shape agrees without running the encoder
    ab = jax.eval_shape(
        lambda v, i: codec.encode(v, i, L),
        jax.ShapeDtypeStruct((k,), jnp.float32),
        jax.ShapeDtypeStruct((k,), jnp.int32),
    )
    assert comm.payload_nbytes(ab) * 8 == codec.wire_bits(L, k)


def test_coo_idx_delta_narrows_index_dtype():
    assert comm.delta_index_dtype(100) == jnp.int8
    assert comm.delta_index_dtype(1000) == jnp.int16
    assert comm.delta_index_dtype(2**20) == jnp.int32
    L, k = 1000, 32
    c = comm.get_codec("coo_idx_delta")
    assert c.wire_bits(L, k) < comm.get_codec("coo_fp32").wire_bits(L, k)


def test_bitmap_dense_wins_above_one_32nd_sparsity():
    L = 3200
    coo = comm.get_codec("coo_fp32")
    bm = comm.get_codec("bitmap_dense")
    assert bm.wire_bits(L, L // 16) < coo.wire_bits(L, L // 16)  # S = 1/16
    assert bm.wire_bits(L, L // 320) > coo.wire_bits(L, L // 320)  # S « 1/32


# ---------------------------------------------------------------------------
# (codec x strategy) reference equivalence vs dense
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("cname", CODEC_NAMES)
@pytest.mark.parametrize("sname", PAYLOAD_STRATEGIES)
def test_reference_aggregation_matches_dense(cname, sname):
    N, L, k = 4, 120, 10
    vals = jnp.stack([_payload_case(s, L, k)[0] for s in range(N)])
    idx = jnp.stack([_payload_case(s, L, k)[1] for s in range(N)])
    w = jnp.full((N,), 1.0 / N)
    ref = jnp.zeros(L)
    for n in range(N):
        ref = ref.at[idx[n]].add(vals[n] / N)
    codec = comm.get_codec(cname)
    payloads = jax.vmap(lambda v, i: codec.encode(v, i, L))(vals, idx)
    got = comm.get_collective(sname).reference(codec, payloads, w, L)
    rel = float(jnp.max(jnp.abs(got - ref))) / (
        float(jnp.max(jnp.abs(ref))) or 1.0
    )
    assert rel < (1e-6 if codec.lossless else 1e-2)


@pytest.mark.parametrize("cname", CODEC_NAMES)
@pytest.mark.parametrize(
    "sname", ["dense_allreduce", "sparse_allgather", "hierarchical"]
)
def test_shard_form_matches_reference_single_device(cname, sname):
    """Collective.shard == Collective.reference on an in-process 1-device
    mesh (axis size 1: the gather/psum are identities, so the shard-form
    plumbing — including the participation hook — is checked without a
    subprocess device farm)."""
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    from repro.compat import make_mesh

    L, k = 96, 8
    codec = comm.get_codec(cname)
    strategy = comm.get_collective(sname)
    vals, idx = _payload_case(3, L, k)
    payload = codec.encode(vals, idx, L)
    stacked = jax.tree.map(lambda x: x[None], payload)
    ref = strategy.reference(
        codec, stacked, jnp.ones((1,)), L
    )
    mesh = make_mesh((1,), ("data",))
    in_specs = jax.tree.map(
        lambda x: P(*(("data",) + (None,) * x.ndim)), payload
    )

    def body(p):
        local = jax.tree.map(lambda x: x[0], p)
        full = strategy.shard(codec, local, L, ("data",), 1.0)
        part = strategy.shard(
            codec, local, L, ("data",), 1.0, participation=jnp.float32(1.0)
        )
        return full, part

    with mesh:
        got_full, got_part = shard_map(
            body,
            mesh=mesh,
            in_specs=(in_specs,),
            out_specs=(P(None), P(None)),
            check_vma=False,
        )(stacked)
    np.testing.assert_allclose(
        np.asarray(got_full), np.asarray(ref), rtol=1e-6, atol=1e-7
    )
    # a unit participation mask must not change the shard-form numerics
    np.testing.assert_allclose(
        np.asarray(got_part), np.asarray(got_full), rtol=1e-6, atol=1e-7
    )


# ---------------------------------------------------------------------------
# simulator end-to-end: every pair matches dense_allreduce training
# ---------------------------------------------------------------------------
def _toy_setup():
    x = jnp.array([[100.0, 1.0], [-100.0, 1.0]])

    def grad_fn(theta, n):
        xn = x[n]
        e = jnp.exp(-jnp.dot(theta, xn))
        return -e * xn / (1 + e)

    return grad_fn


@pytest.mark.parametrize("cname", CODEC_NAMES)
@pytest.mark.parametrize("sname", PAYLOAD_STRATEGIES)
def test_simulator_codec_strategy_matches_dense(cname, sname):
    grad_fn = _toy_setup()
    cfg = SparsifierConfig(kind="regtopk", sparsity=0.5, mu=1.0)
    ref_sim = DistributedSim(grad_fn, 2, 2, cfg, learning_rate=0.9)
    fin_ref, _ = ref_sim.run(jnp.array([0.0, 1.0]), 30)
    sim = DistributedSim(
        grad_fn, 2, 2, cfg, learning_rate=0.9, codec=cname, collective=sname
    )
    fin, _ = sim.run(jnp.array([0.0, 1.0]), 30)
    ref = np.asarray(fin_ref.theta)
    rel = np.max(np.abs(np.asarray(fin.theta) - ref)) / max(
        np.max(np.abs(ref)), 1e-30
    )
    assert rel < (1e-5 if comm.get_codec(cname).lossless else 1e-2)


def test_simulator_q8_error_feedback_converges():
    """With the quantization residual folded into eps, q8 training tracks
    the exact run; without feedback the bias would accumulate."""
    grad_fn = _toy_setup()
    cfg = SparsifierConfig(kind="regtopk", sparsity=0.5, mu=1.0)

    def loss(theta):
        x = jnp.array([[100.0, 1.0], [-100.0, 1.0]])
        return float(jnp.mean(jnp.log(1 + jnp.exp(-x @ theta))))

    sim = DistributedSim(
        grad_fn, 2, 2, cfg, learning_rate=0.9,
        codec="coo_q8", collective="sparse_allgather",
    )
    fin, _ = sim.run(jnp.array([0.0, 1.0]), 60)
    assert loss(fin.theta) < 0.05  # same convergence bar as the fig1 test


def test_none_sparsifier_payload_collective_stays_dense():
    """kind='none' has no fixed-k payload; with a payload collective the
    simulator must aggregate the full dense gradient (like _spa_leaf), not
    silently truncate it to k coordinates (regression)."""
    grad_fn = _toy_setup()
    cfg = SparsifierConfig(kind="none", sparsity=0.5)
    ref = DistributedSim(grad_fn, 2, 2, cfg, learning_rate=0.9)
    sim = DistributedSim(
        grad_fn, 2, 2, cfg, learning_rate=0.9,
        collective="sparse_allgather",
    )
    st_ref, st = ref.init(jnp.array([0.0, 1.0])), sim.init(
        jnp.array([0.0, 1.0])
    )
    _, g_ref = ref.step_fn(st_ref)
    _, g = sim.step_fn(st)
    np.testing.assert_allclose(np.asarray(g), np.asarray(g_ref), rtol=1e-6)


def test_dense_wire_bytes_track_state_dtype():
    """bf16 eps state psums a bf16 vector — comm_bytes must halve, not
    assume 4-byte words (regression)."""
    from jax.sharding import PartitionSpec as P

    from repro.core.distributed import (
        DistConfig,
        LeafPlan,
        comm_round_bytes,
    )

    class _Mesh:
        shape: ClassVar[dict] = {"data": 4}

    plan = LeafPlan((64,), (64,), 64, 4, P(None))
    f32 = DistConfig(aggregation="dense_allreduce", state_dtype="float32")
    bf16 = DistConfig(aggregation="dense_allreduce", state_dtype="bfloat16")
    p32, m32 = comm_round_bytes(plan, f32, _Mesh())
    p16, m16 = comm_round_bytes(plan, bf16, _Mesh())
    assert (p16, m16) == (p32 // 2, m32 // 2)
    # kind="none" pmeans in f32 regardless of state dtype
    none16 = dataclasses.replace(
        bf16, sparsifier=SparsifierConfig(kind="none")
    )
    assert comm_round_bytes(plan, none16, _Mesh()) == (p32, m32)


def test_hard_threshold_payload_collective_raises():
    grad_fn = _toy_setup()
    cfg = SparsifierConfig(kind="hard_threshold", threshold=0.1)
    with pytest.raises(ValueError, match="hard_threshold"):
        DistributedSim(grad_fn, 2, 2, cfg, collective="sparse_allgather")
    with pytest.raises(ValueError, match="hard_threshold"):
        DistributedSim(grad_fn, 2, 2, cfg, aggregation="sparse_allgather")
    # dense aggregation stays supported
    DistributedSim(grad_fn, 2, 2, cfg)


def test_q8_sim_state_matches_compact_runtime_state():
    """The dense-state simulator path and the compact distributed-runtime
    path must evolve identically under a lossy codec: eps carries the
    quantization residual and RegTop-k conditions on the *decoded* payload
    in both (regression for the a_prev/sent_vals mismatch)."""
    from repro.core import compact as C
    from repro.core.selectors import mask_to_payload

    L, k, steps = 32, 4, 8
    cfg = SparsifierConfig(kind="regtopk", sparsity=k / L, mu=1.0, omega=0.5)
    codec = comm.get_codec("coo_q8")
    sp = make_sparsifier(cfg)
    dense_st = sp.init(L)
    comp_st = C.compact_init(L, k)
    g_prev = jnp.zeros(L)
    key = jax.random.PRNGKey(0)
    for t in range(steps):
        key, sk = jax.random.split(key)
        g = jax.random.normal(sk, (L,))
        # dense-state path (simulator algebra)
        ghat, mask, new_ws = sp.step(dense_st, g, g_prev)
        vals, idx = mask_to_payload(mask, ghat, k)
        dv, di = codec.decode(codec.encode(vals, idx, L), L)
        sent = jnp.zeros(L).at[di].add(dv)
        intended = jnp.zeros(L).at[idx].add(vals)
        delta = sent - intended
        dense_st = sp.on_wire_residual(new_ws, delta)
        # compact path (distributed runtime algebra)
        a, cvals, cidx = C.compact_select(cfg, comp_st, g, k)
        cdv, cdi = codec.decode(codec.encode(cvals, cidx, L), L)
        csent = jnp.zeros(L).at[cdi].add(cdv)
        agg = 0.5 * csent
        comp_st = C.compact_finalize_sent(comp_st, a, cdv, cdi, csent, agg)
        g_prev = agg
        assert bool((jnp.sort(cidx) == jnp.sort(idx)).all()), f"mask @ t={t}"
        np.testing.assert_allclose(
            np.asarray(comp_st.eps), np.asarray(dense_st.eps), atol=1e-6
        )


# ---------------------------------------------------------------------------
# cost model
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("cname", CODEC_NAMES)
@pytest.mark.parametrize(
    "sname", ["dense_allreduce", "sparse_allgather", "hierarchical"]
)
def test_measured_within_predicted(cname, sname):
    L, k, dp = 512, 16, (4, 2)
    codec = comm.get_codec(cname)
    vals, idx = _payload_case(0, L, k)
    payload = codec.encode(vals, idx, L)
    pred = comm.predicted_bytes(codec, sname, L, k, dp)
    meas = comm.measured_bytes(sname, L, payload, dp)
    assert meas <= pred * 1.05
    est = comm.predict(codec, sname, L, k, dp)
    assert est.bytes_on_wire == pred
    assert est.seconds > 0 and est.n_messages > 0


def test_hierarchical_compresses_the_outer_slow_axes():
    """Mesh dp axes are ordered outermost (slow) first — ("pod", "data").
    Hierarchical must move *payloads* over the outer axes and the dense
    vector only over the innermost fast axis: growing the outer axis must
    not grow the dense term."""
    L, k = 100_000, 100
    pb = comm.get_codec("coo_fp32").wire_bits(L, k) // 8
    dense_term = lambda a: 2 * (a - 1) / a * L * 4
    two_pods = comm.predicted_bytes(
        "coo_fp32", "hierarchical", L, k, (2, 8)
    )
    four_pods = comm.predicted_bytes(
        "coo_fp32", "hierarchical", L, k, (4, 8)
    )
    # inter (outer, pod) term is payload-sized; intra (inner, data) is dense
    assert two_pods == int(np.ceil((2 - 1) * pb + dense_term(8)))
    assert four_pods - two_pods == 2 * pb  # only payload bytes grow


def test_sparse_beats_dense_at_low_sparsity():
    L, N = 100_000, 16
    k = sparsity_to_k(L, 0.001)
    dense = comm.predicted_bytes("coo_fp32", "dense_allreduce", L, k, (N,))
    sparse = comm.predicted_bytes("coo_fp32", "sparse_allgather", L, k, (N,))
    assert sparse < dense


def test_wire_words_from_codec_wire_bits():
    # the removed ``cost.wire_words_per_worker`` shim's word counts fall
    # straight out of ``Codec.wire_bits`` (migration recipe: docs/comm.md)
    # — dense ships L f32 words, the fp32-COO allgather 2*k words/worker.
    L, k, N = 1000, 10, 4
    assert comm.get_codec("coo_fp32").wire_bits(L, k) * N // 32 == 80
    dense_words = L  # the dense vector itself, one f32 word per coord
    assert dense_words == 1000
    # and the shim's ValueError on unknown modes lives on in the registry
    with pytest.raises(ValueError, match="codec"):
        comm.get_codec("bogus")


# ---------------------------------------------------------------------------
# DGC momentum is config-threaded (ISSUE 1 satellite)
# ---------------------------------------------------------------------------
def test_dgc_momentum_from_config():
    g = jnp.array([4.0, -3.0, 1.0, 0.5])
    for m in (0.0, 0.5, 0.9):
        sp = make_sparsifier(
            SparsifierConfig(kind="dgc", sparsity=0.5, momentum=m)
        )
        state = sp.init(4)
        _, _, s1 = sp.step(state, g, jnp.zeros(4))
        g2 = jnp.array([0.0, 0.0, 1.0, 0.0])
        ghat2, _, _ = sp.step(s1, g2, jnp.zeros(4))
        # round 1 at idx 2: v = eps + (m*u + g2) = 1 + m*1 + 1
        np.testing.assert_allclose(
            float(ghat2[2]), 2.0 + m, rtol=1e-6
        )


# ---------------------------------------------------------------------------
# shard_map runtime equivalence (subprocess, 8 forced CPU devices)
# ---------------------------------------------------------------------------
SUB_CODE = textwrap.dedent(
    """
    import json
    import jax, jax.numpy as jnp
    from repro.compat import make_mesh
    mesh = make_mesh((4, 2), ("data", "model"))
    from repro.models import ModelConfig, get_family
    from repro.core.distributed import (DistConfig, assemble,
                                        comm_round_bytes,
                                        init_sparsifier_state)
    from repro.core.sparsify import SparsifierConfig
    from repro.optim import OptConfig, make_optimizer
    from repro.data import TokenPipeline

    cfg = ModelConfig(name="tiny", family="dense", n_layers=2, d_model=64,
                      n_heads=4, n_kv_heads=2, head_dim=16, d_ff=128,
                      vocab=256, remat=False)
    mod = get_family(cfg)

    def train(codec, collective, steps=8):
        dist = DistConfig(
            sparsifier=SparsifierConfig(kind="regtopk", sparsity=0.05,
                                        mu=1.0),
            optimizer=OptConfig(kind="adam", learning_rate=3e-3),
            codec=codec, collective=collective, microbatches=1,
            dp_axes=("data",))
        asm = assemble(mod, cfg, dist, mesh)
        params, _ = mod.init(jax.random.PRNGKey(0), cfg)
        opt = make_optimizer(dist.optimizer)
        opt_state = opt.init(params)
        sp_state, _ = init_sparsifier_state(asm.plan, 4, mesh, ("data",),
                                            jnp.float32)
        pipe = TokenPipeline(cfg, global_batch=8, seq=32)
        step = jax.jit(asm.train_step)
        losses = []
        with mesh:
            for t in range(steps):
                params, opt_state, sp_state, m = step(
                    params, opt_state, sp_state, pipe.batch_at(t))
                losses.append(float(m["loss"]))
        pred, meas = comm_round_bytes(asm.plan, dist, mesh)
        return losses, (meas, pred)

    ref, _ = train("coo_fp32", "dense_allreduce")
    out = {}
    for codec in {CODECS}:
        for coll in {STRATEGIES}:
            l, (meas, pred) = train(codec, coll)
            out[codec + "/" + coll] = {
                "diff": max(abs(a - b) for a, b in zip(ref, l)),
                "meas": meas, "pred": pred,
                "lossless": codec != "coo_q8"}
    print(json.dumps(out))
    """
)


@pytest.mark.parametrize("group", [0, 1])
def test_shard_map_codec_strategy_matches_dense(group):
    """Every (codec, strategy) pair matches dense_allreduce in the real
    shard_map runtime, and measured wire bytes stay within the prediction.
    Split into two subprocesses to keep per-case compile time bounded."""
    from tests.test_distributed import run_sub

    codecs = (
        ["coo_fp32", "coo_idx_delta"] if group == 0
        else ["bitmap_dense", "coo_q8"]
    )
    code = SUB_CODE.replace("{CODECS}", repr(codecs)).replace(
        "{STRATEGIES}", repr(PAYLOAD_STRATEGIES)
    )
    res = run_sub(code)
    assert set(res) == {
        f"{c}/{s}" for c in codecs for s in PAYLOAD_STRATEGIES
    }
    for name, r in res.items():
        tol = 1e-4 if r["lossless"] else 1e-2
        assert r["diff"] < tol, f"{name}: loss diverged by {r['diff']}"
        assert r["meas"] <= r["pred"] * 1.05, f"{name}: wire accounting"
