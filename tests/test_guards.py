"""Runtime guards paired with reprolint (ISSUE 7 tentpole):

* retrace counters (the chex ``assert_max_traces`` idiom, implemented
  locally so CI needs no extra dependency) asserting the
  ``DistributedSim`` and ``make_sparsify_aggregate`` round loops compile
  exactly once across rounds and participation schedules — a silent
  per-round retrace is a throughput bug no numeric test catches;
* a shard-safety smoke running every collective under a *renamed* mesh
  axis, proving no hardcoded axis name survives anywhere in the payload
  path (the runtime twin of RPL102);
* ``compact_select`` fastpath on/off/auto routing.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import shard_map
from jax.sharding import PartitionSpec as P

from repro import comm
from repro.compat import make_mesh
from repro.core.simulator import DistributedSim
from repro.core.sparsify import SparsifierConfig


def counting(fn):
    """Python-side trace counter: the body runs once per trace, so the
    counter equals the number of compilations of the jitted wrapper."""
    calls = {"n": 0}

    def wrapper(*args, **kwargs):
        calls["n"] += 1
        return fn(*args, **kwargs)

    return wrapper, calls


# ---------------------------------------------------------------------------
# retrace guards
# ---------------------------------------------------------------------------
N, L = 4, 64


def _sim(collective, kind, participation=None, **kw):
    return DistributedSim(
        grad_fn=lambda theta, i: theta * (1.0 + i) - 0.1,
        n_workers=N,
        length=L,
        sparsifier_cfg=SparsifierConfig(kind=kind, sparsity=8 / L),
        aggregation=collective,
        participation=participation,
        **kw,
    )


@pytest.mark.parametrize(
    "collective,kind,participation",
    [
        ("dense_allreduce", "topk", None),
        ("sparse_allgather", "regtopk", None),
        (
            "sparse_allgather",
            "regtopk",
            comm.Participation("round_robin", n_stragglers=1),
        ),
        (
            "sparse_allgather",
            "regtopk",
            comm.Participation("bernoulli", drop_rate=0.5, seed=3),
        ),
        (
            "dense_allreduce",
            "regtopk",
            comm.Participation(
                "stale", n_stragglers=1, staleness=2, discount=0.5
            ),
        ),
    ],
    ids=["dense-topk", "spa-regtopk", "round_robin", "bernoulli", "stale"],
)
def test_sim_round_loop_compiles_once(collective, kind, participation):
    """5 rounds of the simulator step under one jit wrapper: exactly one
    trace, including when the participation mask varies per round (the
    round index is part of traced state, so schedule changes must not
    retrace)."""
    sim = _sim(collective, kind, participation)
    counted, calls = counting(sim.step_fn)
    step = jax.jit(counted)
    state = sim.init(jnp.linspace(1.0, 2.0, L))
    for _ in range(5):
        state, g_agg = step(state)
    jax.block_until_ready(g_agg)
    assert calls["n"] == 1, (
        f"step_fn retraced: {calls['n']} traces over 5 rounds"
    )
    assert int(state.step) == 5


def test_sim_distinct_configs_compile_separately():
    """The guard has teeth: a genuinely different config is a different
    compilation (counter 1 each), not a cache hit on the first."""
    for kind in ("topk", "regtopk"):
        sim = _sim("sparse_allgather", kind)
        counted, calls = counting(sim.step_fn)
        # one jit per config is the point here
        step = jax.jit(counted)  # reprolint: disable=RPL104
        state = sim.init(jnp.ones((L,)))
        for _ in range(3):
            state, _ = step(state)
        assert calls["n"] == 1


def test_make_sparsify_aggregate_round_loop_compiles_once():
    """4 rounds through the shard_map aggregation on an in-process (1,1)
    mesh: one trace, with the compact state's round counter advancing."""
    from repro.core.distributed import (
        DistConfig,
        LeafPlan,
        init_sparsifier_state,
        make_sparsify_aggregate,
    )

    mesh = make_mesh((1, 1), ("data", "model"))
    dist = DistConfig(
        sparsifier=SparsifierConfig(kind="regtopk", sparsity=8 / 256),
        codec="coo_fp32",
        collective="sparse_allgather",
        dp_axes=("data",),
    )
    plan = {"w": LeafPlan((256,), (256,), 256, 8, P(None), fused=False)}
    state, _specs = init_sparsifier_state(
        plan, 1, mesh, ("data",), jnp.float32
    )
    spa = make_sparsify_aggregate(
        mesh, plan, {"w": P(None)}, _specs, dist, 1
    )
    counted, calls = counting(spa)
    step = jax.jit(counted)
    grads = {"w": jnp.linspace(-1.0, 1.0, 256).reshape(1, 256)}
    with mesh:
        for _ in range(4):
            agg, state, _ = step(grads, state)
    jax.block_until_ready(agg)
    assert calls["n"] == 1, (
        f"make_sparsify_aggregate retraced: {calls['n']} traces in 4 rounds"
    )
    assert int(state["w"].t[0]) == 4


# ---------------------------------------------------------------------------
# shard-safety smoke: renamed mesh axis (runtime twin of RPL102)
# ---------------------------------------------------------------------------
@pytest.mark.parametrize(
    "sname", ["dense_allreduce", "sparse_allgather", "hierarchical"]
)
def test_collectives_survive_renamed_axis(sname):
    """Every collective's shard form must run under a mesh whose axis is
    named something no repo module ever mentions — any hardcoded axis
    name in the payload path would raise NameError at trace time."""
    L, k = 96, 8
    axis = "zz9_renamed"
    codec = comm.get_codec("coo_fp32")
    strategy = comm.get_collective(sname)
    rng = np.random.default_rng(0)
    vals = jnp.asarray(rng.normal(size=(k,)), jnp.float32)
    idx = jnp.asarray(rng.choice(L, size=(k,), replace=False), jnp.int32)
    payload = codec.encode(vals, idx, L)
    stacked = jax.tree.map(lambda x: x[None], payload)
    ref = strategy.reference(codec, stacked, jnp.ones((1,)), L)

    mesh = make_mesh((1,), (axis,))
    in_specs = jax.tree.map(
        lambda x: P(*((axis,) + (None,) * x.ndim)), payload
    )

    def body(p):
        local = jax.tree.map(lambda x: x[0], p)
        return strategy.shard(codec, local, L, (axis,), 1.0)

    with mesh:
        got = shard_map(
            body,
            mesh=mesh,
            in_specs=(in_specs,),
            out_specs=P(None),
            check_vma=False,
        )(stacked)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(ref), rtol=1e-6, atol=1e-7
    )


# ---------------------------------------------------------------------------
# compact_select fastpath routing
# ---------------------------------------------------------------------------
FUSABLE_L, FUSABLE_K = 8192, 8


def _compact_inputs(dtype=jnp.float32):
    from repro.core import compact as C

    st = C.compact_init(FUSABLE_L, FUSABLE_K, dtype=dtype)
    g = jnp.asarray(
        np.random.default_rng(1).normal(size=(FUSABLE_L,)), dtype
    )
    return st, g


def _route_recorder(monkeypatch):
    import repro.comm.fastpath as fp

    hits = {"n": 0}

    def fake_fused(scfg, st, g, k, *, interpret=None):
        hits["n"] += 1
        a = st.eps + g.astype(st.eps.dtype)
        return (
            a, jnp.zeros((k,), a.dtype), jnp.zeros((k,), jnp.int32),
            jnp.bool_(False),
        )

    monkeypatch.setattr(fp, "fused_compact_select", fake_fused)
    return hits


def test_fastpath_on_routes_to_fused(monkeypatch):
    from repro.core import compact as C

    hits = _route_recorder(monkeypatch)
    cfg = SparsifierConfig(kind="topk", sparsity=FUSABLE_K / FUSABLE_L)
    st, g = _compact_inputs()
    C.compact_select(cfg, st, g, FUSABLE_K, fastpath="on")
    assert hits["n"] == 1


def test_fastpath_off_and_none_stay_dense(monkeypatch):
    from repro.core import compact as C

    hits = _route_recorder(monkeypatch)
    cfg = SparsifierConfig(kind="topk", sparsity=FUSABLE_K / FUSABLE_L)
    st, g = _compact_inputs()
    a1, v1, i1 = C.compact_select(cfg, st, g, FUSABLE_K, fastpath="off")
    a2, v2, i2 = C.compact_select(cfg, st, g, FUSABLE_K, fastpath=None)
    assert hits["n"] == 0
    np.testing.assert_array_equal(np.asarray(v1), np.asarray(v2))
    np.testing.assert_array_equal(np.asarray(i1), np.asarray(i2))


def test_fastpath_auto_declines_off_tpu(monkeypatch):
    from repro.core import compact as C

    hits = _route_recorder(monkeypatch)
    cfg = SparsifierConfig(kind="topk", sparsity=FUSABLE_K / FUSABLE_L)
    st, g = _compact_inputs()
    C.compact_select(cfg, st, g, FUSABLE_K, fastpath="auto")
    if jax.default_backend() != "tpu":
        assert hits["n"] == 0


def test_fastpath_on_declines_non_fusable_state(monkeypatch):
    # non-f32 state never fuses (the kernel scores in f32 — not
    # bit-for-bit against a bf16 dense path), even when forced "on".
    from repro.core import compact as C

    hits = _route_recorder(monkeypatch)
    cfg = SparsifierConfig(kind="topk", sparsity=FUSABLE_K / FUSABLE_L)
    st, g = _compact_inputs(dtype=jnp.bfloat16)
    C.compact_select(cfg, st, g, FUSABLE_K, fastpath="on")
    assert hits["n"] == 0


def test_fastpath_unknown_mode_raises():
    from repro.core import compact as C

    cfg = SparsifierConfig(kind="topk", sparsity=FUSABLE_K / FUSABLE_L)
    st, g = _compact_inputs()
    with pytest.raises(ValueError, match="unknown fastpath"):
        C.compact_select(cfg, st, g, FUSABLE_K, fastpath="bogus")
