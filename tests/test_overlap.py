"""Bucketed overlap scheduler tests (ISSUE 10).

Property tests (hypothesis, or the ``_hyp`` fallback shim) over the
bin-pack + timeline math, spec/validation gates, and the off-switch
guarantee: in-process bucketed aggregation must be bit-for-bit identical
to ``overlap="off"`` across codecs — the schedule may only *reorder* the
independent per-leaf rounds. The real 8-device differential lives in
``tests/test_distributed.py``.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hyp import given, settings, st

from repro import comm
from repro.comm.overlap import (
    Bucket,
    LeafCost,
    OverlapConfig,
    bucketize,
    overlap_timeline,
    parse_overlap,
)

# ---------------------------------------------------------------------------
# spec / config gates
# ---------------------------------------------------------------------------


def test_parse_overlap_grammar():
    assert parse_overlap("off") is None
    assert parse_overlap(" off ") is None
    assert parse_overlap("buckets:1").n_buckets == 1
    assert parse_overlap("buckets:16").n_buckets == 16
    with pytest.raises(ValueError, match="n_buckets"):
        parse_overlap("buckets:0")
    with pytest.raises(ValueError, match="not an int"):
        parse_overlap("buckets:x")
    with pytest.raises(ValueError, match="unknown overlap spec"):
        parse_overlap("stream")


def test_overlap_config_validation():
    with pytest.raises(ValueError, match="balance_factor"):
        OverlapConfig(balance_factor=0.5)
    with pytest.raises(ValueError, match="min_bucket_bytes"):
        OverlapConfig(min_bucket_bytes=-1)
    with pytest.raises(ValueError, match="max_bucket_bytes"):
        OverlapConfig(min_bucket_bytes=100, max_bucket_bytes=50)


def test_bucketize_input_validation():
    with pytest.raises(ValueError, match="at least one leaf"):
        bucketize([])
    mixed = [LeafCost(1, (1.0,)), LeafCost(1, (1.0, 2.0))]
    with pytest.raises(ValueError, match="same dp axes"):
        bucketize(mixed)


def test_timeline_compute_seconds_validation():
    plan = bucketize([LeafCost(10, (1e-3,))])
    with pytest.raises(ValueError, match="1 buckets"):
        overlap_timeline(plan, [0.1, 0.2])
    with pytest.raises(ValueError, match="non-negative"):
        overlap_timeline(plan, [-1.0])


# ---------------------------------------------------------------------------
# bin-pack + timeline properties
# ---------------------------------------------------------------------------

_costs_strategy = st.lists(
    st.floats(min_value=1e-6, max_value=1.0), min_size=1, max_size=24
)


def _as_costs(seconds, n_axes=2):
    # split each leaf's seconds across axes deterministically (60/40)
    out = []
    for i, s in enumerate(seconds):
        ax = (
            (0.6 * s, 0.4 * s) if n_axes == 2 else (s,)
        )
        out.append(LeafCost(int(1e4 * s) + 1, ax, ("c", "h")))
    return out


@settings(max_examples=40, deadline=None)
@given(_costs_strategy, st.integers(min_value=1, max_value=8))
def test_bucketize_partitions_exactly(seconds, n_buckets):
    costs = _as_costs(seconds)
    plan = bucketize(costs, OverlapConfig(n_buckets=n_buckets))
    order = sorted(plan.leaf_order())
    assert order == list(range(len(costs)))
    assert plan.n_leaves == len(costs)
    # buckets launch in ascending smallest-leaf order
    firsts = [min(b.leaves) for b in plan.buckets]
    assert firsts == sorted(firsts)


@settings(max_examples=40, deadline=None)
@given(_costs_strategy, st.integers(min_value=1, max_value=8))
def test_bucketize_balance_bound(seconds, n_buckets):
    costs = _as_costs(seconds)
    cfg = OverlapConfig(n_buckets=n_buckets)
    plan = bucketize(costs, cfg)
    total = sum(c.seconds for c in costs)
    max_leaf = max(c.seconds for c in costs)
    ideal = max(total / plan.n_buckets, max_leaf)
    assert (
        plan.n_buckets == 1
        or max(b.seconds for b in plan.buckets)
        <= cfg.balance_factor * ideal + 1e-6
    )


@settings(max_examples=40, deadline=None)
@given(_costs_strategy, st.integers(min_value=1, max_value=8))
def test_timeline_never_exceeds_sync(seconds, n_buckets):
    costs = _as_costs(seconds)
    plan = bucketize(costs, OverlapConfig(n_buckets=n_buckets))
    tl = overlap_timeline(plan)
    assert tl.seconds <= tl.sync_seconds + 1e-12
    # stamps are monotone and self-consistent
    assert all(
        lo <= mid <= hi
        for lo, mid, hi in zip(tl.launch, tl.intra_done, tl.complete)
    )


@settings(max_examples=20, deadline=None)
@given(_costs_strategy)
def test_timeline_single_bucket_equals_sync(seconds):
    plan = bucketize(_as_costs(seconds), OverlapConfig(n_buckets=1))
    tl = overlap_timeline(plan)
    assert tl.seconds == tl.sync_seconds


def test_timeline_strict_win_on_slow_outer_topo():
    """Two equal buckets with a dominant inter stage: bucket 1's intra
    work hides behind bucket 0's inter drain — strictly faster."""
    costs = [LeafCost(100, (2e-3, 1e-3)), LeafCost(100, (2e-3, 1e-3))]
    tl = overlap_timeline(bucketize(costs, OverlapConfig(n_buckets=2)))
    assert tl.seconds < tl.sync_seconds
    # exactly one intra stage (1ms) is hidden
    assert np.isclose(tl.sync_seconds - tl.seconds, 1e-3)


def test_bucket_stage_split():
    b = Bucket(
        leaves=(0,), seconds=3.0, bytes_on_wire=1,
        axis_seconds=(2.0, 1.0),
    )
    assert b.inter_seconds == 2.0
    assert b.intra_seconds == 1.0


def test_min_bucket_bytes_merges():
    costs = [LeafCost(10, (1e-3,)) for _ in range(6)]
    plan = bucketize(
        costs, OverlapConfig(n_buckets=3, min_bucket_bytes=1000)
    )
    assert plan.n_buckets == 1
    assert sorted(plan.leaf_order()) == list(range(6))


def test_max_bucket_bytes_steers():
    costs = [LeafCost(100, (1e-3,)) for _ in range(4)]
    plan = bucketize(
        costs, OverlapConfig(n_buckets=4, max_bucket_bytes=100)
    )
    assert plan.n_buckets == 4
    assert all(b.bytes_on_wire == 100 for b in plan.buckets)


# ---------------------------------------------------------------------------
# leaf_cost / planner integration
# ---------------------------------------------------------------------------


def test_leaf_cost_matches_predict():
    topo = comm.LinkTopo(
        (comm.AlphaBeta(1e-5, 1e-9), comm.AlphaBeta(1e-6, 1e-10))
    )
    lc = comm.leaf_cost(
        "coo_fp32", "hierarchical", 1 << 16, 1 << 10, (2, 4), topo
    )
    est = comm.predict(
        "coo_fp32", "hierarchical", 1 << 16, 1 << 10, (2, 4), topo
    )
    assert lc.bytes_on_wire == est.bytes_on_wire
    assert np.isclose(lc.seconds, est.seconds, rtol=1e-12)
    assert len(lc.axis_seconds) == 2
    assert lc.wire == ("coo_fp32", "hierarchical")


def test_plan_tree_overlap_schedule():
    from jax.sharding import PartitionSpec as P

    from repro.core.distributed import LeafPlan

    tree = {
        "a": LeafPlan((1 << 16,), (1 << 16,), 1 << 16, 1 << 10, P(None)),
        "b": LeafPlan((1 << 14,), (1 << 14,), 1 << 14, 1 << 8, P(None)),
        "c": LeafPlan((256,), (256,), 256, 8, P(None)),
    }
    topo = comm.LinkTopo(
        (comm.AlphaBeta(1e-4, 1e-8), comm.AlphaBeta(1e-5, 1e-9))
    )
    cp = comm.plan_tree(tree, (2, 4), topo)
    assert cp.buckets is None and cp.timeline is None
    cp2 = comm.plan_tree(
        tree, (2, 4), topo,
        collectives=["hierarchical"],
        overlap=OverlapConfig(n_buckets=2),
    )
    assert cp2.buckets.n_buckets == 2
    assert sorted(cp2.buckets.leaf_order()) == [0, 1, 2]
    assert cp2.timeline.seconds < cp2.total_seconds
    cp1 = comm.plan_tree(
        tree, (2, 4), topo,
        collectives=["hierarchical"],
        overlap=OverlapConfig(n_buckets=1),
    )
    assert np.isclose(cp1.timeline.seconds, cp1.total_seconds, rtol=1e-9)


# ---------------------------------------------------------------------------
# distributed runtime: off-switch bit-for-bit + the round timeline
# ---------------------------------------------------------------------------


def _micro_train(overlap, codec, steps=2, monkey_costs=None, monkeypatch=None):
    from repro.compat import make_mesh
    from repro.core import distributed as D
    from repro.core.sparsify import SparsifierConfig
    from repro.data import TokenPipeline
    from repro.models import ModelConfig, get_family
    from repro.optim import OptConfig, make_optimizer

    if monkey_costs is not None:
        monkeypatch.setattr(D, "_leaf_overlap_costs", monkey_costs)
    mesh = make_mesh((1, 1), ("data", "model"))
    cfg = ModelConfig(
        name="tiny", family="dense", n_layers=1, d_model=32, n_heads=2,
        n_kv_heads=2, head_dim=16, d_ff=64, vocab=128, remat=False,
    )
    mod = get_family(cfg)
    dist = D.DistConfig(
        sparsifier=SparsifierConfig(kind="regtopk", sparsity=0.05, mu=1.0),
        optimizer=OptConfig(kind="adam", learning_rate=3e-3),
        aggregation="sparse_allgather", dp_axes=("data",),
        codec=codec, overlap=overlap,
    )
    asm = D.assemble(mod, cfg, dist, mesh)
    params, _ = mod.init(jax.random.PRNGKey(0), cfg)
    opt = make_optimizer(dist.optimizer)
    opt_state = opt.init(params)
    sp_state, _ = D.init_sparsifier_state(
        asm.plan, 1, mesh, ("data",), jnp.float32
    )
    pipe = TokenPipeline(cfg, global_batch=4, seq=16)
    step = jax.jit(asm.train_step)
    with mesh:
        for t in range(steps):
            params, opt_state, sp_state, _ = step(
                params, opt_state, sp_state, pipe.batch_at(t)
            )
    return params, (asm.plan, dist, mesh)


def _synthetic_costs(plan, dist, mesh):
    """Nonzero heterogeneous fake costs: on the single-device test mesh
    every real leaf cost is zero (no wire), which collapses the schedule
    to one bucket — these force a genuine multi-bucket reorder so the
    bit-for-bit property is tested against a *permuted* leaf order."""
    from repro.core.distributed import _is_plan

    leaves = jax.tree.leaves(plan, is_leaf=_is_plan)
    n = len(leaves)
    return [
        LeafCost(100 * (i + 1), (float(n - i), 1.0), ("c", "h"))
        for i in range(n)
    ]


@pytest.mark.parametrize("codec", ["coo_fp32", "coo_idx_delta", "coo_q8"])
def test_bucketed_aggregation_bitforbit(codec, monkeypatch):
    from repro.core import distributed as D

    p_off, _ = _micro_train("off", codec)
    p_on, on = _micro_train(
        "buckets:3", codec,
        monkey_costs=_synthetic_costs, monkeypatch=monkeypatch,
    )
    for a, b in zip(jax.tree.leaves(p_off), jax.tree.leaves(p_on)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    _, timeline = D.comm_round_timeline(*on)
    tl = np.stack([timeline.launch, timeline.complete], axis=1)
    assert tl.shape == (3, 2)
    # launch <= complete per bucket, completes monotone
    assert (tl[:, 0] <= tl[:, 1]).all()
    assert (np.diff(tl[:, 1]) >= 0).all()


def test_comm_round_timeline_gates():
    from jax.sharding import PartitionSpec as P

    from repro.compat import make_mesh
    from repro.core import distributed as D
    from repro.core.sparsify import SparsifierConfig

    mesh = make_mesh((1, 1), ("data", "model"))
    plan = {"w": D.LeafPlan((64,), (64,), 64, 4, P(None))}
    base = dict(
        sparsifier=SparsifierConfig(kind="regtopk", sparsity=0.05, mu=1.0),
        aggregation="sparse_allgather", dp_axes=("data",),
    )
    off = D.DistConfig(**base)
    assert off.resolved_overlap() is None
    with pytest.raises(ValueError, match="overlap != 'off'"):
        D.comm_round_timeline(plan, off, mesh)
    on = D.DistConfig(overlap="buckets:2", **base)
    bplan, tl = D.comm_round_timeline(plan, on, mesh)
    assert bplan.n_leaves == 1
    assert tl.seconds <= tl.sync_seconds + 1e-12
    with pytest.raises(ValueError, match="unknown overlap spec"):
        D.DistConfig(overlap="bogus", **base).resolved_overlap()


# ---------------------------------------------------------------------------
# simulator
# ---------------------------------------------------------------------------


def _sim(**kw):
    from repro.core.simulator import DistributedSim
    from repro.core.sparsify import SparsifierConfig

    def gf(theta, w):
        return theta + jnp.asarray(w, theta.dtype)

    return DistributedSim(
        gf, 8, 2048,
        SparsifierConfig(kind="regtopk", sparsity=0.02, mu=1.0),
        codec="coo_fp32", collective="hierarchical", dp_shape=(2, 4),
        link_topo=comm.LinkTopo(
            (comm.AlphaBeta(1e-5, 1e-9), comm.AlphaBeta(1e-6, 1e-10))
        ),
        **kw,
    )


def test_sim_overlap_bitforbit_and_timeline():
    theta0 = jnp.zeros(2048)
    _, tr_off = _sim().run(theta0, 4)
    s_on = _sim(overlap="buckets:4")
    _, tr_on = s_on.run(theta0, 4)
    np.testing.assert_array_equal(np.asarray(tr_off), np.asarray(tr_on))
    bplan, tl = s_on.round_timeline()
    # single leaf -> the schedule clamps to one bucket; pricing matches
    # the synchronous wire estimate
    assert bplan.n_buckets == 1
    assert np.isclose(
        tl.sync_seconds, s_on.wire_bytes_per_round().seconds, rtol=1e-9
    )
    assert np.isclose(tl.seconds, tl.sync_seconds, rtol=1e-9)


def test_sim_overlap_gates():
    with pytest.raises(ValueError, match="unknown overlap spec"):
        _sim(overlap="stream")
    with pytest.raises(ValueError, match="overlap != 'off'"):
        _sim().round_timeline()
