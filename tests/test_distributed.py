"""Distributed runtime integration tests.

Multi-device cases run in a subprocess with
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` so the main pytest
process keeps seeing 1 device (per the dry-run isolation contract).
"""
import json
import os
import re
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.compact import compact_finalize, compact_select
from repro.core.sparsify import SparsifierConfig

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def run_sub(code: str, devices: int = 8) -> dict:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = SRC
    out = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True, env=env,
        timeout=480,
    )
    assert out.returncode == 0, f"subprocess failed:\n{out.stderr[-4000:]}"
    return json.loads(out.stdout.strip().splitlines()[-1])


# ---------------------------------------------------------------------------
# compact-state equivalence with the dense simulator algebra
# ---------------------------------------------------------------------------
@pytest.mark.parametrize(
    "kind,y",
    [("topk", 1.0), ("regtopk", 1.0), ("regtopk", 0.5), ("regtopk", 2.0)],
)
def test_compact_matches_dense_state(kind, y):
    """Dense <-> compact equivalence, including the Remark-4 prior exponent
    (regression: compact_select silently ignored cfg.y)."""
    L, k, steps = 64, 8, 5
    cfg = SparsifierConfig(kind=kind, sparsity=k / L, mu=1.5, omega=0.1, y=y)
    from repro.core.compact import compact_init, reference_step

    st = compact_init(L, k)
    key = jax.random.PRNGKey(0)
    g_prev_dense = jnp.zeros(L)
    for _t in range(steps):
        key, sk = jax.random.split(key)
        g = jax.random.normal(sk, (L,))
        # dense reference on the reconstructed state
        ghat_ref, mask_ref, _ = reference_step(cfg, st, g, g_prev_dense, k)
        a, vals, idx = compact_select(cfg, st, g, k)
        ghat = jnp.zeros(L).at[idx].set(vals)
        np.testing.assert_allclose(
            np.asarray(ghat), np.asarray(ghat_ref), rtol=1e-5, atol=1e-6
        )
        agg = 0.1 * ghat  # arbitrary aggregate
        st = compact_finalize(st, a, vals, idx, agg)
        g_prev_dense = agg


def test_compact_threshold_selector_routes_not_drops():
    """Regression: compact_select ignored SparsifierConfig.selector — the
    distributed runtime always ran exact top-k whatever the config said.
    selector='threshold' must route through the bisection mask +
    mask_to_payload (same selected set when the mask has no ties), and
    unknown selectors must raise, not silently fall back."""
    import dataclasses

    from repro.core.compact import compact_init

    L, k = 64, 8
    key = jax.random.PRNGKey(3)
    g = jax.random.normal(key, (L,))
    cfg = SparsifierConfig(kind="regtopk", sparsity=k / L, mu=1.5, omega=0.1)
    st = compact_init(L, k)
    a_e, v_e, i_e = compact_select(cfg, st, g, k)
    a_t, v_t, i_t = compact_select(
        dataclasses.replace(cfg, selector="threshold"), st, g, k
    )
    np.testing.assert_allclose(np.asarray(a_t), np.asarray(a_e))
    # same coordinate set (payload order may differ)
    assert set(np.asarray(i_t).tolist()) == set(np.asarray(i_e).tolist())
    dense_e = np.zeros(L)
    dense_e[np.asarray(i_e)] = np.asarray(v_e)
    dense_t = np.zeros(L)
    dense_t[np.asarray(i_t)] = np.asarray(v_t)
    np.testing.assert_allclose(dense_t, dense_e, rtol=1e-6)
    with pytest.raises(ValueError, match="selector"):
        compact_select(
            dataclasses.replace(cfg, selector="bogus"), st, g, k
        )


def test_compact_zero_gradient_round_threshold_selector():
    """A zero gradient round with the threshold selector must produce an
    all-(0, 0) payload (scatter no-op), not ship the whole vector."""
    import dataclasses

    from repro.core.compact import compact_init

    L, k = 32, 4
    cfg = SparsifierConfig(
        kind="regtopk", sparsity=k / L, selector="threshold"
    )
    st = compact_init(L, k)
    a, vals, idx = compact_select(cfg, st, jnp.zeros(L), k)
    np.testing.assert_array_equal(np.asarray(vals), 0.0)
    np.testing.assert_array_equal(np.asarray(idx), 0)


def test_compact_exact_padding_never_destroys_live_coordinates():
    """Regression: with fewer than k nonzero scores, the exact selector's
    padding slots must not collide with a genuinely selected coordinate 0
    (a duplicate-index scatter-set silently dropped its gradient from
    both the aggregate and error feedback)."""
    from repro.core.compact import compact_init

    L, k = 8, 4
    g = jnp.zeros(L).at[jnp.array([0, 3])].set(jnp.array([5.0, 3.0]))
    cfg = SparsifierConfig(kind="topk", sparsity=k / L)
    st = compact_init(L, k)
    a, vals, idx = compact_select(cfg, st, g, k)
    # payload indices are distinct -> scatter set/add agree downstream
    assert len(set(np.asarray(idx).tolist())) == k
    ghat = np.zeros(L)
    np.add.at(ghat, np.asarray(idx), np.asarray(vals))
    np.testing.assert_allclose(ghat, np.asarray(g))  # 5.0 survives
    st2 = compact_finalize(st, a, vals, idx, jnp.zeros(L))
    # error conservation: eps' + sent == a, for every coordinate
    np.testing.assert_allclose(
        np.asarray(st2.eps) + ghat, np.asarray(a), rtol=1e-6
    )
    # the (0, j)-padded threshold payload conserves too
    mask = jnp.zeros(L).at[3].set(1.0)  # cardinality 1 < k
    from repro.core.selectors import mask_to_payload

    pv, pi = mask_to_payload(mask, a, k)
    st3 = compact_finalize(st, a, pv, pi, jnp.zeros(L))
    sent = np.zeros(L)
    np.add.at(sent, np.asarray(pi), np.asarray(pv))
    np.testing.assert_allclose(
        np.asarray(st3.eps) + sent, np.asarray(a), rtol=1e-6
    )
    assert float(st3.eps[0]) == 5.0  # unsent coordinate 0 stays in eps


def test_compact_cyclic_covers_all_coordinates():
    L, k = 20, 6
    cfg = SparsifierConfig(kind="cyclic", sparsity=k / L)
    from repro.core.compact import compact_init

    st = compact_init(L, k)
    seen = set()
    for _t in range(-(-L // k) + 1):
        g = jnp.ones(L)
        a, vals, idx = compact_select(cfg, st, g, k)
        seen.update(np.asarray(idx).tolist())
        st = compact_finalize(st, a, vals, idx, jnp.zeros(L))
    assert seen == set(range(L))


# ---------------------------------------------------------------------------
# multi-device integration (subprocess)
# ---------------------------------------------------------------------------
SUB_TEMPLATE = textwrap.dedent(
    """
    import json
    import jax, jax.numpy as jnp, numpy as np
    from repro.compat import make_mesh
    mesh = make_mesh((4, 2), ("data", "model"))
    from repro.models import ModelConfig, get_family
    from repro.core.distributed import DistConfig, assemble, init_sparsifier_state
    from repro.core.sparsify import SparsifierConfig
    from repro.optim import OptConfig, make_optimizer
    from repro.data import TokenPipeline

    cfg = ModelConfig(name="tiny", family="dense", n_layers=2, d_model=64,
                      n_heads=4, n_kv_heads=2, head_dim=16, d_ff=128,
                      vocab=256, remat=False)
    mod = get_family(cfg)

    def train(kind, agg, steps=25, sparsity=0.05, fastpath="off", **dkw):
        dist = DistConfig(
            sparsifier=SparsifierConfig(kind=kind, sparsity=sparsity, mu=1.0),
            optimizer=OptConfig(kind="adam", learning_rate=3e-3),
            aggregation=agg, microbatches=2, dp_axes=("data",),
            fastpath=fastpath, **dkw)
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
        return losses, params

    {BODY}
    """
)


def test_sparse_equals_dense_aggregation_multidevice():
    body = """
l1, p1 = train("regtopk", "dense_allreduce")
l2, p2 = train("regtopk", "sparse_allgather")
d = max(abs(a - b) for a, b in zip(l1, l2))
print(json.dumps({"max_loss_diff": d, "decreased": l1[-1] < l1[0]}))
"""
    res = run_sub(SUB_TEMPLATE.replace("{BODY}", body))
    assert res["max_loss_diff"] < 1e-4
    assert res["decreased"]


def test_fused_fastpath_training_equivalence_multidevice():
    """ISSUE 5 acceptance: dense↔fused training equivalence in the real
    shard_map runtime — the fused select→encode pipeline (interpret-mode
    Pallas inside an 8-device mesh) reproduces the unfused losses exactly
    (the selection payload is bit-for-bit, so trajectories cannot
    diverge)."""
    body = """
l1, p1 = train("regtopk", "sparse_allgather", steps=6, sparsity=0.002)
l2, p2 = train("regtopk", "sparse_allgather", steps=6, sparsity=0.002,
               fastpath="on")
import jax as _j
pdiff = max(float(abs(a - b).max())
            for a, b in zip(_j.tree.leaves(p1), _j.tree.leaves(p2)))
d = max(abs(a - b) for a, b in zip(l1, l2))
print(json.dumps({"max_loss_diff": d, "max_param_diff": pdiff}))
"""
    res = run_sub(SUB_TEMPLATE.replace("{BODY}", body))
    assert res["max_loss_diff"] == 0.0
    assert res["max_param_diff"] == 0.0


def test_bucketed_overlap_bitforbit_multidevice():
    """ISSUE 10 acceptance: the bucketed overlap schedule is a pure
    reorder — ``overlap='buckets:3'`` reproduces the synchronous
    ``overlap='off'`` losses and parameters bit-for-bit on a real
    8-device shard_map mesh (the timeline metric itself is covered in
    ``tests/test_overlap.py``)."""
    body = """
l1, p1 = train("regtopk", "sparse_allgather", steps=6)
l2, p2 = train("regtopk", "sparse_allgather", steps=6,
               overlap="buckets:3")
import jax as _j
pdiff = max(float(abs(a - b).max())
            for a, b in zip(_j.tree.leaves(p1), _j.tree.leaves(p2)))
d = max(abs(a - b) for a, b in zip(l1, l2))
print(json.dumps({"max_loss_diff": d, "max_param_diff": pdiff}))
"""
    res = run_sub(SUB_TEMPLATE.replace("{BODY}", body))
    assert res["max_loss_diff"] == 0.0
    assert res["max_param_diff"] == 0.0


def test_compact_select_fastpath_multi_round_parity():
    """compact_select(fastpath="on") == the dense path, bit-for-bit, over
    an evolving multi-round regtopk state (posterior statistics scattered
    from the compact k-vectors must reproduce the k-vector score math
    exactly)."""
    L, k = 10_000, 16
    cfg = SparsifierConfig(kind="regtopk", mu=1.0, omega=0.125)
    from repro.core.compact import compact_init

    st = compact_init(L, k)
    key = jax.random.PRNGKey(3)
    for _t in range(4):
        key, sk = jax.random.split(key)
        g = jax.random.normal(sk, (L,))
        a1, v1, i1 = compact_select(cfg, st, g, k)
        a2, v2, i2 = compact_select(cfg, st, g, k, fastpath="on")
        np.testing.assert_array_equal(np.asarray(v1), np.asarray(v2))
        np.testing.assert_array_equal(np.asarray(i1), np.asarray(i2))
        agg = 0.125 * jnp.zeros(L).at[i1].add(v1)
        st = compact_finalize(st, a1, v1, i1, agg)


def test_fused_plan_validation_and_dtype_gate():
    """A plan hand-marked fused on a non-fusable wire fails fast at
    aggregation build (not deep inside shard_map); fastpath='on' with a
    bf16 state raises (the fused kernel scores in f32 — not bit-for-bit
    against a bf16 unfused path) while 'auto' quietly declines."""
    import dataclasses

    from jax.sharding import PartitionSpec as P

    from repro.compat import make_mesh
    from repro.core.distributed import (
        DistConfig,
        LeafPlan,
        leaf_fastpath,
        make_sparsify_aggregate,
        sparsifier_state_shapes,
    )

    mesh = make_mesh((1, 1), ("data", "model"))
    dist = DistConfig(
        sparsifier=SparsifierConfig(kind="regtopk", sparsity=0.002),
        codec="bitmap_dense", collective="sparse_allgather",
        dp_axes=("data",), fastpath="on",
    )
    plan = {"w": LeafPlan((8192,), (8192,), 8192, 17, P(None), fused=True)}
    _, sspecs = sparsifier_state_shapes(plan, 1, mesh, ("data",), jnp.float32)
    with pytest.raises(ValueError, match="not fusable"):
        make_sparsify_aggregate(
            mesh, plan, {"w": P(None)}, sspecs, dist, 1
        )
    bf16_on = dataclasses.replace(
        dist, codec="coo_fp32", state_dtype="bfloat16"
    )
    with pytest.raises(ValueError, match="float32"):
        bf16_on.resolved_fastpath()
    bf16_auto = dataclasses.replace(bf16_on, fastpath="auto")
    assert bf16_auto.resolved_fastpath() == "off"
    # the dtype gate also zeroes the per-leaf resolution
    assert not leaf_fastpath(plan["w"], bf16_auto)


@pytest.mark.parametrize("kind", ["topk", "cyclic", "none"])
def test_all_kinds_train_multidevice(kind):
    body = f"""
l, p = train("{kind}", "dense_allreduce", steps=20)
print(json.dumps({{"first": l[0], "last": l[-1]}}))
"""
    res = run_sub(SUB_TEMPLATE.replace("{BODY}", body))
    assert np.isfinite(res["last"])
    assert res["last"] < res["first"]


def test_checkpoint_roundtrip_multidevice():
    body = """
import tempfile, os
from repro.checkpoint import save, restore
l, p = train("regtopk", "dense_allreduce", steps=5)
d = tempfile.mkdtemp()
save(d, p, metadata={"step": 5})
p2 = restore(d, p)
same = all(bool(jnp.allclose(a, b)) for a, b in
           zip(jax.tree.leaves(p), jax.tree.leaves(p2)))
print(json.dumps({"same": same}))
"""
    res = run_sub(SUB_TEMPLATE.replace("{BODY}", body))
    assert res["same"]


def test_dryrun_mini_multidevice():
    """Mini dry-run: lower+compile a reduced arch on a (2,4) mesh and check
    the cost walker sees nonzero flops and collectives."""
    code = textwrap.dedent(
        """
        import json
        import jax, jax.numpy as jnp
        from repro.compat import make_mesh
        mesh = make_mesh((2, 4), ("data", "model"))
        from repro import configs as cfglib
        from repro.models import get_family, input_specs
        from repro.core.distributed import DistConfig, assemble
        from repro.core.sparsify import SparsifierConfig
        from repro.optim import OptConfig, make_optimizer
        from repro.launch import hlo_cost
        from jax.sharding import NamedSharding, PartitionSpec as P

        cfg = cfglib.get_config("qwen2.5-3b").smoke_variant()
        mod = get_family(cfg)
        dist = DistConfig(
            sparsifier=SparsifierConfig(kind="regtopk", sparsity=0.01),
            optimizer=OptConfig(kind="adam"),
            aggregation="sparse_allgather", microbatches=2,
            dp_axes=("data",))
        asm = assemble(mod, cfg, dist, mesh)
        opt_shape = jax.eval_shape(
            lambda p: make_optimizer(dist.optimizer).init(p), asm.params_shape)
        sh = lambda t: jax.tree.map(
            lambda s: NamedSharding(mesh, s), t,
            is_leaf=lambda x: isinstance(x, P))
        batch = input_specs(cfg, 8, 32, kind="train")
        bs = jax.tree.map(lambda s: NamedSharding(mesh, P("data")), batch)
        opt_specs = {"step": P(), "m": asm.param_specs, "v": asm.param_specs}
        with mesh:
            lowered = jax.jit(
                asm.train_step,
                in_shardings=(sh(asm.param_specs), sh(opt_specs),
                              sh(asm.state_specs), bs),
            ).lower(asm.params_shape, opt_shape, asm.state_shapes, batch)
            compiled = lowered.compile()
        res = hlo_cost.analyze(compiled.as_text())
        mem = compiled.memory_analysis()
        print(json.dumps({
            "flops": res["flops"],
            "coll": res["collective_bytes"]["total"],
            "peak": getattr(mem, "peak_memory_in_bytes", 0) or 0,
        }))
        """
    )
    res = run_sub(code)
    assert res["flops"] > 1e6
    assert res["coll"] > 0
    # the CPU backend of older jaxlibs reports no memory analysis (peak 0);
    # only assert when the backend provides the number.
    assert res["peak"] >= 0
    if res["peak"]:
        assert res["peak"] > 1e5


def test_train_cli_checkpoint_resume(tmp_path):
    """End-to-end launcher: train -> checkpoint -> resume continues."""
    env = dict(os.environ)
    env["PYTHONPATH"] = SRC
    ckpt = str(tmp_path / "ck")
    base = [sys.executable, "-m", "repro.launch.train",
            "--arch", "paper-resnet-proxy", "--smoke", "--steps", "4",
            "--global-batch", "2", "--seq", "16", "--log-every", "2"]
    r1 = subprocess.run([*base, "--checkpoint", ckpt],
                        capture_output=True, text=True, env=env, timeout=480)
    assert r1.returncode == 0, r1.stderr[-2000:]
    assert "checkpointed" in r1.stdout
    assert re.search(
        r"select: two-level top-k on \d+/\d+ leaves \(\d+\.\d% of the "
        r"gradient's elements\)",
        r1.stdout,
    )
    r2 = subprocess.run([*base, "--resume", ckpt],
                        capture_output=True, text=True, env=env, timeout=480)
    assert r2.returncode == 0, r2.stderr[-2000:]
    assert "resumed from step 4" in r2.stdout
    assert "step     7" in r2.stdout or "step 7" in r2.stdout.replace("  ", " ")


def test_spa_participation_round_loop_compiles_once_multidevice():
    """Retrace guard (ISSUE 7): the shard_map aggregation under a
    round_robin participation schedule on a real 4-worker mesh compiles
    exactly once across rounds — the rotating drop set is a function of
    the *traced* round counter, never a fresh compilation."""
    code = textwrap.dedent("""
        import json

        import jax
        import jax.numpy as jnp
        from jax.sharding import PartitionSpec as P

        from repro.comm import Participation
        from repro.compat import make_mesh
        from repro.core.distributed import (
            DistConfig,
            LeafPlan,
            init_sparsifier_state,
            make_sparsify_aggregate,
        )
        from repro.core.sparsify import SparsifierConfig

        mesh = make_mesh((4, 1), ("data", "model"))
        dist = DistConfig(
            sparsifier=SparsifierConfig(kind="regtopk", sparsity=8 / 256),
            codec="coo_fp32",
            collective="sparse_allgather",
            dp_axes=("data",),
            participation=Participation("round_robin", n_stragglers=1),
        )
        plan = {"w": LeafPlan((256,), (256,), 256, 8, P(None), fused=False)}
        state, specs = init_sparsifier_state(
            plan, 4, mesh, ("data",), jnp.float32
        )
        spa = make_sparsify_aggregate(mesh, plan, {"w": P(None)}, specs,
                                      dist, 4)
        calls = {"n": 0}

        def counted(g, s):
            calls["n"] += 1
            return spa(g, s)

        step = jax.jit(counted)
        grads = {"w": jnp.linspace(-1.0, 1.0, 4 * 256).reshape(4, 256)}
        with mesh:
            for _ in range(5):
                agg, state, _ = step(grads, state)
        jax.block_until_ready(agg)
        print(json.dumps({"traces": calls["n"],
                          "t": int(state["w"].t[0])}))
    """)
    res = run_sub(code, devices=4)
    assert res["traces"] == 1, res
    assert res["t"] == 5


# ---------------------------------------------------------------------------
# per-coordinate weighting: reference <-> shard_map differentials (ISSUE 9)
# ---------------------------------------------------------------------------
COORD_SUB = textwrap.dedent("""
    import json
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import PartitionSpec as P
    from jax import shard_map
    from repro.compat import make_mesh
    from repro import comm

    W, L, k = 8, 96, 9
    ks = jax.random.split(jax.random.PRNGKey(0), W)
    vals = jnp.stack([
        jnp.sign(jax.random.normal(kk, (k,)))
        * (0.5 + jax.random.uniform(kk, (k,))) for kk in ks])
    idx = jnp.stack([
        jnp.sort(jax.random.permutation(kk, L)[:k]) for kk in ks
    ]).astype(jnp.int32)
    weights = jnp.full((W,), 1.0 / W, jnp.float32)
    pmask = jnp.asarray([1, 0, 1, 1, 0, 1, 1, 1], jnp.float32)
    mesh = make_mesh((W,), ("data",))
    out = {}
    for cname in ("coo_fp32", "coo_q8"):
        codec = comm.get_codec(cname)
        payloads = jax.vmap(lambda v, i: codec.encode(v, i, L))(vals, idx)
        in_specs = jax.tree.map(
            lambda x: P(*(("data",) + (None,) * (x.ndim - 1))), payloads)
        for sname in ("sparse_allgather", "hierarchical"):
            strat = comm.get_collective(sname)
            for tag, pm in (("full", None), ("partial", pmask)):
                w = weights if pm is None else comm.renormalize_weights(
                    weights, pm)
                ref_agg, ref_den = strat.reference_coord(
                    codec, payloads, weights, L, participation=pm)

                def body(p, m):
                    local = jax.tree.map(lambda x: x[0], p)
                    part = None if pm is None else m[0]
                    # shard form: each worker passes its renormalized
                    # weight entry (the runtime's _spa_leaf does the same)
                    wi = jax.lax.axis_index("data")
                    return strat.shard_coord(
                        codec, local, L, ("data",), w[wi],
                        participation=part)

                with mesh:
                    got_agg, got_den = shard_map(
                        body, mesh=mesh,
                        in_specs=(in_specs, P("data")),
                        out_specs=(P(None), P(None)), check_vma=False,
                    )(payloads, pmask)
                key = f"{cname}/{sname}/{tag}"
                out[key] = {
                    "agg_exact": bool((got_agg == ref_agg).all()),
                    "den_exact": bool((got_den == ref_den).all()),
                    "agg_close": float(jnp.abs(got_agg - ref_agg).max()),
                    "den_close": float(jnp.abs(got_den - ref_den).max()),
                    "finite": bool(jnp.isfinite(got_agg).all()),
                }
    print(json.dumps(out))
""")


def test_shard_coord_matches_reference_multidevice():
    """Coordinate weighting, reference vs in-shard_map form on a real
    8-device mesh: the flat-gather strategy reduces in worker-stack
    order through the shared scatter-add, so it is bit-for-bit;
    hierarchical regroups the sum (intra psum) and is equal to
    tolerance. Both codecs (incl. the lossy coo_q8, whose
    quantized-to-zero values must carry no sender mass) and both full
    and partial schedules."""
    res = run_sub(COORD_SUB)
    for key, r in res.items():
        assert r["finite"], (key, r)
        if "sparse_allgather" in key:
            assert r["agg_exact"] and r["den_exact"], (key, r)
        else:
            assert r["agg_close"] < 1e-6 and r["den_close"] < 1e-6, (key, r)


COORD_TRAIN_SUB = textwrap.dedent("""
    import json
    import jax, jax.numpy as jnp
    from repro.compat import make_mesh
    mesh = make_mesh((4, 2), ("data", "model"))
    from repro.models import ModelConfig, get_family
    from repro.core.distributed import (DistConfig, assemble,
                                        init_sparsifier_state)
    from repro.core.sparsify import SparsifierConfig
    from repro.optim import OptConfig, make_optimizer
    from repro.data import TokenPipeline
    from repro import comm

    cfg = ModelConfig(name="tiny", family="dense", n_layers=2, d_model=64,
                      n_heads=4, n_kv_heads=2, head_dim=16, d_ff=128,
                      vocab=256, remat=False)
    mod = get_family(cfg)

    def train(collective, weighting, participation=None, steps=6):
        dist = DistConfig(
            sparsifier=SparsifierConfig(kind="regtopk", sparsity=0.05,
                                        mu=1.0),
            optimizer=OptConfig(kind="adam", learning_rate=3e-3),
            codec="coo_fp32", collective=collective, microbatches=1,
            dp_axes=("data",), participation=participation,
            weighting=weighting)
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
        return losses

    worker = train("sparse_allgather", "worker")
    coord_sparse = train("sparse_allgather", "coordinate")
    coord_dense = train("dense_allreduce", "coordinate")
    samp = comm.Participation("sampled", n_sampled=2, seed=3)
    coord_samp = train("sparse_allgather", "coordinate", samp)
    print(json.dumps({
        "coord_changes_training": max(
            abs(a - b) for a, b in zip(worker, coord_sparse)) > 0,
        "dense_vs_sparse": max(
            abs(a - b) for a, b in zip(coord_dense, coord_sparse)),
        "samp_finite": all(x == x for x in coord_samp),
        "finite": all(x == x for x in coord_sparse + coord_dense),
    }))
""")


def test_coordinate_weighting_trains_multidevice():
    """End-to-end shard_map runtime under weighting='coordinate': the
    dense and payload paths agree (same per-coordinate reduction through
    two different wire forms), training stays finite — including under
    S-of-N sampled participation — and the axis actually changes the
    numerics vs worker weighting."""
    res = run_sub(COORD_TRAIN_SUB)
    assert res["finite"] and res["samp_finite"]
    assert res["coord_changes_training"] is True
    assert res["dense_vs_sparse"] < 1e-4
