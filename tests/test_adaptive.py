"""Adaptive-k acceptance tests (ISSUE 8, tier1-slow).

Two claims ride here:

* **Convergence** (seed-pinned Fig-3 linear regression): static Top-k at
  high compression plateaus at a strictly positive distance-to-optimum,
  while the adaptive RegTop-k controller — free to spend k up to a dense
  capacity when the error budget demands it — converges below tolerance
  on the same data, seed and learning rate.
* **Multi-worker off-switch**: the pinned-controller differential
  (``tests/test_controller.py`` runs it on one device) holds bit-for-bit
  on a real 4-worker shard_map mesh, where the controller's norms travel
  through psum/pmean.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import textwrap

import jax.numpy as jnp
import numpy as np

from repro import comm
from repro.core import DistributedSim, SparsifierConfig
from repro.data.pipeline import linreg_grad_fn, make_linreg

SRC = os.path.join(os.path.dirname(__file__), "..", "src")

N, J = 20, 100
SEED = 42
STEPS = 2000
TOL = 1e-3


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


def _gap_trace(kind, sparsity, adaptive=None):
    data = make_linreg(SEED, N, J, 500, homogeneous=False)
    cfg = SparsifierConfig(kind=kind, sparsity=sparsity, mu=16.0)
    sim = DistributedSim(
        linreg_grad_fn(data), N, J, cfg, learning_rate=1e-2,
        adaptive_k=adaptive,
    )
    if adaptive is None:
        _, tr = sim.run(
            jnp.zeros(J), STEPS,
            trace_fn=lambda th: jnp.linalg.norm(th - data.theta_star),
        )
        return np.asarray(tr), None
    _, tr = sim.run(
        jnp.zeros(J), STEPS,
        trace_state_fn=lambda s: (
            jnp.linalg.norm(s.theta - data.theta_star), s.ctrl.k
        ),
    )
    return np.asarray(tr[0]), np.asarray(tr[1])


def test_static_topk_plateaus_adaptive_regtopk_converges():
    """Paper Fig. 3 at S = 0.05 (20x compression): plain Top-k's optimality
    gap flatlines strictly above zero; the error-budget controller grows k
    whenever ||eps||/||g_agg|| overshoots and drives the gap below TOL."""
    static, _ = _gap_trace("topk", 0.05)
    # plateau: strictly positive, and no longer improving over the last
    # half of the run (the paper's high-compression stall)
    assert static[-1] > 0.2
    assert static[-1] > 0.8 * static[STEPS // 2]

    ctrl = comm.AdaptiveKController(budget=1.0, k_min=2, k_max=J)
    adaptive, ks = _gap_trace("regtopk", 0.05, adaptive=ctrl)
    assert adaptive[-1] < TOL, (
        f"adaptive gap {adaptive[-1]:.3e} above tolerance {TOL}"
    )
    # the win came from the controller actually moving k, within bounds
    assert ks.min() >= 2 and ks.max() <= J
    assert ks.max() > ks.min()
    # and strictly beats the static plateau on the same seed/data/lr
    assert adaptive[-1] < 1e-2 * static[-1]


def test_adaptive_equilibrates_to_budget():
    """A looser budget must equilibrate the smoothed error ratio near the
    budget itself (the closed loop's fixed point), holding k between the
    bounds rather than saturating — the distinguishing behavior of
    feedback control over a static schedule."""
    data = make_linreg(SEED, N, J, 500, homogeneous=False)
    cfg = SparsifierConfig(kind="regtopk", sparsity=0.05, mu=16.0)
    ctrl = comm.AdaptiveKController(budget=3.0, k_min=2, k_max=J)
    sim = DistributedSim(
        linreg_grad_fn(data), N, J, cfg, learning_rate=1e-2,
        adaptive_k=ctrl,
    )
    _, tr = sim.run(
        jnp.zeros(J), STEPS,
        trace_state_fn=lambda s: (s.ctrl.err_ratio, s.ctrl.k),
    )
    ratios, ks = np.asarray(tr[0]), np.asarray(tr[1])
    tail = ratios[STEPS // 2:]
    assert 0.5 * 3.0 < tail.mean() < 2.0 * 3.0
    assert 2 < ks[-1] < J  # interior equilibrium, not a bound


def test_spa_disabled_controller_bit_for_bit_multidevice():
    """Acceptance: disabled-controller trajectories are bit-for-bit
    unchanged in the shard_map runtime on a real 4-worker dp mesh —
    the controller's psum/pmean norm plumbing must not perturb a single
    ulp of the static path when k is pinned at the static value."""
    code = textwrap.dedent("""
        import json

        import jax
        import jax.numpy as jnp
        import numpy as np
        from jax.sharding import PartitionSpec as P

        from repro import comm
        from repro.compat import make_mesh
        from repro.core.distributed import (
            DistConfig,
            LeafPlan,
            init_controller_state,
            init_sparsifier_state,
            make_sparsify_aggregate,
        )
        from repro.core.sparsify import SparsifierConfig

        mesh = make_mesh((4, 1), ("data", "model"))
        J, k = 256, 8
        grads = {"w": jnp.linspace(-1.0, 1.0, 4 * J).reshape(4, J)}
        plan = {"w": LeafPlan((J,), (J,), J, k, P(None), fused=False)}

        def rollout(adaptive):
            dist = DistConfig(
                sparsifier=SparsifierConfig(
                    kind="regtopk", sparsity=k / J, mu=4.0
                ),
                codec="coo_fp32", collective="sparse_allgather",
                dp_axes=("data",), adaptive_k=adaptive,
            )
            state, specs = init_sparsifier_state(
                plan, 4, mesh, ("data",), jnp.float32
            )
            spa = make_sparsify_aggregate(
                mesh, plan, {"w": P(None)}, specs, dist, 4
            )
            aggs = []
            with mesh:
                if adaptive is None:
                    for _ in range(5):
                        agg, state, _ = jax.jit(spa)(grads, state)
                        aggs.append(np.asarray(agg["w"]))
                else:
                    ctrl, _ = init_controller_state(plan, dist, mesh)
                    for _ in range(5):
                        agg, state, ctrl, _ = jax.jit(spa)(
                            grads, state, ctrl
                        )
                        aggs.append(np.asarray(agg["w"]))
            return aggs, state

        pinned = comm.AdaptiveKController(
            budget=1e9, k_min=k, k_max=k, hysteresis=0.0
        )
        a0, s0 = rollout(None)
        a1, s1 = rollout(pinned)
        agg_same = all(
            bool(np.array_equal(x, y))
            for x, y in zip(a0, a1, strict=True)
        )
        st_same = all(
            bool(np.array_equal(np.asarray(x), np.asarray(y)))
            for x, y in zip(
                jax.tree.leaves(s0), jax.tree.leaves(s1), strict=True
            )
        )
        print(json.dumps({"agg_same": agg_same, "st_same": st_same}))
    """)
    res = run_sub(code, devices=4)
    assert res["agg_same"] and res["st_same"], res


def test_adaptive_spa_multidevice_adapts_and_compiles_once():
    """4-worker adaptive round: k moves under a tight budget, controller
    state stays replicated-consistent, and the loop compiles once."""
    code = textwrap.dedent("""
        import json

        import jax
        import jax.numpy as jnp
        from jax.sharding import PartitionSpec as P

        from repro import comm
        from repro.compat import make_mesh
        from repro.core.distributed import (
            DistConfig,
            LeafPlan,
            init_controller_state,
            init_sparsifier_state,
            make_sparsify_aggregate,
        )
        from repro.core.sparsify import SparsifierConfig

        mesh = make_mesh((4, 1), ("data", "model"))
        J = 256
        dist = DistConfig(
            sparsifier=SparsifierConfig(
                kind="regtopk", sparsity=8 / J, mu=4.0
            ),
            codec="coo_fp32", collective="sparse_allgather",
            dp_axes=("data",),
            adaptive_k=comm.AdaptiveKController(
                budget=0.01, k_min=2, k_max=64
            ),
        )
        plan = {"w": LeafPlan((J,), (J,), J, 64, P(None), fused=False)}
        state, specs = init_sparsifier_state(
            plan, 4, mesh, ("data",), jnp.float32
        )
        ctrl, _ = init_controller_state(plan, dist, mesh)
        spa = make_sparsify_aggregate(
            mesh, plan, {"w": P(None)}, specs, dist, 4
        )
        calls = {"n": 0}

        def counted(g, s, c):
            calls["n"] += 1
            return spa(g, s, c)

        step = jax.jit(counted)
        grads = {"w": jnp.linspace(-1.0, 1.0, 4 * J).reshape(4, J)}
        ks = []
        with mesh:
            for _ in range(6):
                agg, state, ctrl, _ = step(grads, state, ctrl)
                ks.append(int(ctrl["w"].k))
        jax.block_until_ready(agg)
        print(json.dumps({
            "traces": calls["n"], "ks": ks,
            "t": int(state["w"].t[0]),
        }))
    """)
    res = run_sub(code, devices=4)
    assert res["traces"] == 1, res
    assert len(set(res["ks"])) > 1, res
    assert res["t"] == 6
