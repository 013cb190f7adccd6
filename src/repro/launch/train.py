"""Training launcher.

    PYTHONPATH=src python -m repro.launch.train --arch paper-resnet-proxy \
        --steps 50 --global-batch 8 --seq 64

One host: ``--mesh host`` (the default) builds a (data, model) mesh over
``jax.devices()`` — one CPU device, one TPU chip, or the four chips of a
v5e host, each chip a data-parallel worker unless ``--model-parallel``
says otherwise. ``--mesh production`` is the 16x16 (2x16x16 with
``--multi-pod``) slice the dry run lowers for.
"""
from __future__ import annotations

import argparse
import time
from typing import Any, List, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro import configs as cfglib
from repro.checkpoint import restore, save
from repro.core.distributed import (
    DistConfig,
    assemble,
    comm_round_bytes,
    comm_round_cost,
    init_sparsifier_state,
)
from repro.core.sparsify import SparsifierConfig
from repro.data import TokenPipeline
from repro.launch import mesh as meshlib
from repro.models import get_family
from repro.optim import OptConfig, make_optimizer


def _replan(dist, mesh, dp_axes, plan, step_fn, sp_state, mod, cfg, asm, t,
            jit_step):
    """Mid-training re-plan (--replan-every): probe the live collectives,
    fit a fresh alpha-beta model from the measured samples, re-run the
    per-leaf (codec x collective) planning at the k actually being sent,
    and rebuild the jitted step on the regrafted plan. Capacities (and so
    every state shape) are untouched — training resumes in place."""
    from collections import Counter

    from repro import comm
    from repro.comm import calibrate as cal
    from repro.core.distributed import (
        apply_plan_decisions,
        leaf_wire,
        make_train_step,
    )

    res = cal.calibrate(mesh=mesh, dp_axes=dp_axes)
    if not res.calibrated:
        print(
            f"replan @step {t + 1}: skipped (no dp axis with >1 worker)",
            flush=True,
        )
        return plan, step_fn
    W = int(np.prod([mesh.shape[a] for a in dp_axes]))
    part = dist.resolved_participation()
    k_over = None
    if dist.resolved_adaptive_k() is not None:
        k_over = jax.tree.map(
            lambda c: int(c.k),
            sp_state[1],
            is_leaf=lambda x: isinstance(x, comm.ControllerState),
        )
    cp = comm.replan(
        plan,
        [mesh.shape[a] for a in dp_axes],
        res.samples,
        k_overrides=k_over,
        codecs=None if dist.codec == "auto" else [dist.codec],
        collectives=(
            None if dist.resolved_collective() == "auto"
            else [dist.resolved_collective()]
        ),
        allow_lossy=dist.codec != "auto",
        participants=(
            part.expected_participants(W) if part is not None else None
        ),
        fastpath=dist.resolved_fastpath(),
    )
    new_plan = apply_plan_decisions(plan, cp)
    lk = cp.model.links[0]
    print(
        f"replan @step {t + 1}: alpha={lk.alpha:.3e} s/msg "
        f"beta={lk.beta:.3e} s/B -> "
        f"{cp.total_seconds * 1e3:.3f} ms/round predicted",
        flush=True,
    )
    picks = Counter(
        leaf_wire(p, dist)
        for p in jax.tree.leaves(
            new_plan, is_leaf=lambda x: hasattr(x, "local_len")
        )
    )
    for (c, s), n in sorted(picks.items()):
        print(f"replan:   {c}/{s}: {n} leaves", flush=True)
    step = jit_step(make_train_step(
        mod, cfg, dist, mesh, asm.param_specs, new_plan, asm.state_specs
    ))
    return new_plan, step


class TrainRun(NamedTuple):
    """What one :func:`main` run saw."""

    losses: List[float]  # per step
    # per step: fused-leaf selections (leaf x device) whose exactness
    # certificate failed, so the dense path chose the payload
    fallbacks: List[float]
    compile_seconds: float  # lowering + compiling the step
    # steady seconds per step after the first, timed to block_until_ready
    # (None for a one-step run)
    step_seconds: Optional[float]
    n_fused: int  # leaves on the fused select->encode path
    n_leaves: int
    compiled: Any  # the compiled step (HLO text, memory analysis)
    params: Any  # final parameters
    sp_state: Any  # final sparsifier state


def main(argv=None) -> TrainRun:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="paper-resnet-proxy")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--global-batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--sparsifier", default="regtopk",
                    choices=["none", "topk", "regtopk", "cyclic"])
    ap.add_argument("--sparsity", type=float, default=0.01)
    ap.add_argument("--mu", type=float, default=1.0)
    ap.add_argument("--aggregation", default="sparse_allgather",
                    help="legacy alias for --collective")
    ap.add_argument("--codec", default="coo_fp32",
                    choices=["coo_fp32", "coo_idx_delta", "bitmap_dense",
                             "coo_q8", "auto"],
                    help="'auto' plans per leaf via the alpha-beta model")
    ap.add_argument("--collective", default=None,
                    choices=["dense_allreduce", "sparse_allgather",
                             "hierarchical", "auto"])
    ap.add_argument("--fastpath", default="off",
                    choices=["off", "on", "auto"],
                    help="fused Pallas select->encode pipeline: 'on' "
                         "fuses every fusable leaf (with a runtime "
                         "exactness fallback to dense selection; see "
                         "docs/comm.md for how close it stays to 'off'), "
                         "'auto' fuses the leaves the measured-throughput "
                         "table prices faster (resolves to 'off' off-TPU)")
    ap.add_argument("--link-topo", default=None, metavar="SPEC",
                    help="per-dp-axis link model for auto-planning: "
                         "';'-separated 'class:alpha,beta' entries where "
                         "class is a dp axis name or 'intra'/'inter' "
                         "(e.g. 'intra:1e-6,1e-11;inter:1e-5,1e-10'), or a "
                         "bare 'alpha,beta' for a uniform model")
    ap.add_argument("--calibrate", action="store_true",
                    help="fit the alpha-beta link model from real "
                         "collectives before auto-planning (per dp axis "
                         "on multi-axis meshes; ignored when --link-topo "
                         "is given)")
    ap.add_argument("--participation", default=None, metavar="SPEC",
                    help="partial-participation schedule over the dp "
                         "worker group: 'full' (default), "
                         "'bernoulli:drop_rate[,seed]', "
                         "'round_robin:n_stragglers', or "
                         "'sampled:S[,seed]' (S-of-N client sampling via "
                         "a common-knowledge PRNG) — dropped workers "
                         "keep their payload in the error accumulator and "
                         "the round aggregates with renormalized weights "
                         "('stale:...' bounded-staleness delivery is "
                         "simulator-only)")
    ap.add_argument("--coord-weights", action="store_true",
                    help="per-coordinate aggregation weights: renormalize "
                         "each coordinate by the mass of the workers that "
                         "actually sent it instead of one per-worker "
                         "scalar (weighting='coordinate'; implies "
                         "fastpath stays off for regtopk)")
    ap.add_argument("--adaptive-k", default=None, metavar="SPEC",
                    help="error-budget-driven per-round k: "
                         "'budget[,k_min,k_max]' — the controller grows/"
                         "shrinks each leaf's k to hold "
                         "||eps||/||g_agg|| at the budget; bounds in "
                         "(0,1) are fractions of the leaf length, >= 1 "
                         "absolute counts; payloads ride at the k_max "
                         "capacity so k changes never retrace")
    ap.add_argument("--overlap", default="off", metavar="SPEC",
                    help="bucketed overlap schedule: 'off' (synchronous "
                         "round, the historical program) or 'buckets:B' — "
                         "split the leaf tree into B size-balanced launch "
                         "buckets so hierarchical's slow inter-axis stage "
                         "pipelines behind the next bucket's intra-axis "
                         "work; numerics are bit-for-bit identical either "
                         "way (each bucket's round runs under a "
                         "spa_bucketNNN profiler scope around its stage "
                         "scopes; the predicted schedule is printed at "
                         "start-up)")
    ap.add_argument("--replan-every", type=int, default=0, metavar="N",
                    help="every N steps, re-fit the alpha-beta link model "
                         "from live collective probes and re-plan the "
                         "per-leaf codec/collective choices from the "
                         "measured samples (0 disables)")
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--mesh", default="host", choices=["host", "production"])
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--model-parallel", type=int, default=1)
    ap.add_argument("--smoke", action="store_true",
                    help="use the reduced smoke variant of --arch")
    ap.add_argument("--checkpoint", default=None)
    ap.add_argument("--resume", default=None)
    ap.add_argument("--log-every", type=int, default=10)
    args = ap.parse_args(argv)
    meshlib.enable_compile_cache()

    cfg = cfglib.get_config(args.arch)
    if args.smoke:
        cfg = cfg.smoke_variant()
    if args.mesh == "production":
        mesh = meshlib.make_production_mesh(multi_pod=args.multi_pod)
    else:
        mesh = meshlib.make_host_mesh(model=args.model_parallel)
    dp_axes = meshlib.dp_axes_of(mesh)
    W = int(np.prod([mesh.shape[a] for a in dp_axes]))
    if args.global_batch % W:
        raise SystemExit(f"--global-batch must be divisible by {W} workers")

    link_model = None
    link_topo = None
    if args.link_topo:
        from repro import comm

        link_topo = comm.parse_link_topo(args.link_topo, dp_axes)
        for ax, lk in zip(dp_axes, link_topo.links, strict=True):
            print(
                f"link-topo {ax}: alpha={lk.alpha:.3e} s/msg "
                f"beta={lk.beta:.3e} s/B",
                flush=True,
            )
        if args.calibrate:
            print("--link-topo given; skipping --calibrate", flush=True)
    elif args.calibrate:
        from repro.comm import calibrate as cal

        if len(dp_axes) > 1:
            res = cal.calibrate_topo(mesh=mesh, dp_axes=dp_axes)
            if res.calibrated:
                link_topo = res.topo
                for ax, c in zip(res.axes, res.per_axis, strict=True):
                    print(
                        f"calibrated {ax}: alpha={c.model.alpha:.3e} s/msg "
                        f"beta={c.model.beta:.3e} s/B "
                        f"(rms {c.residual:.2e}s over {len(c.samples)} "
                        "probes)"
                        if c.calibrated
                        else f"calibrated {ax}: size-1 axis, defaults kept",
                        flush=True,
                    )
            else:
                print(
                    "calibration skipped (no dp axis with >1 worker); "
                    "using defaults",
                    flush=True,
                )
        else:
            res = cal.calibrate(mesh=mesh, dp_axes=dp_axes)
            link_model = res.model
            print(
                f"calibrated alpha={link_model.alpha:.3e} s/msg "
                f"beta={link_model.beta:.3e} s/B "
                f"(rms {res.residual:.2e}s over {len(res.samples)} probes)"
                if res.calibrated
                else "calibration skipped (single device); using defaults",
                flush=True,
            )

    participation = None
    if args.participation:
        from repro import comm

        participation = comm.parse_participation(args.participation)
        participation.validate(W)
        if not participation.is_full:
            print(
                f"participation: {participation.kind} — expected "
                f"{participation.expected_participants(W):.2f}/{W} workers "
                "on time per round (renormalized weights)",
                flush=True,
            )

    adaptive_k = None
    if args.adaptive_k:
        from repro import comm

        adaptive_k = comm.parse_adaptive_k(args.adaptive_k)
        print(
            f"adaptive-k: budget={adaptive_k.budget:g} "
            f"bounds=[{adaptive_k.k_min:g}, {adaptive_k.k_max:g}] "
            f"momentum={adaptive_k.momentum:g} "
            f"hysteresis={adaptive_k.hysteresis:g}",
            flush=True,
        )

    dist = DistConfig(
        sparsifier=SparsifierConfig(
            kind=args.sparsifier, sparsity=args.sparsity, mu=args.mu
        ),
        optimizer=OptConfig(kind="adam", learning_rate=args.lr),
        aggregation=args.aggregation,
        codec=args.codec,
        collective=args.collective,
        microbatches=args.microbatches,
        dp_axes=dp_axes,
        link_model=link_model,
        link_topo=link_topo,
        participation=participation,
        fastpath=args.fastpath,
        adaptive_k=adaptive_k,
        weighting="coordinate" if args.coord_weights else "worker",
        overlap=args.overlap,
    )
    if args.coord_weights:
        print(
            "weighting: coordinate — per-coordinate renormalization over "
            "the workers that sent each coordinate",
            flush=True,
        )
    if args.fastpath != "off":
        print(
            f"fastpath: {args.fastpath} (resolved "
            f"{dist.resolved_fastpath()}) — fused select->encode on "
            "fusable leaves",
            flush=True,
        )
    mod = get_family(cfg)
    asm = assemble(mod, cfg, dist, mesh)
    # initialized in place under the parameter specs, not on one device
    params = jax.jit(
        lambda key: mod.init(key, cfg)[0],
        out_shardings=jax.tree.map(
            lambda s: NamedSharding(mesh, s), asm.param_specs
        ),
    )(jax.random.PRNGKey(0))
    opt = make_optimizer(dist.optimizer)
    opt_state = jax.jit(opt.init)(params)
    sp_state, _ = init_sparsifier_state(
        asm.plan, W, mesh, dp_axes, jnp.float32
    )
    if adaptive_k is not None:
        from repro.core.distributed import init_controller_state

        ctrl0, _ = init_controller_state(asm.plan, dist, mesh)
        sp_state = (sp_state, ctrl0)
    start = 0
    if args.resume:
        params = restore(args.resume + "/params", params)
        opt_state = restore(args.resume + "/opt", opt_state)
        sp_state = restore(args.resume + "/sparsifier", sp_state)
        from repro.checkpoint.store import metadata

        start = metadata(args.resume + "/params").get("step", 0)
        print(f"resumed from step {start}")

    pipe = TokenPipeline(cfg, args.global_batch, args.seq)
    # the step hands its state back where it found it, so the second call
    # sees the first call's input shardings and reuses its compilation;
    # what init left on one device (scalars, a restored checkpoint) is
    # replicated over the mesh
    state_shardings = jax.tree.map(
        lambda x: (
            x.sharding if isinstance(x.sharding, NamedSharding)
            else NamedSharding(mesh, P())
        ),
        (params, opt_state, sp_state),
    )
    params, opt_state, sp_state = jax.device_put(
        (params, opt_state, sp_state), state_shardings
    )

    def jit_step(fn):
        return jax.jit(
            fn,
            out_shardings=(
                *state_shardings, NamedSharding(mesh, P())
            ),
        )

    step_fn = jit_step(asm.train_step)
    pred_b, meas_b = comm_round_bytes(asm.plan, dist, mesh)
    round_cost = comm_round_cost(asm.plan, dist, mesh)
    print(
        f"comm: codec={dist.codec} collective={dist.resolved_collective()} "
        f"{meas_b / 1e6:.3f} MB/worker/round "
        f"(predicted {pred_b / 1e6:.3f} MB, "
        f"{round_cost.seconds * 1e3:.3f} ms/round under the link model)",
        flush=True,
    )
    if dist.resolved_overlap() is not None:
        from repro.core.distributed import comm_round_timeline

        bplan, tline = comm_round_timeline(asm.plan, dist, mesh)
        print(
            f"comm:   overlap {bplan.n_buckets} buckets "
            f"({dist.overlap}): {tline.sync_seconds * 1e3:.3f} ms sync -> "
            f"{tline.seconds * 1e3:.3f} ms overlapped",
            flush=True,
        )
    if dist.codec == "auto" or dist.resolved_collective() == "auto":
        from collections import Counter

        from repro.core.distributed import LeafPlan, leaf_wire

        picks = Counter(
            leaf_wire(p, dist)
            for p in jax.tree.leaves(
                asm.plan, is_leaf=lambda x: isinstance(x, LeafPlan)
            )
        )
        for (c, s), n in sorted(picks.items()):
            print(f"comm:   auto-plan {c}/{s}: {n} leaves", flush=True)
    from repro.core.distributed import LeafPlan, leaf_fastpath

    leaves = jax.tree.leaves(
        asm.plan, is_leaf=lambda x: isinstance(x, LeafPlan)
    )
    n_fused = sum(leaf_fastpath(p, dist) for p in leaves)
    if dist.resolved_fastpath() != "off":
        print(
            f"comm:   fastpath: {n_fused}/{len(leaves)} leaves fused",
            flush=True,
        )
    sp = dist.sparsifier
    if sp.kind in ("topk", "regtopk") and sp.selector == "exact":
        from repro.core.compact import top_k_group

        two = [
            p
            for p in leaves
            if not leaf_fastpath(p, dist) and top_k_group(p.local_len, p.k) > 1
        ]
        share = sum(p.local_len for p in two) / sum(p.local_len for p in leaves)
        print(
            f"select: two-level top-k on {len(two)}/{len(leaves)} leaves "
            f"({share:.1%} of the gradient's elements)",
            flush=True,
        )
    plan = asm.plan
    losses, fallbacks = [], []
    with mesh:
        batch = pipe.batch_at(start)
        t0 = time.perf_counter()
        compiled = step_fn.lower(params, opt_state, sp_state, batch).compile()
        compile_s = time.perf_counter() - t0
        print(f"compile: {compile_s:.2f}s", flush=True)
        t0 = time.perf_counter()
        for t in range(start, start + args.steps):
            if t > start:
                batch = pipe.batch_at(t)
            params, opt_state, sp_state, m = step_fn(
                params, opt_state, sp_state, batch
            )
            losses.append(m["loss"])
            fallbacks.append(m["fastpath_fallbacks"])
            if t == start:
                # steady timing starts once the first step has finished
                jax.block_until_ready(m)
                t_steady = time.perf_counter()
            if t % args.log_every == 0 or t == start + args.steps - 1:
                dt = time.perf_counter() - t0
                extra = (
                    f" k {float(m['adaptive_k']):7.1f}"
                    if "adaptive_k" in m else ""
                )
                print(
                    f"step {t:5d} loss {float(m['loss']):.4f}{extra} "
                    f"({dt / max(1, t - start + 1):.2f}s/step)",
                    flush=True,
                )
            is_last = t == start + args.steps - 1
            if (
                args.replan_every
                and not is_last
                and (t - start + 1) % args.replan_every == 0
            ):
                plan, step_fn = _replan(
                    dist, mesh, dp_axes, plan, step_fn, sp_state,
                    mod, cfg, asm, t, jit_step,
                )
        jax.block_until_ready((params, opt_state, sp_state))
    step_s = None
    if args.steps > 1:
        step_s = (time.perf_counter() - t_steady) / (args.steps - 1)
    if args.checkpoint:
        save(args.checkpoint + "/params", params,
             metadata={"step": start + args.steps})
        save(args.checkpoint + "/opt", opt_state)
        save(args.checkpoint + "/sparsifier", sp_state)
        print(f"checkpointed to {args.checkpoint}")
    return TrainRun(
        losses=[float(x) for x in losses],
        fallbacks=[float(x) for x in fallbacks],
        compile_seconds=compile_s,
        step_seconds=step_s,
        n_fused=n_fused,
        n_leaves=len(leaves),
        compiled=compiled,
        params=params,
        sp_state=sp_state,
    )


if __name__ == "__main__":
    main()
