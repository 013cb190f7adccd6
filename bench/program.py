"""The system under test: the train step that ``repro.launch.train.main``
builds, with the benchmark's seeded weights and feed.

What is taken from the program: ``DistConfig``, ``assemble``, the step it
returns, ``init_sparsifier_state``, the optimizer's ``init``, the mesh, and
``comm_round_bytes``. The step is jitted as ``train.main`` jits it: state
out-shardings equal to the in-shardings, no donation.
"""
from __future__ import annotations

import functools
from typing import Any, Callable, NamedTuple

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

from bench import feed
from repro.compat import make_mesh
from repro.core.distributed import (
    DistConfig,
    assemble,
    comm_round_bytes,
    init_sparsifier_state,
)
from repro.core.sparsify import SparsifierConfig
from repro.launch import mesh as meshlib
from repro.models import get_family
from repro.models.config import ModelConfig
from repro.optim import OptConfig, make_optimizer


class Program(NamedTuple):
    step: Callable  # (params, opt_state, sp_state, batch) -> (..., metrics)
    init_state: Callable  # seed -> (params, opt_state, sp_state)
    batch: Callable  # (key, step) -> global batch, sharded over the workers
    mesh: Any
    n_workers: int
    wire_bytes: int  # payload bytes per worker per round, from the plan
    cfg: ModelConfig


def dist_config(traffic, dp_axes) -> DistConfig:
    sp, opt = traffic["sparsifier"], traffic["optimizer"]
    return DistConfig(
        sparsifier=SparsifierConfig(
            kind=sp["kind"], sparsity=sp["sparsity"], mu=sp["mu"]),
        optimizer=OptConfig(
            kind="adam", learning_rate=opt["lr"], b1=opt["b1"],
            b2=opt["b2"], eps=opt["eps"]),
        aggregation=traffic["collective"],
        codec=traffic["codec"],
        collective=traffic["collective"],
        dp_axes=dp_axes,
        fastpath=traffic["fastpath"],
    )


def build(model, config, traffic, devices) -> Program:
    """Assemble the step over ``devices`` (one data-parallel worker each)
    for the cell's configuration and traffic."""
    cfg = ModelConfig(**config["model"])
    mesh = make_mesh((len(devices), 1), ("data", "model"), devices=devices)
    dp_axes = meshlib.dp_axes_of(mesh)
    dist = dist_config(traffic, dp_axes)
    mod = get_family(cfg)
    asm = assemble(mod, cfg, dist, mesh)
    param_sh = jax.tree.map(lambda s: NamedSharding(mesh, s), asm.param_specs)
    want = jax.tree.map(lambda s: tuple(s), model.param_shapes(config["model"]),
                        is_leaf=lambda x: isinstance(x, tuple))
    got = jax.tree.map(lambda x: tuple(x.shape), asm.params_shape)
    if want != got:
        raise ValueError("the configuration's parameter tree differs from "
                         f"the program's: {want} vs {got}")
    opt = make_optimizer(dist.optimizer)
    W = len(devices)
    replicated = NamedSharding(mesh, P())

    def init_state(seed: int):
        params = jax.jit(
            lambda k: model.init(k, config["model"], cfg.jdtype),
            out_shardings=param_sh)(feed.weights_key(seed))
        opt_state = jax.jit(opt.init)(params)
        sp_state, _ = init_sparsifier_state(
            asm.plan, W, mesh, dp_axes, jax.numpy.float32)
        # as train.main does: what init left on one device is replicated
        shard = lambda x: (x.sharding if isinstance(x.sharding, NamedSharding)
                           else replicated)
        state = (params, opt_state, sp_state)
        return jax.device_put(state, jax.tree.map(shard, state))

    batch = jax.jit(
        functools.partial(feed.make_batch, model=config["model"],
                          batch=traffic["batch_per_chip"] * W,
                          seq=traffic["seq"]),
        out_shardings=NamedSharding(mesh, P("data")))
    _, wire = comm_round_bytes(asm.plan, dist, mesh)
    return Program(asm.train_step, init_state, batch, mesh, W, int(wire), cfg)


def compile_step(prog: Program, state, batch, train_step=None):
    """Jit the step as ``train.main`` does (state handed back where it came
    from, metrics replicated, nothing donated) and compile it for this
    state and batch. ``train_step`` replaces the program's step (tests
    plant faults this way)."""
    sh = jax.tree.map(lambda x: x.sharding, state)
    jitted = jax.jit(train_step or prog.step,
                     out_shardings=(*sh, NamedSharding(prog.mesh, P())))
    return jitted.lower(*state, batch).compile()
