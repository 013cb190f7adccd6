"""Faults planted under the timed path, to show that ``correct`` catches
them: by the tests at a small size, and by ``calibrate.py`` at the cell's
own size on the chip.
"""
from __future__ import annotations

import contextlib

import jax
import jax.numpy as jnp


def unchanged_state(train_step):
    """A step that returns its state unchanged (where the program donates
    its state, the outputs alias the inputs: the same buffers, handed
    back)."""
    def step(params, opt_state, sp_state, batch):
        metrics = train_step(params, opt_state, sp_state, batch)[3]
        return params, opt_state, sp_state, metrics
    return step


def half_batch(train_step):
    """A step that leaves out the second half of the batch and takes the
    mean over the rest (the first half, seen twice)."""
    def step(params, opt_state, sp_state, batch):
        half = jax.tree.map(
            lambda x: jnp.concatenate([x[:x.shape[0] // 2]] * 2), batch)
        return train_step(params, opt_state, sp_state, half)
    return step


@contextlib.contextmanager
def no_exchange():
    """While open, the sparse all-gather leaves out the exchange between
    chips: each worker aggregates its own payload alone. Build the step
    inside it."""
    from repro.comm import collectives

    cls = collectives.SparseAllgather
    shard = cls.shard

    def local(self, codec, payload, length, axis_names, weight,
              participation=None):
        vals, idx = codec.decode(payload, length)
        return jnp.zeros((length,), vals.dtype).at[idx].add(vals * weight)

    cls.shard = local
    try:
        yield
    finally:
        cls.shard = shard


FAULTS = {
    "unchanged_state": unchanged_state,
    "half_batch": half_batch,
}
