"""Seeds and the token feed, made on the device from ``(seed, step)``.

The generator copies ``repro.data.pipeline.TokenPipeline.batch_at``
(Zipf-ish tokens, labels that half the time repeat a token three back,
encoder frames for enc-dec models), so that the benchmark owns its inputs
and the reference takes nothing the program made.
"""
from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp

# fold-in tags that keep the weights' and the batches' streams apart
_WEIGHTS, _BATCHES = 0, 1


def root_key(seed: int) -> jax.Array:
    """A key from any whole seed below 2**64: ``PRNGKey`` keeps only the
    low 32 bits of a large seed, so the high bits are folded in."""
    if not 0 <= seed < 2**64:
        raise ValueError(f"seed {seed} is not a whole number below 2**64")
    return jax.random.fold_in(
        jax.random.PRNGKey(seed & 0xFFFFFFFF), seed >> 32
    )


def weights_key(seed: int) -> jax.Array:
    return jax.random.fold_in(root_key(seed), _WEIGHTS)


def batches_key(seed: int) -> jax.Array:
    return jax.random.fold_in(root_key(seed), _BATCHES)


def make_batch(key: jax.Array, step, model: Dict[str, Any], batch: int,
               seq: int) -> Dict[str, jax.Array]:
    """The global batch of ``step``: every row differs, and every step's
    batch differs. Traceable: jit it with the batch's sharding."""
    ks = jax.random.split(jax.random.fold_in(key, step), 4)
    V = model["vocab"]
    u = jax.random.uniform(ks[0], (batch, seq))
    tokens = jnp.minimum((u * u * V).astype(jnp.int32), V - 1)
    flip = jax.random.bernoulli(ks[1], 0.5, (batch, seq))
    labels = jnp.where(
        flip, jnp.roll(tokens, 3, axis=1), jnp.roll(tokens, -1, axis=1)
    )
    out = {"tokens": tokens, "labels": labels}
    if model["family"] == "encdec":
        out["frames"] = 0.1 * jax.random.normal(
            ks[2], (batch, model["enc_seq"], model["d_model"]), jnp.float32
        )
    return out
