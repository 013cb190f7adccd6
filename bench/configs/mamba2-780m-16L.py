"""mamba2-780m, 16 of its 48 layers: plain reference, seeded weights and
analytic FLOPs.

The reference is Mamba-2 in straightforward ``jax.numpy``: it imports
nothing of the program. Its state-space layer is the quadratic (masked
attention-like) form of SSD from arXiv:2405.21060, section 6: every output
position sums over all earlier positions, with the decay between them
taken from the sequence's cumulative sum, and no chunks. Every
array takes the dtype of the weights it is given, so the same code in
bfloat16 is the control.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

REF_ROWS = 1  # rows per reference block: the quadratic form is S x S a head
CONV_K = 4


def padded_vocab(m):
    return -(-m["vocab"] // m["vocab_pad_multiple"]) * m["vocab_pad_multiple"]


def _dims(m):
    E, N, P = m["d_model"], m["ssm_state"], m["ssm_headdim"]
    Di = m["ssm_expand"] * E
    return E, Di, N, P, Di // P


def param_shapes(m):
    """Every leaf's shape, in the program's tree: layer stacks lead."""
    E, Di, N, _, H = _dims(m)
    L = m["n_layers"]
    return {
        "embed": {"embedding": (padded_vocab(m), E)},
        "trailing": {
            "mixer": {
                "in_proj": (L, E, 2 * Di + 2 * N + H),
                "conv_w": (L, CONV_K, Di + 2 * N),
                "conv_b": (L, Di + 2 * N),
                "A_log": (L, H),
                "D": (L, H),
                "dt_bias": (L, H),
                "norm": (L, Di),
                "out_proj": (L, Di, E),
            },
            "norm": {"scale": (L, E)},
        },
        "final_norm": {"scale": (E,)},
    }


def _size(tree):
    return sum(math.prod(s) for s in jax.tree.leaves(
        tree, is_leaf=lambda x: isinstance(x, tuple)))


def param_count(m):
    return _size(param_shapes(m))


def init(key, m, dtype=jnp.float32):
    """Seeded weights: 0.02 for the embedding, fan-in**-0.5 for the
    projections, 0.1 for the convolution; A_log over 1..16 and dt_bias
    from Mamba-2's init (softplus(dt_bias) log-uniform in [1e-3, 0.1])."""
    E, Di, _, _, H = _dims(m)
    shapes = param_shapes(m)
    paths = jax.tree.leaves_with_path(
        shapes, is_leaf=lambda x: isinstance(x, tuple))

    def leaf(i, path, shape):
        name = jax.tree_util.keystr(path)
        k = jax.random.fold_in(key, i)
        normal = lambda std: std * jax.random.normal(k, shape, jnp.float32)
        if "scale" in name or "'norm'" in name or "'D'" in name:
            x = jnp.ones(shape, jnp.float32)
        elif "conv_b" in name:
            x = jnp.zeros(shape, jnp.float32)
        elif "A_log" in name:
            x = jnp.broadcast_to(
                jnp.log(jnp.linspace(1.0, 16.0, H, dtype=jnp.float32)), shape)
        elif "dt_bias" in name:
            u = jax.random.uniform(k, shape, jnp.float32)
            dt = jnp.exp(u * (math.log(0.1) - math.log(1e-3)) + math.log(1e-3))
            x = dt + jnp.log(-jnp.expm1(-dt))  # softplus**-1
        elif "embedding" in name:
            x = normal(0.02)
        elif "conv_w" in name:
            x = normal(0.1)
        elif "out_proj" in name:
            x = normal(Di**-0.5)
        else:
            x = normal(E**-0.5)
        return x.astype(dtype)

    leaves = [leaf(i, p, s) for i, (p, s) in enumerate(paths)]
    return jax.tree.unflatten(
        jax.tree.structure(shapes, is_leaf=lambda x: isinstance(x, tuple)),
        leaves)


def _rmsnorm(x, scale, eps):
    return x / jnp.sqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps) * scale


def _segsum(x):
    """x [..., T] -> [..., T, T]: entry (i, j) is x[j+1] + ... + x[i] for
    i >= j (the difference of two cumulative sums over the whole
    sequence) and -inf above the diagonal."""
    T = x.shape[-1]
    cs = jnp.cumsum(x, axis=-1)
    d = cs[..., :, None] - cs[..., None, :]
    return jnp.where(jnp.tril(jnp.ones((T, T), bool)), d, -jnp.inf)


def _mixer(p, h, m):
    _, Di, N, P, H = _dims(m)
    B, S, _ = h.shape
    zxbcdt = h @ p["in_proj"]
    z = zxbcdt[..., :Di]
    xbc = zxbcdt[..., Di:2 * Di + 2 * N]
    dt = zxbcdt[..., 2 * Di + 2 * N:]
    xp = jnp.pad(xbc, ((0, 0), (CONV_K - 1, 0), (0, 0)))
    conv = sum(xp[:, k:k + S] * p["conv_w"][k] for k in range(CONV_K))
    xbc = jax.nn.silu(conv + p["conv_b"])
    x = xbc[..., :Di].reshape(B, S, H, P)
    Bm, Cm = xbc[..., Di:Di + N], xbc[..., Di + N:]
    dt = jax.nn.softplus(dt + p["dt_bias"])  # [B, S, H]
    A = -jnp.exp(p["A_log"])
    decay = jnp.exp(_segsum((dt * A).transpose(0, 2, 1)))  # [B, H, S, S]
    M = (decay * jnp.einsum("bin,bjn->bij", Cm, Bm)[:, None]
         * dt.transpose(0, 2, 1)[:, :, None, :])
    y = jnp.einsum("bhij,bjhp->bihp", M, x) + p["D"][:, None] * x
    y = y.reshape(B, S, Di) * jax.nn.silu(z)
    return _rmsnorm(y, p["norm"], m["norm_eps"]) @ p["out_proj"]


def loss(params, batch, m):
    """Mean next-token cross-entropy over the rows of ``batch``."""
    eps = m["norm_eps"]
    emb = params["embed"]["embedding"]

    def layer(x, lp):
        h = _rmsnorm(x, lp["norm"]["scale"], eps)
        return x + _mixer(lp["mixer"], h, m), None

    x, _ = jax.lax.scan(jax.checkpoint(layer), emb[batch["tokens"]],
                        params["trailing"])
    x = _rmsnorm(x, params["final_norm"]["scale"], eps)
    logits = jnp.einsum("bse,ve->bsv", x, emb)
    real = jnp.arange(emb.shape[0]) < m["vocab"]
    logits = jnp.where(real, logits, -jnp.inf)
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, batch["labels"][..., None], -1)[..., 0]
    return jnp.mean(logz - gold)


def flops_per_step(m, rows, seq):
    """Model FLOPs of one training step over ``rows`` sequences: forward
    and backward (three times the forward's 2 FLOPs per multiply-add),
    nothing recomputed. Per token: the projections, the depthwise
    convolution, chunked SSD (the lower triangle of the chunk's C.B and
    its product with x, then the chunk states and the output they give),
    and the tied head over the published vocabulary."""
    E, Di, N, P, H = _dims(m)
    L, Q = m["n_layers"], m["ssm_chunk"]
    mixer = param_shapes(m)["trailing"]["mixer"]
    proj = _size([mixer["in_proj"], mixer["out_proj"]])
    conv = L * CONV_K * (Di + 2 * N)
    ssd = L * (Q * N // 2 + H * Q * P // 2 + 2 * H * P * N)
    head = E * m["vocab"]
    return 6 * rows * seq * (proj + conv + ssd + head)
