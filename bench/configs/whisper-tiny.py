"""whisper-tiny: plain reference, seeded weights and analytic FLOPs.

The reference is the encoder-decoder in straightforward ``jax.numpy``: it
imports nothing of the program. It follows the repository's model, whose
departures from Whisper are listed under ``assumed`` in the JSON beside
this file. Every array takes the dtype of the weights it is given, so the
same code in bfloat16 is the control.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

REF_ROWS = 8  # rows per reference block: what one block's gradient holds


def padded_vocab(m):
    return -(-m["vocab"] // m["vocab_pad_multiple"]) * m["vocab_pad_multiple"]


def param_shapes(m):
    """Every leaf's shape, in the program's tree: layer stacks lead."""
    E, H, D, F = m["d_model"], m["n_heads"], m["head_dim"], m["d_ff"]

    def attn(n):
        return {"wq": (n, E, H, D), "wk": (n, E, H, D), "wv": (n, E, H, D),
                "wo": (n, H, D, E)}

    def norm(n):
        return {"scale": (n, E), "bias": (n, E)}

    Le, Ld = m["n_enc_layers"], m["n_layers"]
    return {
        "embed": {"embedding": (padded_vocab(m), E)},
        "enc_layers": {"attn": attn(Le), "mlp": {"wu": (Le, E, F),
                       "wd": (Le, F, E)}, "norm1": norm(Le),
                       "norm2": norm(Le)},
        "dec_layers": {"self": attn(Ld), "cross": attn(Ld),
                       "mlp": {"wu": (Ld, E, F), "wd": (Ld, F, E)},
                       "norm1": norm(Ld), "norm2": norm(Ld),
                       "norm3": norm(Ld)},
        "enc_norm": {"scale": (E,), "bias": (E,)},
        "final_norm": {"scale": (E,), "bias": (E,)},
    }


def param_count(m):
    return _size(param_shapes(m))


def init(key, m, dtype=jnp.float32):
    """Seeded weights at the repository's init scales: 0.02 for the
    embedding, fan-in**-0.5 for projections, ones and zeros for norms."""
    E, F = m["d_model"], m["d_ff"]
    shapes = param_shapes(m)
    paths = jax.tree.leaves_with_path(
        shapes, is_leaf=lambda x: isinstance(x, tuple))

    def leaf(i, path, shape):
        name = jax.tree_util.keystr(path)
        if "scale" in name:
            return jnp.ones(shape, dtype)
        if "bias" in name:
            return jnp.zeros(shape, dtype)
        if "embedding" in name:
            std = 0.02
        elif "'wd'" in name:
            std = F**-0.5
        else:
            std = E**-0.5
        k = jax.random.fold_in(key, i)
        return (std * jax.random.normal(k, shape, jnp.float32)).astype(dtype)

    leaves = [leaf(i, p, s) for i, (p, s) in enumerate(paths)]
    return jax.tree.unflatten(
        jax.tree.structure(shapes, is_leaf=lambda x: isinstance(x, tuple)),
        leaves)


def _layernorm(p, x, eps):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * p["scale"] + p["bias"]


def _rope(x, base):
    """Rotate the two halves of each head by position: [B, S, H, D]."""
    S, D = x.shape[1], x.shape[-1]
    half = D // 2
    freq = base ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * freq
    cos = jnp.cos(ang)[None, :, None, :].astype(x.dtype)
    sin = jnp.sin(ang)[None, :, None, :].astype(x.dtype)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(p, x, src, causal, rotary, base):
    q = jnp.einsum("bse,ehd->bshd", x, p["wq"])
    k = jnp.einsum("bse,ehd->bshd", src, p["wk"])
    v = jnp.einsum("bse,ehd->bshd", src, p["wv"])
    if rotary:
        q, k = _rope(q, base), _rope(k, base)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / math.sqrt(q.shape[-1])
    if causal:
        n = x.shape[1]
        s = jnp.where(jnp.tril(jnp.ones((n, n), bool)), s, -jnp.inf)
    ctx = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1), v)
    return jnp.einsum("bshd,hde->bse", ctx, p["wo"])


def _gelu(x):
    """GELU, tanh form."""
    c = math.sqrt(2.0 / math.pi)
    return 0.5 * x * (1.0 + jnp.tanh(c * (x + 0.044715 * x**3)))


def _mlp(p, x):
    return _gelu(x @ p["wu"]) @ p["wd"]


def loss(params, batch, m):
    """Mean next-token cross-entropy over the rows of ``batch``."""
    eps, base = m["norm_eps"], m["rope_base"]
    dt = params["embed"]["embedding"].dtype

    def enc_layer(x, lp):
        h = _layernorm(lp["norm1"], x, eps)
        x = x + _attention(lp["attn"], h, h, False, True, base)
        return x + _mlp(lp["mlp"], _layernorm(lp["norm2"], x, eps)), None

    x, _ = jax.lax.scan(jax.checkpoint(enc_layer),
                        batch["frames"].astype(dt), params["enc_layers"])
    enc = _layernorm(params["enc_norm"], x, eps)

    def dec_layer(x, lp):
        h = _layernorm(lp["norm1"], x, eps)
        x = x + _attention(lp["self"], h, h, True, True, base)
        h = _layernorm(lp["norm2"], x, eps)
        x = x + _attention(lp["cross"], h, enc, False, False, base)
        return x + _mlp(lp["mlp"], _layernorm(lp["norm3"], x, eps)), None

    emb = params["embed"]["embedding"]
    x, _ = jax.lax.scan(jax.checkpoint(dec_layer), emb[batch["tokens"]],
                        params["dec_layers"])
    x = _layernorm(params["final_norm"], x, eps)
    logits = jnp.einsum("bse,ve->bsv", x, emb)
    real = jnp.arange(emb.shape[0]) < m["vocab"]
    logits = jnp.where(real, logits, -jnp.inf)
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, batch["labels"][..., None], -1)[..., 0]
    return jnp.mean(logz - gold)


def flops_per_step(m, rows, seq):
    """Model FLOPs of one training step over ``rows`` examples: forward
    and backward (three times the forward's 2 FLOPs per multiply-add),
    nothing recomputed. Per example: every projection, once per frame or
    token; attention scores and values (causal self-attention over the
    lower triangle); cross-attention keys and values over the frames; the
    tied output head over the published vocabulary."""
    E, S_e = m["d_model"], m["enc_seq"]
    HD = m["n_heads"] * m["head_dim"]
    Le, Ld = m["n_enc_layers"], m["n_layers"]
    sh = param_shapes(m)
    enc_l, dec_l = sh["enc_layers"], sh["dec_layers"]
    enc = S_e * (_size(enc_l["attn"]) + _size(enc_l["mlp"]))
    enc += Le * 2 * HD * S_e * S_e  # bidirectional self-attention
    cross = dec_l["cross"]
    dec = seq * (_size(dec_l["self"]) + _size(dec_l["mlp"])
                 + _size([cross["wq"], cross["wo"]]))
    dec += S_e * _size([cross["wk"], cross["wv"]])  # over the frames
    dec += Ld * 2 * HD * seq * (seq + 1) // 2  # causal self-attention
    dec += Ld * 2 * HD * seq * S_e  # cross-attention
    dec += seq * E * m["vocab"]  # tied head, published vocabulary
    return 6 * rows * (enc + dec)


def _size(tree):
    return sum(math.prod(s) for s in jax.tree.leaves(
        tree, is_leaf=lambda x: isinstance(x, tuple)))
