"""Plain reference of the first training steps.

Per worker, the gradient of its rows' mean loss (summed over blocks of
rows, so that it fits); then the sparsifier (RegTop-k, arXiv:2501.05633
Algorithm 2; Top-k; or none) with each worker's error accumulator; the
workers' sent vectors averaged; then Adam. It imports nothing of the
program and takes nothing the program made: the weights and batches come
from the seed, through the configuration's ``init`` and
``feed.make_batch``. Matrix products run at ``highest`` precision;
``dtype=bfloat16`` computes all of it in bfloat16 and is the control.
"""
from __future__ import annotations

import functools
import math
from typing import Dict, List, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from bench import feed


class Readings(NamedTuple):
    """What is compared: per-step loss, and per leaf the norm of the first
    aggregated gradient (what Adam is handed) and of the weights' change
    over the steps."""

    losses: List[float]
    grad_norms: Dict[str, float]
    delta_norms: Dict[str, float]


def leaf_names(tree) -> List[str]:
    return [jax.tree_util.keystr(p) for p, _ in
            jax.tree.leaves_with_path(tree)]


def leaf_norms(leaves) -> jax.Array:
    """Per-leaf L2 norms in float32."""
    return jnp.stack([jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
                      for x in leaves])


def sparsity_to_k(length: int, sparsity: float) -> int:
    """k = ceil(S * J), read a few ulps below so that an integer product
    does not round up, within [1, J]."""
    target = sparsity * length
    return max(1, min(length, math.ceil(target - 1e-9 * max(1.0, target))))


def _worker_round(kind, ks, mu, omega, first, g, eps, prev_idx, prev_vals,
                  prev_agg):
    """One worker, every leaf: add the gradient to the error accumulator,
    score, keep the k best. RegTop-k scales the magnitude of a coordinate
    sent last round by tanh(|1 + (g_prev - omega * a_prev) / (omega * a)| /
    mu), where a_prev is what this worker sent there and g_prev the
    aggregate there. Returns the dense sent vectors, the new accumulators,
    and the sent coordinates with their values."""
    out = ([], [], [], [])
    for i, k in enumerate(ks):
        a = eps[i] + g[i]
        mag = jnp.abs(a)
        score = mag
        if kind == "regtopk" and not first:
            j = prev_idx[i]
            denom = omega * a[j]
            delta = (prev_agg[i] - omega * prev_vals[i]) / jnp.where(
                denom == 0, 1, denom)
            score = mag.at[j].set(mag[j] * jnp.tanh(jnp.abs(1 + delta) / mu))
        _, idx = jax.lax.top_k(score, k)
        vals = jnp.where(score[idx] > 0, a[idx], 0)
        sent = jnp.zeros_like(a).at[idx].set(vals)
        for lst, x in zip(out, (sent, a - sent, idx, vals), strict=True):
            lst.append(x)
    return out


def _adam(lr, b1, b2, eps, t, params, m, v, g):
    out = ([], [], [])
    for p, m_, v_, g_ in zip(params, m, v, g, strict=True):
        m_ = b1 * m_ + (1 - b1) * g_
        v_ = b2 * v_ + (1 - b2) * jnp.square(g_)
        mh = m_ / (1 - b1 ** (t + 1)).astype(p.dtype)
        vh = v_ / (1 - b2 ** (t + 1)).astype(p.dtype)
        for lst, x in zip(out, (p - lr * mh / (jnp.sqrt(vh) + eps), m_, v_),
                          strict=True):
            lst.append(x)
    return out


def _flat_grad(model, m, params, rows, share):
    loss, g = jax.value_and_grad(lambda p: model.loss(p, rows, m))(params)
    return loss, [share * x.reshape(-1) for x in jax.tree.leaves(g)]


def run(model, m, traffic, seed: int, n_workers: int, steps: int = 3,
        dtype=jnp.float32, device=None) -> Readings:
    """The reference's readings over the first ``steps`` steps, every
    worker, the aggregate and Adam on ``device`` (default: the first), so
    that each of its functions compiles once, however many workers."""
    b, seq = traffic["batch_per_chip"], traffic["seq"]
    sp, opt = traffic["sparsifier"], traffic["optimizer"]
    kind, omega = sp["kind"], 1.0 / n_workers
    R = model.REF_ROWS
    home = device or jax.devices()[0]
    put = functools.partial(jax.device_put, device=home)
    with jax.default_matmul_precision("highest"):
        init = jax.jit(lambda k: model.init(k, m, jnp.float32))
        params = jax.tree.map(lambda x: x.astype(dtype),
                              put(init(feed.weights_key(seed))))
        names = leaf_names(params)
        treedef = jax.tree.structure(params)
        shapes = [x.shape for x in jax.tree.leaves(params)]
        flat = [x.reshape(-1) for x in jax.tree.leaves(params)]
        ks = tuple(sparsity_to_k(x.size, sp["sparsity"]) for x in flat)
        zeros = lambda: put([jnp.zeros_like(x) for x in flat])
        eps = [zeros() for _ in range(n_workers)]
        prev = [(None, None, None)] * n_workers
        adam_m, adam_v = zeros(), zeros()
        make_batch = jax.jit(functools.partial(
            feed.make_batch, model=m, batch=b * n_workers, seq=seq))
        grad = jax.jit(functools.partial(_flat_grad, model, m))
        add = jax.jit(lambda acc, g: [x + y for x, y in zip(acc, g,
                                                            strict=True)],
                      donate_argnums=0)
        rounds = {first: jax.jit(functools.partial(
            _worker_round, kind, ks, sp.get("mu", 1.0), omega, first))
            for first in (True, False)}
        adam = jax.jit(functools.partial(
            _adam, opt["lr"], opt["b1"], opt["b2"], opt["eps"]),
            donate_argnums=(1, 2, 3))
        losses, grad_norms = [], None
        for t in range(steps):
            batch = put(make_batch(feed.batches_key(seed), t))
            tree = jax.tree.unflatten(
                treedef, [x.reshape(s) for x, s in zip(flat, shapes,
                                                       strict=True)])
            outs, step_loss = [], []
            for w in range(n_workers):  # dispatched without waiting
                g_w, end = None, (w + 1) * b
                for r in range(w * b, end, R):
                    rows = jax.tree.map(
                        lambda x, r=r: x[r:min(r + R, end)], batch)
                    share = rows["tokens"].shape[0] / b
                    lv, gv = grad(tree, rows, share)
                    step_loss.append(lv * (share / n_workers))
                    g_w = gv if g_w is None else add(g_w, gv)
                if kind == "none":
                    outs.append(g_w)
                    continue
                sent, eps[w], idx, vals = rounds[t == 0](
                    g_w, eps[w], *prev[w])
                outs.append(sent)
                prev[w] = (idx, vals)
            agg = None
            for o in outs:
                o = [omega * x for x in o]
                agg = o if agg is None else add(agg, o)
            if kind != "none":
                for w in range(n_workers):
                    idx, vals = prev[w]
                    prev[w] = (idx, vals, [a[i] for a, i in
                                           zip(agg, idx, strict=True)])
            del outs
            losses.append(sum(float(x) for x in step_loss))
            if t == 0:
                grad_norms = np.asarray(leaf_norms(agg)).tolist()
            flat, adam_m, adam_v = adam(jnp.int32(t), flat, adam_m, adam_v,
                                        agg)
            del agg
        start = [x.reshape(-1) for x in jax.tree.leaves(
            put(init(feed.weights_key(seed))))]
        delta = [x.astype(jnp.float32) - y for x, y in zip(flat, start,
                                                           strict=True)]
        return Readings(
            losses=losses,
            grad_norms=dict(zip(names, grad_norms, strict=True)),
            delta_norms=dict(zip(names, np.asarray(leaf_norms(delta)).tolist(),
                                 strict=True)),
        )
