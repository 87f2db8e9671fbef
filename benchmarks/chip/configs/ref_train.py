"""Plain reference of a training step's optimizer and of the readings that
``correct`` compares for training cells.

The optimizer follows the mix's ``optimizer`` entry as written there
(AdamW with bias correction and decoupled weight decay, or SGD with
momentum and L2 weight decay), after clipping the gradient by its global
norm; the learning rate is a linear warm-up followed by a cosine decay to
zero.  It imports nothing of the program.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

tmap = jax.tree_util.tree_map


def learning_rate(o: dict, step: int) -> float:
    lr, total, warm = o["lr"], o["total_steps"], o["warmup"]
    if step < warm:
        return lr * step / max(warm, 1)
    t = min(max((step - warm) / max(total - warm, 1), 0.0), 1.0)
    return 0.5 * lr * (1 + math.cos(math.pi * t))


def _norms(tree) -> jax.Array:
    return jnp.stack([jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
                      for x in jax.tree_util.tree_leaves(tree)])


# The 2-norm of every leaf, in ``tree_leaves`` order.
leaf_norms = jax.jit(_norms)


def _opt_init(o, params):
    z = lambda: tmap(jnp.zeros_like, params)
    return {"m": z(), "v": z()} if o["kind"] == "adamw" else {"m": z()}


@jax.jit
def _global_norm(g):
    return jnp.sqrt(sum(jnp.sum(jnp.square(x))
                        for x in jax.tree_util.tree_leaves(g)))


def _update(o, params, grads, state, lr, count):
    """One optimizer step; ``params`` and ``state`` are donated."""
    if o["kind"] == "adamw":
        b1, b2, eps, wd = o["b1"], o["b2"], o["eps"], o["weight_decay"]
        bc1, bc2 = 1 - b1 ** count, 1 - b2 ** count

        def leaf(p, g, m, v):
            m = b1 * m + (1 - b1) * g
            v = b2 * v + (1 - b2) * g * g
            step = (m / bc1) / (jnp.sqrt(v / bc2) + eps) + wd * p
            return p - lr * step, m, v

        out = tmap(leaf, params, grads, state["m"], state["v"])
        pick = lambda i: tmap(lambda t: t[i], out,
                              is_leaf=lambda t: isinstance(t, tuple))
        return pick(0), {"m": pick(1), "v": pick(2)}
    mom, wd = o["momentum"], o["weight_decay"]

    def leaf(p, g, m):
        m = mom * m + g + wd * p
        return p - lr * m, m

    out = tmap(leaf, params, grads, state["m"])
    pick = lambda i: tmap(lambda t: t[i], out,
                          is_leaf=lambda t: isinstance(t, tuple))
    return pick(0), {"m": pick(1)}


def train_readings(o: dict, init, loss_and_grad, batches) -> dict:
    """Follow ``len(batches)`` steps from ``init()`` and return the
    readings: each step's loss, the first step's global gradient norm
    before clipping, the first clipped gradient's and the whole change's
    norm per leaf.

    ``loss_and_grad(params, batch)`` gives the step's loss and gradient;
    ``init()`` gives the initial parameters, and is called again at the end
    rather than keeping a copy, to leave the chip's memory to the steps.
    """
    params = init()
    state = _opt_init(o, params)
    update = jax.jit(lambda p, g, s, lr, n: _update(o, p, g, s, lr, n),
                     donate_argnums=(0, 1, 2))
    clip = jax.jit(lambda g, n: tmap(
        lambda x: x * jnp.minimum(1.0, o["clip_norm"] / jnp.maximum(n, 1e-12)),
        g), donate_argnums=(0,))
    losses, out = [], {}
    for k, batch in enumerate(batches):
        loss, grads = loss_and_grad(params, batch)
        norm = _global_norm(grads)
        grads = clip(grads, norm)
        if k == 0:
            out["grad_norm"] = float(norm)
            out["grad_leaf_norms"] = np.asarray(leaf_norms(grads))
        params, state = update(params, grads, state,
                               jnp.float32(learning_rate(o, k)),
                               jnp.float32(k + 1))
        losses.append(float(loss))
        del grads
    del state
    out["losses"] = losses
    out["change_leaf_norms"] = np.asarray(jax.jit(
        lambda p, p0: _norms(tmap(jnp.subtract, p, p0)))(params, init()))
    return out
