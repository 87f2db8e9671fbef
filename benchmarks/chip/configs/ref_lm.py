"""Plain float32 reference of the decoder-only LM of configs/<lm>.json.

Straightforward ``jax.numpy`` at ``highest`` matmul precision, with no
quantization, kernel, cache or batching trick, following the published
description of starcoder2 (pre-LayerNorm blocks with biases, grouped-query
attention with RoPE and a causal sliding window, a tanh-GELU MLP, an
untied LM head).  It imports nothing of the program.  Departures, none of
which changes the mathematics: attention and the loss are computed in
blocks of query rows under ``jax.checkpoint`` so that the reference fits
one chip beside nothing else.

The benchmark's weights come from ``init_params``: one jitted call from the
seed, in the layout the program's parameter tree has (``wq [D, KV, G, hd]``,
layers stacked on a leading axis), at the program's own init scales.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

HI = jax.lax.Precision.HIGHEST
tmap = jax.tree_util.tree_map


def param_specs(c: dict) -> dict:
    """``{path: (shape, init)}`` of every weight; ``init`` is a normal's
    scale, or "zeros" / "ones".  Layers are stacked on a leading axis."""
    L, d, h, kv, hd, f, v = (c["n_layers"], c["d_model"], c["n_heads"],
                             c["n_kv"], c["head_dim"], c["d_ff"], c["vocab"])
    g = h // kv
    blk = ("decoder", "blocks", "b0")
    specs = {("embed",): ((v, d), d ** -0.5), ("head",): ((d, v), d ** -0.5),
             ("final_norm", "scale"): ((d,), "ones"),
             ("final_norm", "bias"): ((d,), "zeros")}
    layer = {
        ("attn", "wq"): ((d, kv, g, hd), d ** -0.5),
        ("attn", "wk"): ((d, kv, hd), d ** -0.5),
        ("attn", "wv"): ((d, kv, hd), d ** -0.5),
        ("attn", "wo"): ((kv, g, hd, d), (h * hd) ** -0.5),
        ("attn", "bq"): ((kv, g, hd), "zeros"),
        ("attn", "bk"): ((kv, hd), "zeros"),
        ("attn", "bv"): ((kv, hd), "zeros"),
        ("attn", "bo"): ((d,), "zeros"),
        ("mlp", "w_up"): ((d, f), d ** -0.5),
        ("mlp", "w_down"): ((f, d), f ** -0.5),
        ("mlp", "b_up"): ((f,), "zeros"),
        ("mlp", "b_down"): ((d,), "zeros"),
        ("ln1", "scale"): ((d,), "ones"), ("ln1", "bias"): ((d,), "zeros"),
        ("ln2", "scale"): ((d,), "ones"), ("ln2", "bias"): ((d,), "zeros"),
    }
    specs.update({blk + k: ((L,) + shape, init)
                  for k, (shape, init) in layer.items()})
    return specs


def init_leaf(key, c: dict, path: tuple):
    """The weight at ``path``, drawn from ``key`` as ``init_params`` does."""
    specs = param_specs(c)
    shape, init = specs[path]
    if init == "zeros":
        return jnp.zeros(shape, jnp.float32)
    if init == "ones":
        return jnp.ones(shape, jnp.float32)
    k = jax.random.fold_in(key, sorted(specs).index(path))
    return jax.random.normal(k, shape, jnp.float32) * init


def init_params(key, c: dict) -> dict:
    """Random fp32 weights from ``key`` (call under ``jax.jit``)."""
    out: dict = {"decoder": {"tail": {}}}
    for path in param_specs(c):
        node = out
        for name in path[:-1]:
            node = node.setdefault(name, {})
        node[path[-1]] = init_leaf(key, c, path)
    return out


def fake_quant(x, bits, symmetric):
    """``x`` on a uniform ``bits``-bit grid over its own current range
    (per tensor), with a straight-through gradient: the lower precision
    of the control."""
    if bits is None:
        return x
    if symmetric:
        scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-8) / (2 ** (bits - 1) - 1)
        q = jnp.clip(jnp.round(x / scale), -(2 ** (bits - 1)),
                     2 ** (bits - 1) - 1) * scale
    else:
        lo, hi = jnp.minimum(jnp.min(x), 0.0), jnp.maximum(jnp.max(x), 0.0)
        scale = jnp.maximum(hi - lo, 1e-8) / (2 ** bits - 1)
        zero = jnp.round(-lo / scale)
        q = (jnp.clip(jnp.round(x / scale) + zero, 0, 2 ** bits - 1)
             - zero) * scale
    return x + jax.lax.stop_gradient(q - x)


def _dense(spec, x, w, bits):
    """A weight contraction; with ``bits``, of the quantized activation
    (asymmetric) and weight (symmetric), as the program's sites are."""
    return jnp.einsum(spec, fake_quant(x, bits, False),
                      fake_quant(w, bits, True), precision=HI)


def _layernorm(x, p, eps):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * p["scale"] + p["bias"]


def _gelu(x):
    return 0.5 * x * (1 + jnp.tanh(math.sqrt(2 / math.pi)
                                   * (x + 0.044715 * x ** 3)))


def _rope(x, pos, theta):
    """x [B, S, ..., hd]: rotate the two halves of the head dimension."""
    hd = x.shape[-1]
    freqs = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = pos[:, :, None].astype(jnp.float32) * freqs
    ang = ang.reshape(ang.shape[:2] + (1,) * (x.ndim - 3) + (hd // 2,))
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * jnp.cos(ang) - x2 * jnp.sin(ang),
                            x2 * jnp.cos(ang) + x1 * jnp.sin(ang)], -1)


def _chunks(n: int, size: int) -> list:
    return [(a, min(a + size, n)) for a in range(0, n, size)]


def _attention(q, k, v, c, q_block):
    """q [B,S,KV,G,hd], k/v [B,S,KV,hd]; causal, sliding window."""
    s = q.shape[1]
    window = c.get("sliding_window") or s
    kpos = jnp.arange(s)

    @jax.checkpoint
    def block(qb, q0):
        qpos = q0 + jnp.arange(qb.shape[1])
        sc = jnp.einsum("bqkgh,bskh->bkgqs", qb, k, precision=HI)
        sc = sc * c["head_dim"] ** -0.5
        ok = (kpos[None, :] <= qpos[:, None]) & \
            (qpos[:, None] - kpos[None, :] < window)
        sc = jnp.where(ok, sc, -jnp.inf)
        p = jax.nn.softmax(sc, axis=-1)
        return jnp.einsum("bkgqs,bskh->bqkgh", p, v, precision=HI)

    return jnp.concatenate([block(q[:, a:b], a)
                            for a, b in _chunks(s, q_block)], axis=1)


def _block(x, p, pos, c, q_block, bits):
    eps = c.get("norm_eps", 1e-5)
    a = p["attn"]
    h = _layernorm(x, p["ln1"], eps)
    q = _dense("bsd,dkgh->bskgh", h, a["wq"], bits) + a["bq"]
    k = _dense("bsd,dkh->bskh", h, a["wk"], bits) + a["bk"]
    v = _dense("bsd,dkh->bskh", h, a["wv"], bits) + a["bv"]
    q, k = _rope(q, pos, c["rope_theta"]), _rope(k, pos, c["rope_theta"])
    o = _attention(q, k, v, c, q_block)
    x = x + _dense("bskgh,kghd->bsd", o, a["wo"], bits) + a["bo"]
    m = p["mlp"]
    h = _layernorm(x, p["ln2"], eps)
    h = _gelu(_dense("bsd,df->bsf", h, m["w_up"], bits) + m["b_up"])
    return x + _dense("bsf,fd->bsd", h, m["w_down"], bits) + m["b_down"]


def hidden(params, tokens, c, q_block=1024, bits=None):
    """Final normed hidden states [B, S, D] of ``tokens`` [B, S]."""
    b, s = tokens.shape
    pos = jnp.broadcast_to(jnp.arange(s), (b, s))
    x = params["embed"][tokens]
    layers = params["decoder"]["blocks"]["b0"]
    for i in range(c["n_layers"]):
        p = tmap(lambda w: w[i], layers)
        x = jax.checkpoint(
            lambda x, p: _block(x, p, pos, c, q_block, bits))(x, p)
    return _layernorm(x, params["final_norm"], c.get("norm_eps", 1e-5))


def loss(params, tokens, labels, c, q_block=1024, bits=None):
    """Mean next-token cross-entropy over every position."""
    x = fake_quant(hidden(params, tokens, c, q_block, bits), bits, False)
    head = fake_quant(params["head"], bits, True)

    @jax.checkpoint
    def nll(xb, lb):
        logits = jnp.einsum("bsd,dv->bsv", xb, head, precision=HI)
        gold = jnp.take_along_axis(logits, lb[..., None], -1)[..., 0]
        return jnp.sum(jax.nn.logsumexp(logits, -1) - gold)

    s = tokens.shape[1]
    tot = sum(nll(x[:, a:b], labels[:, a:b]) for a, b in _chunks(s, q_block))
    return tot / labels.size


def logits_at(params, tokens, start, c, q_block=256):
    """Logits [B, S - start, V] of positions ``start`` .. S-1 of ``tokens``."""
    x = hidden(params, tokens, c, q_block)[:, start:]
    return jnp.einsum("bsd,dv->bsv", x, params["head"], precision=HI)


def loss_and_grad(c: dict, microbatches: int, bits=None):
    """``f(params, batch) -> (loss, grads)`` of a whole training batch,
    taken as the mean over ``microbatches`` equal row blocks, as the
    gradient of the mean loss over every position is.  ``bits`` computes
    it with every weight contraction's operands on a ``bits``-bit grid."""
    vg = jax.value_and_grad(lambda p, t, l: loss(p, t, l, c, bits=bits))
    first = jax.jit(vg)

    @jax.jit
    def scale(acc, tot):
        return tot / microbatches, tmap(lambda g: g / microbatches, acc)

    def more(p, acc, tot, t, l):
        val, g = vg(p, t, l)
        return tot + val, tmap(jnp.add, acc, g)

    more = jax.jit(more, donate_argnums=(1,))

    def f(params, batch):
        rows = batch["tokens"].shape[0] // microbatches
        tot, acc = None, None
        for i in range(microbatches):
            t = batch["tokens"][i * rows:(i + 1) * rows]
            l = batch["labels"][i * rows:(i + 1) * rows]
            if acc is None:
                tot, acc = first(params, t, l)
            else:
                tot, acc = more(params, acc, tot, t, l)
        return scale(acc, tot)

    return f

