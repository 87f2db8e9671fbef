"""Plain float32 reference of the DeepSeek-V3-type MoE LM
(Moonlight-16B-A3B and its one-chip cut), in the keys of the published
config.json; the benchmark keeps a copy of it
(``benchmarks/chip/configs/ref_moonlight.py``).

Straightforward ``jax.numpy`` at ``highest`` matmul precision, with no
quantization, kernel, sorting or batching trick, following the published
DeepSeek-V3 modelling of Moonlight: pre-RMSNorm blocks; multi-head latent
attention (queries of ``qk_nope_head_dim + qk_rope_head_dim`` per head;
keys and values up-projected from a ``kv_lora_rank``-wide RMSNorm'd latent,
the rotary key shared by every head; the rotary dims reordered from
interleaved pairs to halves before rotating, as the published rotary does;
softmax scale ``(nope + rope) ** -0.5``); ``first_k_dense_replace`` dense
SwiGLU layers, then expert layers.  An expert layer routes every token
over all ``n_routed_experts``: sigmoid scores, the top
``num_experts_per_tok`` of the scores plus the correction bias, weights
the chosen scores normalised to sum 1 times ``routed_scaling_factor``.
Of the experts it computes those this chip holds (``experts_first`` and
the ``experts_held`` after it), each as a dense SwiGLU over every token
weighed by its gate (zero where not chosen), plus the shared experts.
The loss adds the sequence-wise balance loss (weight ``seq_aux_alpha``).
It imports nothing of the program.  Departures, none of which changes the
mathematics: attention, the dense MLPs and the loss are computed in blocks
of positions, the experts and the microbatches one after another, each
recomputed in the backward, so that the reference fits one chip.

The correction bias is state, not a weight: ``loss_and_grad``'s function
starts from the bias in the weights of its first call and, after each
call (one training step), moves it by the aux-loss-free rule
(arXiv:2412.19437 sec. 2.1.2) by ``bias_update_speed`` against the step's
load; its gradient is zero.

The benchmark's weights come from ``init_params``: one jitted call from the
seed, in the layout the program's parameter tree has (expert layers
stacked on a leading axis), at the program's own init scales.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

HI = jax.lax.Precision.HIGHEST
tmap = jax.tree_util.tree_map


def _dims(c: dict) -> dict:
    return dict(d=c["hidden_size"], h=c["num_attention_heads"],
                nope=c["qk_nope_head_dim"], rope=c["qk_rope_head_dim"],
                vd=c["v_head_dim"], r=c["kv_lora_rank"],
                f=c["intermediate_size"], fe=c["moe_intermediate_size"],
                fs=c["moe_intermediate_size"] * c["n_shared_experts"],
                e=c["n_routed_experts"], held=c["experts_held"],
                v=c["vocab_size"], lead=c["first_k_dense_replace"],
                moe=c["num_hidden_layers"] - c["first_k_dense_replace"])


def param_specs(c: dict) -> dict:
    """``{path: (shape, init)}`` of every weight; ``init`` is a normal's
    scale, or "ones".  Expert layers are stacked on a leading axis."""
    n = _dims(c)
    d, h, qk, r, vd = n["d"], n["h"], n["nope"] + n["rope"], n["r"], n["vd"]
    attn = {
        ("attn", "q_proj"): ((d, h, qk), d ** -0.5),
        ("attn", "kv_a"): ((d, r + n["rope"]), d ** -0.5),
        ("attn", "kv_norm", "scale"): ((r,), "ones"),
        ("attn", "kv_b"): ((r, h, n["nope"] + vd), r ** -0.5),
        ("attn", "o_proj"): ((h, vd, d), (h * vd) ** -0.5),
        ("ln1", "scale"): ((d,), "ones"), ("ln2", "scale"): ((d,), "ones"),
    }
    specs = {("embed",): ((n["v"], d), d ** -0.5),
             ("head",): ((d, n["v"]), d ** -0.5),
             ("final_norm", "scale"): ((d,), "ones")}
    dense = dict(attn)
    dense.update({("mlp", "w_up"): ((d, n["f"]), d ** -0.5),
                  ("mlp", "w_gate"): ((d, n["f"]), d ** -0.5),
                  ("mlp", "w_down"): ((n["f"], d), n["f"] ** -0.5)})
    for j in range(n["lead"]):
        specs.update({("decoder", "lead", f"l{j}") + k: v
                      for k, v in dense.items()})
    g, fe, fs = n["held"], n["fe"], n["fs"]
    moe = dict(attn)
    moe.update({
        ("moe", "router"): ((d, n["e"]), d ** -0.5),
        ("moe", "router_bias"): ((n["e"],), c["router_bias_scale"]),
        ("moe", "w_up"): ((g, d, fe), d ** -0.5),
        ("moe", "w_gate"): ((g, d, fe), d ** -0.5),
        ("moe", "w_down"): ((g, fe, d), fe ** -0.5),
        ("moe", "shared", "w_up"): ((d, fs), d ** -0.5),
        ("moe", "shared", "w_gate"): ((d, fs), d ** -0.5),
        ("moe", "shared", "w_down"): ((fs, d), fs ** -0.5),
    })
    specs.update({("decoder", "blocks", "b0") + k: ((n["moe"],) + shape, i)
                  for k, (shape, i) in moe.items()})
    return specs


def init_leaf(key, c: dict, path: tuple):
    """The weight at ``path``, drawn from ``key`` as ``init_params`` does."""
    specs = param_specs(c)
    shape, init = specs[path]
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


def _rmsnorm(x, w, eps):
    return x / jnp.sqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps) * w


def _rope(x, pos, theta):
    """x [B, S, ..., n], rotary dims interleaved in pairs: reorder them to
    halves (``x[..., 0::2]`` then ``x[..., 1::2]``), then rotate the two
    halves."""
    x = jnp.concatenate([x[..., 0::2], x[..., 1::2]], axis=-1)
    n = x.shape[-1]
    freqs = 1.0 / theta ** (jnp.arange(0, n, 2, dtype=jnp.float32) / n)
    ang = pos[:, :, None].astype(jnp.float32) * freqs
    ang = ang.reshape(ang.shape[:2] + (1,) * (x.ndim - 3) + (n // 2,))
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * jnp.cos(ang) - x2 * jnp.sin(ang),
                            x2 * jnp.cos(ang) + x1 * jnp.sin(ang)], -1)


def _blocks(f, size: int, *xs):
    """``f`` over blocks of ``size`` positions (axis 1) of each of ``xs``,
    one block after another (``lax.map``, each block recomputed in the
    backward); ``f(*blocks, start)``.  Outputs concatenate on axis 1."""
    s = xs[0].shape[1]
    size = min(size, s)
    n = s // size
    split = [jnp.moveaxis(x.reshape(x.shape[0], n, size, *x.shape[2:]), 1, 0)
             for x in xs]
    out = jax.lax.map(lambda a: jax.checkpoint(f)(*a[:-1], a[-1]),
                      (*split, jnp.arange(n) * size))
    return jax.tree_util.tree_map(
        lambda o: jnp.moveaxis(o, 0, 1).reshape(o.shape[1], n * size,
                                                *o.shape[3:])
        if o.ndim > 1 else o, out)


def _attention(q, k, v, scale, q_block):
    """q/k [B,S,H,qk], v [B,S,H,v]; causal."""
    kpos = jnp.arange(q.shape[1])

    def block(qb, q0):
        qpos = q0 + jnp.arange(qb.shape[1])
        sc = jnp.einsum("bqhe,bshe->bhqs", qb, k, precision=HI) * scale
        sc = jnp.where(kpos[None, :] <= qpos[:, None], sc, -jnp.inf)
        p = jax.nn.softmax(sc, axis=-1)
        return jnp.einsum("bhqs,bshe->bqhe", p, v, precision=HI)

    return _blocks(block, q_block, q)


def _mla(x, a, pos, c, q_block, bits):
    n = _dims(c)
    nope, r = n["nope"], n["r"]
    q = _dense("bsd,dhe->bshe", x, a["q_proj"], bits)
    ckv = _dense("bsd,dr->bsr", x, a["kv_a"], bits)
    latent = _rmsnorm(ckv[..., :r], a["kv_norm"]["scale"], c["rms_norm_eps"])
    kv = _dense("bsr,rhe->bshe", latent, a["kv_b"], bits)
    theta = c["rope_theta"]
    q_pe = _rope(q[..., nope:], pos, theta)
    k_pe = _rope(ckv[..., None, r:], pos, theta)
    qh = jnp.concatenate([q[..., :nope], q_pe], -1)
    kh = jnp.concatenate([kv[..., :nope],
                          jnp.broadcast_to(k_pe, kv.shape[:3] + (n["rope"],))],
                         -1)
    o = _attention(qh, kh, kv[..., nope:], (nope + n["rope"]) ** -0.5,
                   q_block)
    return _dense("bshe,hed->bsd", o, a["o_proj"], bits)


def _swiglu(x, up, gate, down, bits, rows=2048):
    """A SwiGLU MLP, in blocks of ``rows`` positions."""
    up, gate = fake_quant(up, bits, True), fake_quant(gate, bits, True)
    down, xq = fake_quant(down, bits, True), fake_quant(x, bits, False)

    def block(xb, _):
        u = jnp.einsum("bsd,df->bsf", xb, up, precision=HI)
        g = jnp.einsum("bsd,df->bsf", xb, gate, precision=HI)
        return jnp.einsum("bsf,fd->bsd", fake_quant(jax.nn.silu(g) * u, bits,
                                                    False), down, precision=HI)

    return _blocks(block, rows, xq)


def _experts(x, m, bias, c, bits):
    """The expert layer over x [B, S, D]; returns (y, balance loss, load
    [E] of the chosen experts)."""
    n = _dims(c)
    b, s, _ = x.shape
    e, k = n["e"], c["num_experts_per_tok"]
    scores = jax.nn.sigmoid(jnp.einsum("bsd,de->bse", x, m["router"],
                                       precision=HI))
    _, idx = jax.lax.top_k(jax.lax.stop_gradient(scores) + bias, k)
    chosen = jnp.sum(jax.nn.one_hot(idx, e, dtype=jnp.float32), axis=2)
    w = scores * chosen
    if c["norm_topk_prob"]:
        w = w / jnp.sum(w, -1, keepdims=True)
    w = w * c["routed_scaling_factor"]
    up, gate = fake_quant(m["w_up"], bits, True), fake_quant(m["w_gate"],
                                                             bits, True)
    down = fake_quant(m["w_down"], bits, True)
    xq = fake_quant(x, bits, False)
    y = _swiglu(x, m["shared"]["w_up"], m["shared"]["w_gate"],
                m["shared"]["w_down"], bits)

    @jax.checkpoint
    def expert(y, args):                   # one held expert after another
        up, gate, down, wi = args
        h = jax.nn.silu(jnp.einsum("bsd,df->bsf", xq, gate, precision=HI)) \
            * jnp.einsum("bsd,df->bsf", xq, up, precision=HI)
        return y + wi[..., None] * jnp.einsum(
            "bsf,fd->bsd", fake_quant(h, bits, False), down, precision=HI), None

    first = c["experts_first"]
    held = jnp.moveaxis(w[..., first:first + n["held"]], -1, 0)
    y, _ = jax.lax.scan(expert, y, (up, gate, down, held))
    frac = jnp.sum(chosen, 1) * e / (k * s)                       # [B, E]
    prob = jnp.mean(scores / jnp.sum(scores, -1, keepdims=True), 1)
    bal = jnp.mean(jnp.sum(frac * prob, -1))
    return y, bal, jnp.sum(chosen, (0, 1))


def hidden(params, tokens, c, bias, q_block=256, bits=None):
    """Final normed hidden states [B, S, D], the summed balance loss and
    the load [L, E] of each expert layer.  ``bias`` [L, E] is the
    routers' correction bias."""
    b, s = tokens.shape
    pos = jnp.broadcast_to(jnp.arange(s), (b, s))
    eps = c["rms_norm_eps"]
    x = params["embed"][tokens]
    dec = params["decoder"]

    def dense_block(x, p):
        x = x + _mla(_rmsnorm(x, p["ln1"]["scale"], eps), p["attn"], pos, c,
                     q_block, bits)
        m = p["mlp"]
        return x + _swiglu(_rmsnorm(x, p["ln2"]["scale"], eps), m["w_up"],
                           m["w_gate"], m["w_down"], bits)

    def moe_block(x, p, bias):
        x = x + _mla(_rmsnorm(x, p["ln1"]["scale"], eps), p["attn"], pos, c,
                     q_block, bits)
        y, bal, load = _experts(_rmsnorm(x, p["ln2"]["scale"], eps),
                                p["moe"], bias, c, bits)
        return x + y, bal, load

    for j in range(c["first_k_dense_replace"]):
        x = jax.checkpoint(dense_block)(x, dec["lead"][f"l{j}"])
    bal, loads = 0.0, []
    layers = dec["blocks"]["b0"]
    for i in range(_dims(c)["moe"]):
        p = tmap(lambda w: w[i], layers)
        x, bl, load = jax.checkpoint(moe_block)(x, p, bias[i])
        bal, loads = bal + bl, loads + [load]
    return (_rmsnorm(x, params["final_norm"]["scale"], eps), bal,
            jnp.stack(loads))


def loss(params, tokens, labels, c, bias, q_block=256, bits=None):
    """Mean next-token cross-entropy over every position plus the balance
    loss; returns ``(loss, load [L, E])``."""
    x, bal, load = hidden(params, tokens, c, bias, q_block, bits)
    x = fake_quant(x, bits, False)
    head = fake_quant(params["head"], bits, True)

    def nll(xb, lb, _):
        logits = jnp.einsum("bsd,dv->bsv", xb, head, precision=HI)
        gold = jnp.take_along_axis(logits, lb[..., None], -1)[..., 0]
        return jnp.sum(jax.nn.logsumexp(logits, -1) - gold)

    tot = jnp.sum(_blocks(nll, q_block, x, labels))
    return tot / labels.size + c["seq_aux_alpha"] * bal, load


def loss_and_grad(c: dict, microbatches: int, bits=None):
    """``f(params, batch) -> (loss, grads)`` of one training step, taken as
    the mean over ``microbatches`` equal row blocks.  ``bits`` computes it
    with every weight contraction's operands on a ``bits``-bit grid.  The
    routers' correction bias is ``f``'s own state (module docstring)."""
    def step_loss(p, tokens, labels, bias):
        # the microbatches in one program, each recomputed in the backward,
        # so that one gradient tree is held
        def micro(acc, tl):
            val, ld = loss(p, *tl, c, bias, bits=bits)
            return (acc[0] + val, acc[1] + ld), None

        split = lambda x: x.reshape((microbatches, -1) + x.shape[1:])
        zero = (jnp.float32(0.0), jnp.zeros(bias.shape, jnp.float32))
        (tot, load), _ = jax.lax.scan(jax.checkpoint(micro), zero,
                                      (split(tokens), split(labels)))
        return tot / microbatches, load

    gamma = c["bias_update_speed"]

    @jax.jit
    def step(p, tokens, labels, bias):
        (val, load), g = jax.value_and_grad(step_loss, has_aux=True)(
            p, tokens, labels, bias)
        g["decoder"]["blocks"]["b0"]["moe"]["router_bias"] = jnp.zeros_like(
            bias)
        new_bias = bias + gamma * jnp.sign(
            jnp.mean(load, -1, keepdims=True) - load)
        return val, g, new_bias

    state = {}

    def f(params, batch):
        if "bias" not in state:
            state["bias"] = params["decoder"]["blocks"]["b0"]["moe"][
                "router_bias"]
        tot, grads, state["bias"] = step(params, batch["tokens"],
                                         batch["labels"], state["bias"])
        return tot, grads

    return f
