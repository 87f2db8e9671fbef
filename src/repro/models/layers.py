"""Shared layers: norms, rotary embeddings, MLP/GLU variants, embeddings.

Every weight-bearing matmul goes through :func:`repro.core.qlinear.qdense`
so the paper's W8/A8/G8 data path and range-state threading apply uniformly
across every architecture in the zoo.  Norms, rotary, softmax and other
elementwise/statistical ops stay in fp32 — mirroring the paper, which keeps
BatchNorm and the weight update in floating point.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.core import qlinear
from repro.core.policy import QuantPolicy

# ---------------------------------------------------------------------------
# Norms (fp32 compute, cast back to input dtype).
# ---------------------------------------------------------------------------
def rmsnorm(x: jax.Array, weight: jax.Array, eps: float = 1e-6) -> jax.Array:
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    y = xf * jax.lax.rsqrt(var + eps) * weight.astype(jnp.float32)
    return y.astype(x.dtype)


def layernorm(x: jax.Array, weight: jax.Array, bias: Optional[jax.Array],
              eps: float = 1e-5) -> jax.Array:
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.mean((xf - mu) ** 2, axis=-1, keepdims=True)
    y = (xf - mu) * jax.lax.rsqrt(var + eps) * weight.astype(jnp.float32)
    if bias is not None:
        y = y + bias.astype(jnp.float32)
    return y.astype(x.dtype)


def apply_norm(x: jax.Array, params: dict, kind: str,
               eps: Optional[float] = None) -> jax.Array:
    """``eps`` None: the norm's own default."""
    kw = {} if eps is None else {"eps": eps}
    if kind == "rmsnorm":
        return rmsnorm(x, params["scale"], **kw)
    return layernorm(x, params["scale"], params.get("bias"), **kw)


def init_norm(d: int, kind: str, use_bias: bool) -> dict:
    p = {"scale": jnp.ones((d,), jnp.float32)}
    if kind == "layernorm" and use_bias:
        p["bias"] = jnp.zeros((d,), jnp.float32)
    return p


# ---------------------------------------------------------------------------
# Rotary position embeddings.
# ---------------------------------------------------------------------------
def rope_frequencies(head_dim: int, theta: float) -> jax.Array:
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))


def apply_rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: [B, S, *head_dims, Dh]; positions: [B, S] (int).

    Works for any number of interior head dims ([B,S,H,Dh], [B,S,KV,G,Dh],
    ...) WITHOUT reshaping — reshapes across sharded head dims would force
    GSPMD resharding (see attention.init_attention)."""
    hd = x.shape[-1]
    freqs = rope_frequencies(hd, theta)                           # [Dh/2]
    angles = positions[..., None].astype(jnp.float32) * freqs     # [B, S, Dh/2]
    expand = angles.shape[:2] + (1,) * (x.ndim - 3) + (hd // 2,)
    cos = jnp.cos(angles).reshape(expand)
    sin = jnp.sin(angles).reshape(expand)
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# Activations.
# ---------------------------------------------------------------------------
def activation(x: jax.Array, kind: str) -> jax.Array:
    if kind == "gelu":
        return jax.nn.gelu(x)
    if kind == "silu":
        return jax.nn.silu(x)
    if kind == "relu":
        return jax.nn.relu(x)
    if kind == "sq_relu":  # squared ReLU (Primer; Nemotron-4)
        r = jax.nn.relu(x)
        return r * r
    raise ValueError(f"unknown activation {kind!r}")


GLU_KINDS = ("swiglu", "geglu", "reglu")
_GLU_ACT = {"swiglu": "silu", "geglu": "gelu", "reglu": "relu"}


# ---------------------------------------------------------------------------
# MLP (dense FFN) — plain or gated, quantized.
# ---------------------------------------------------------------------------
def init_mlp(key, d_model: int, d_ff: int, kind: str, use_bias: bool,
             dtype=jnp.float32) -> dict:
    k1, k2, k3 = jax.random.split(key, 3)
    s_in = d_model ** -0.5
    s_out = d_ff ** -0.5
    p = {
        "w_up": (jax.random.normal(k1, (d_model, d_ff)) * s_in).astype(dtype),
        "w_down": (jax.random.normal(k2, (d_ff, d_model)) * s_out).astype(dtype),
    }
    if kind in GLU_KINDS:
        p["w_gate"] = (jax.random.normal(k3, (d_model, d_ff)) * s_in).astype(dtype)
    if use_bias:
        p["b_up"] = jnp.zeros((d_ff,), dtype)
        p["b_down"] = jnp.zeros((d_model,), dtype)
    return p


def init_mlp_sites(kind: str) -> dict:
    sites = {"up": qlinear.init_site(), "down": qlinear.init_site()}
    if kind in GLU_KINDS:
        sites["gate"] = qlinear.init_site()
    return sites


def apply_mlp(params: dict, sites: dict, x: jax.Array, kind: str,
              policy: QuantPolicy, seed: jax.Array, step: jax.Array
              ) -> tuple[jax.Array, dict]:
    new_sites = {}
    # shared input quantization for up/gate (one Q_Y per tensor, as in the
    # paper); the range state lives on the "up" site.
    xq, in_stats, xqi = qlinear.act_quant_site(x, sites["up"]["act"], policy,
                                               step)
    if kind in GLU_KINDS:
        up, s_up = qlinear.qdense_pre(
            xq, params["w_up"], sites["up"], policy,
            bias=params.get("b_up"), seed=seed, step=step, qinfo=xqi)
        gate, new_sites["gate"] = qlinear.qdense_pre(
            xq, params["w_gate"], sites["gate"], policy, seed=seed + 1,
            step=step, qinfo=xqi)
        h = activation(gate, _GLU_ACT[kind]) * up
    else:
        up, s_up = qlinear.qdense_pre(
            xq, params["w_up"], sites["up"], policy,
            bias=params.get("b_up"), seed=seed, step=step, qinfo=xqi)
        h = activation(up, kind)
    s_up["act"] = in_stats
    new_sites["up"] = s_up
    out, new_sites["down"] = qlinear.qdense(
        h, params["w_down"], sites["down"], policy,
        bias=params.get("b_down"), seed=seed + 2, step=step)
    return out, new_sites


# ---------------------------------------------------------------------------
# Embedding + LM head.
# ---------------------------------------------------------------------------
def init_embedding(key, vocab: int, d_model: int, dtype=jnp.float32) -> jax.Array:
    return (jax.random.normal(key, (vocab, d_model)) * (d_model ** -0.5)).astype(dtype)


def embed(tokens: jax.Array, table: jax.Array) -> jax.Array:
    return table[tokens]


def lm_head(x: jax.Array, table_or_w: jax.Array, site: dict,
            policy: QuantPolicy, seed: jax.Array, step: jax.Array,
            transpose: bool) -> tuple[jax.Array, dict]:
    """Final projection to vocab.  ``transpose=True`` ties to the embedding
    table ([V, D] used as D->V)."""
    w = table_or_w.T if transpose else table_or_w
    return qlinear.qdense(x, w, site, policy, seed=seed, step=step)
