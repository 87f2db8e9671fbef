"""Attention layers: GQA/MQA and multi-head latent attention.

Covers every attention pattern in the assigned architecture pool:

  * ``causal``   — full causal self-attention (dense LMs, MoE LMs)
  * ``sliding``  — causal within a window (StarCoder2 w=4096,
                   RecurrentGemma local attention w=2048)
  * ``prefix``   — prefix-LM mask (PaliGemma: bidirectional over the image
                   prefix, causal after)
  * ``cross``    — encoder-decoder cross attention (SeamlessM4T)

All projections (q, k, v, o) run through the paper's quantized data path
(:func:`repro.core.qlinear.qdense`), so W8/A8/G8 in-hindsight quantization
applies uniformly.  Training and prefill hand the core to the one
attention-core site, :func:`repro.core.backend.attention`: the int8 flash
core for static 8-bit policies, the fp core otherwise, both on one block
plan that skips fully masked blocks and walks only a window's blocks
(``kernels/int8_attention.py``).  This module keeps the projections and
decode: one new token against a cache (``_decode_attn``).

KV caches are plain pytrees ``{"k": [B, L, KV, hd], "v": ..., "pos":
int32[]}``; sliding-window caches are ring buffers of length ``window``
(constant memory in decode).
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.core import backend, qlinear
from repro.core.policy import QuantPolicy
from repro.core.state import init_range_state, make_range_state
from repro.runtime.sharding import attn_hints

from .layers import apply_norm, apply_rope

NEG_INF = -1e30  # large-but-finite: keeps fully-masked rows NaN-free


# ---------------------------------------------------------------------------
# Parameter / site init.
# ---------------------------------------------------------------------------
def init_attention(key, d_model: int, n_heads: int, n_kv: int, head_dim: int,
                   use_bias: bool, dtype=jnp.float32) -> dict:
    """HEAD-MAJOR weight layout: ``wq [D, KV, G, hd]``, ``wo [KV, G, hd, D]``.

    Projections emit head-split tensors directly, so the head sharding
    (KV or G over the ``model`` axis) is carried by the WEIGHT layout and
    no reshape ever crosses a sharded dimension boundary — GSPMD handles
    the non-divisible head counts (e.g. starcoder2's 36 q heads on a
    16-way axis) by padding the weight shard instead of involuntarily
    rematerializing activations (see EXPERIMENTS.md §Perf)."""
    kq, kk, kv, ko = jax.random.split(key, 4)
    s = d_model ** -0.5
    g = n_heads // n_kv
    p = {
        "wq": (jax.random.normal(kq, (d_model, n_kv, g, head_dim)) * s).astype(dtype),
        "wk": (jax.random.normal(kk, (d_model, n_kv, head_dim)) * s).astype(dtype),
        "wv": (jax.random.normal(kv, (d_model, n_kv, head_dim)) * s).astype(dtype),
        "wo": (jax.random.normal(ko, (n_kv, g, head_dim, d_model))
               * (n_heads * head_dim) ** -0.5).astype(dtype),
    }
    if use_bias:
        p["bq"] = jnp.zeros((n_kv, g, head_dim), dtype)
        p["bk"] = jnp.zeros((n_kv, head_dim), dtype)
        p["bv"] = jnp.zeros((n_kv, head_dim), dtype)
        p["bo"] = jnp.zeros((d_model,), dtype)
    return p


def init_attention_sites() -> dict:
    sites = {name: qlinear.init_site() for name in ("q", "k", "v", "o")}
    # The attention CORE's quant sites (backend.attention): hindsight
    # ranges for the rope'd q/k, v, and the softmax probabilities.  The
    # probability leaf is initialized a-priori to the softmax codomain
    # [0, 1] — its range is consumed mid-kernel, before the tensor
    # exists, so it has no first-batch minmax fallback (and [0, 1] is
    # exact: each row's running-max entry quantizes to 1.0, masked
    # entries to 0.0).
    sites["core"] = {
        "q": {"act": init_range_state()},
        "k": {"act": init_range_state()},
        "v": {"act": init_range_state()},
        "p": {"act": make_range_state(0.0, 1.0)},
    }
    return sites


# ---------------------------------------------------------------------------
# Decode attention (single new token against a cache).
# ---------------------------------------------------------------------------
def _decode_attn(q, k_cache, v_cache, cache_pos, cur_pos, *, mode: str,
                 window, prefix_len, scale: float, kv_scale=None):
    """q: [B, 1, KV, G, hd]; caches: [B, L, KV, hd]; cache_pos: [B, L] abs
    positions (-1 = empty slot); cur_pos: [B] absolute position of q.
    ``kv_scale`` = (k_scale, v_scale) for int8 caches — folded into the
    attention epilogue (no dequantized cache copy is materialized)."""
    b, _, nkv, g, hd = q.shape
    qf = q[:, 0].astype(jnp.float32) * scale                    # [B, KV, G, hd]
    if kv_scale is not None:
        qf = qf * kv_scale[0]
    kf = k_cache.astype(jnp.float32)
    s = jnp.einsum("bkgh,blkh->bkgl", qf, kf)                   # [B, KV, G, L]
    pos = cache_pos[:, None, None, :]
    cur = cur_pos[:, None, None, None]
    valid = (pos >= 0) & (pos <= cur)
    if mode == "sliding":
        valid &= (cur - pos) < window
    if mode == "prefix":
        valid |= (pos >= 0) & (pos < prefix_len)
    s = jnp.where(valid, s, NEG_INF)
    m = jnp.max(s, axis=-1, keepdims=True)
    p = jnp.exp(s - m)
    out = jnp.einsum("bkgl,blkh->bkgh", p, v_cache.astype(jnp.float32))
    out = out / jnp.maximum(jnp.sum(p, axis=-1), 1e-30)[..., None]
    if kv_scale is not None:
        out = out * kv_scale[1]
    return out[:, None].astype(q.dtype)                         # [B, 1, KV, G, hd]


# ---------------------------------------------------------------------------
# KV cache pytree.
# ---------------------------------------------------------------------------
def init_kv_cache(batch: int, length: int, n_kv: int, head_dim: int,
                  dtype=jnp.bfloat16) -> dict:
    """KV cache pytree.  dtype int8 = the IN-HINDSIGHT QUANTIZED cache
    (beyond-paper): k/v stored int8 with per-tensor symmetric scales set
    from the prefill pass — decode steps quantize incoming tokens with the
    hindsight scale (no rescan of the cache) and fold the scales into the
    attention epilogue.  2x less cache HBM + 2x less decode read traffic
    vs bf16."""
    c = {
        "k": jnp.zeros((batch, length, n_kv, head_dim), dtype),
        "v": jnp.zeros((batch, length, n_kv, head_dim), dtype),
        "pos": jnp.full((batch, length), -1, jnp.int32),
    }
    if jnp.dtype(dtype) == jnp.int8:
        c["scale"] = jnp.ones((2,), jnp.float32)    # (k_scale, v_scale)
    return c


def _quant_kv(x, scale):
    return jnp.clip(jnp.round(x.astype(jnp.float32) / scale),
                    -127, 127).astype(jnp.int8)


def cache_fill(cache: dict, k, v, kv_positions=None):
    """Prefill: write a full [B, S, KV, hd] projection into the cache.

    For ring caches (L < S) only the last L tokens are kept, at their ring
    slots ``pos % L`` so subsequent ``cache_insert`` calls line up."""
    import numpy as np
    b, s = k.shape[0], k.shape[1]
    length = cache["k"].shape[1]
    if kv_positions is None:
        start = max(0, s - length)
        pos_np = np.arange(start, s)
        slots = pos_np % length
        ksrc, vsrc = k[:, start:], v[:, start:]
    else:
        pos_np = np.asarray(kv_positions)
        slots = pos_np % length
        ksrc, vsrc = k, v
    out = {}
    if "scale" in cache:
        # int8 cache: set the hindsight scales from this (prefill) pass.
        ks = jnp.maximum(jnp.max(jnp.abs(ksrc.astype(jnp.float32))) / 127.0,
                         1e-8)
        vs = jnp.maximum(jnp.max(jnp.abs(vsrc.astype(jnp.float32))) / 127.0,
                         1e-8)
        out["scale"] = jnp.stack([ks, vs])
        ksrc, vsrc = _quant_kv(ksrc, ks), _quant_kv(vsrc, vs)
    kc = cache["k"].at[:, slots].set(ksrc.astype(cache["k"].dtype))
    vc = cache["v"].at[:, slots].set(vsrc.astype(cache["v"].dtype))
    pc = cache["pos"].at[:, slots].set(
        jnp.broadcast_to(jnp.asarray(pos_np, jnp.int32), (b, len(pos_np))))
    out.update(k=kc, v=vc, pos=pc)
    return out


def cache_insert(cache: dict, k_new, v_new, pos):
    """Insert one token's (k, v) at absolute position ``pos`` [B].  Ring
    buffer semantics: slot = pos % L (full caches have L >= max position so
    this is the identity until the window wraps).  int8 caches quantize
    the incoming token with the stored HINDSIGHT scale — static, one pass,
    the paper's property applied to the cache."""
    length = cache["k"].shape[1]
    slot = (pos % length).astype(jnp.int32)                      # [B]
    b = jnp.arange(k_new.shape[0])
    kn, vn = k_new[:, 0], v_new[:, 0]
    out = {}
    if "scale" in cache:
        kn = _quant_kv(kn, cache["scale"][0])
        vn = _quant_kv(vn, cache["scale"][1])
        out["scale"] = cache["scale"]
    k = cache["k"].at[b, slot].set(kn.astype(cache["k"].dtype))
    v = cache["v"].at[b, slot].set(vn.astype(cache["v"].dtype))
    p = cache["pos"].at[b, slot].set(pos)
    out.update(k=k, v=v, pos=p)
    return out


# ---------------------------------------------------------------------------
# Full attention layer: projections (quantized) + core + output proj.
# ---------------------------------------------------------------------------
def attention_layer(
    params: dict,
    sites: dict,
    x: jax.Array,                    # [B, S, D]
    *,
    n_heads: int,
    n_kv: int,
    head_dim: int,
    mode: str = "causal",            # causal | sliding | prefix | cross
    window: Optional[int] = None,
    prefix_len: Optional[int] = None,
    rope_theta: Optional[float] = 10000.0,   # None = no RoPE (learned/abs elsewhere)
    positions: Optional[jax.Array] = None,   # [B, S] absolute positions
    kv_x: Optional[jax.Array] = None,        # cross-attention source [B, Skv, D]
    kv_len: Optional[jax.Array] = None,      # valid encoder length
    cache: Optional[dict] = None,            # decode-mode KV cache
    policy: QuantPolicy,
    seed: jax.Array,
    step: jax.Array,
) -> tuple[jax.Array, dict, Optional[dict]]:
    """Returns (y, new_sites, new_cache)."""
    b, s, _ = x.shape
    scale = head_dim ** -0.5
    src = x if kv_x is None else kv_x

    # Cross-attention decode: the encoder projections were cached at prefill
    # time (signalled by kv_x=None) — no k/v projection runs here.
    cross_decode = cache is not None and mode == "cross" and kv_x is None
    new_sites = {}
    # ONE shared activation quantization for q/k/v (paper: Q_Y quantizes
    # each tensor once; per-consumer re-quantization would triple the
    # fake-quant traffic).  Its range state lives on the "q" site.
    xq, in_stats, xqi = qlinear.act_quant_site(x, sites["q"]["act"], policy,
                                               step)
    q, sq = qlinear.qdense_pre(xq, params["wq"], sites["q"], policy,
                               einsum_spec="bsd,dkgh->bskgh",
                               bias=params.get("bq"), seed=seed, step=step,
                               qinfo=xqi)
    sq["act"] = in_stats
    new_sites["q"] = sq
    if cross_decode:
        # encoder projections already live in the cache; no k/v proj here.
        k = v = None
        new_sites["k"], new_sites["v"] = sites["k"], sites["v"]
    else:
        if kv_x is None:
            src_q, src_stats, src_qi = xq, None, xqi
        else:
            src_q, src_stats, src_qi = qlinear.act_quant_site(
                src, sites["k"]["act"], policy, step)
        k, sk = qlinear.qdense_pre(src_q, params["wk"], sites["k"], policy,
                                   einsum_spec="bsd,dkh->bskh",
                                   bias=params.get("bk"), seed=seed + 1,
                                   step=step, qinfo=src_qi)
        v, sv = qlinear.qdense_pre(src_q, params["wv"], sites["v"], policy,
                                   einsum_spec="bsd,dkh->bskh",
                                   bias=params.get("bv"), seed=seed + 2,
                                   step=step, qinfo=src_qi)
        if src_stats is not None:
            sk["act"] = src_stats
        new_sites["k"], new_sites["v"] = sk, sv

    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(s), (b, s))
    # No positional rotation across the encoder/decoder boundary (standard
    # for cross-attention); self-attention uses RoPE when configured.
    if rope_theta is not None and mode != "cross":
        q = apply_rope(q, positions, rope_theta)
        k = apply_rope(k, positions, rope_theta)

    q, k, v = attn_hints(q, k, v)

    new_cache = None
    if cross_decode:
        # decode cross-attn: cache holds the (fixed) encoder projections.
        out = _decode_attn(q, cache["k"], cache["v"], cache["pos"],
                           jnp.full((b,), 2 ** 30, jnp.int32),
                           mode="cross_dec", window=None, prefix_len=None,
                           scale=scale, kv_scale=cache.get("scale"))
        new_cache = cache
    elif cache is not None and s == 1 and mode != "cross":
        # decode: insert the new token, then attend against the cache.
        cur = positions[:, 0]
        new_cache = cache_insert(cache, k, v, cur)
        out = _decode_attn(q, new_cache["k"], new_cache["v"], new_cache["pos"],
                           cur, mode=mode, window=window,
                           prefix_len=prefix_len, scale=scale,
                           kv_scale=new_cache.get("scale"))
    else:
        # training / prefill: the attention-core site chooses the core;
        # optionally fill the cache.
        out, new_sites["core"] = backend.attention(
            policy, q, k, v, sites["core"], mode=mode, window=window,
            prefix_len=prefix_len, kv_len=kv_len, scale=scale, step=step)
        if cache is not None:
            new_cache = cache_fill(cache, k, v)
    if "core" not in new_sites:
        # decode: the core did not run; mark every core site "not
        # visited" so its state passes through the estimator update
        # unchanged.
        new_sites["core"] = jax.tree_util.tree_map(
            lambda _: qlinear.stats_zeros(policy), sites["core"])

    y, new_sites["o"] = qlinear.qeinsum("bskgh,kghd->bsd", out, params["wo"],
                                        sites["o"], policy, seed=seed + 3,
                                        step=step)
    if "bo" in params:
        y = y + params["bo"].astype(y.dtype)
    return y, new_sites, new_cache


# ---------------------------------------------------------------------------
# Multi-head latent attention (DeepSeek-V2/V3, Moonlight), training and
# prefill.  k and v come up from a ``kv_lora_rank``-wide latent (RMSNorm'd
# in fp) through one projection; a ``rope``-wide RoPE key shared by every
# head is concatenated after each head's ``k_nope``.  Every projection is a
# quantized site; the core is the same ``backend.attention`` site, with a
# query-key head dim (nope + rope) above the value head dim.
# ---------------------------------------------------------------------------
def init_mla(key, d_model: int, n_heads: int, nope: int, rope: int,
             v_dim: int, rank: int, dtype=jnp.float32) -> dict:
    """``q_proj [D, H, nope+rope]``, ``kv_a [D, rank+rope]``, ``kv_b
    [rank, H, nope+v]``, ``o_proj [H, v, D]`` and the latent's norm."""
    kq, ka, kb, ko = jax.random.split(key, 4)
    s = d_model ** -0.5
    return {
        "q_proj": (jax.random.normal(kq, (d_model, n_heads, nope + rope))
                   * s).astype(dtype),
        "kv_a": (jax.random.normal(ka, (d_model, rank + rope)) * s
                 ).astype(dtype),
        "kv_norm": {"scale": jnp.ones((rank,), jnp.float32)},
        "kv_b": (jax.random.normal(kb, (rank, n_heads, nope + v_dim))
                 * rank ** -0.5).astype(dtype),
        "o_proj": (jax.random.normal(ko, (n_heads, v_dim, d_model))
                   * (n_heads * v_dim) ** -0.5).astype(dtype),
    }


def init_mla_sites() -> dict:
    sites = {name: qlinear.init_site() for name in ("q", "kv_a", "kv_b", "o")}
    sites["core"] = init_attention_sites()["core"]
    return sites


def rope_halves(x: jax.Array) -> jax.Array:
    """Reorder the last dim from interleaved pairs to halves, as the
    published DeepSeek-V3 rotary does before rotating (``x[..., 0::2]``
    then ``x[..., 1::2]``)."""
    return jnp.concatenate([x[..., 0::2], x[..., 1::2]], axis=-1)


def mla_layer(params: dict, sites: dict, x: jax.Array, *, n_heads: int,
              nope: int, rope: int, v_dim: int, rank: int, rope_theta: float,
              norm_eps: Optional[float], positions: Optional[jax.Array] = None,
              policy: QuantPolicy, seed: jax.Array,
              step: jax.Array) -> tuple[jax.Array, dict]:
    """Causal latent attention over ``x [B, S, D]``; returns ``(y,
    new_sites)``."""
    b, s, _ = x.shape
    new_sites = {}
    # One activation site for the two projections of x (its range state
    # lives on the "q" site).
    xq, in_stats, xqi = qlinear.act_quant_site(x, sites["q"]["act"], policy,
                                               step)
    q, sq = qlinear.qdense_pre(xq, params["q_proj"], sites["q"], policy,
                               einsum_spec="bsd,dhe->bshe", seed=seed,
                               step=step, qinfo=xqi)
    sq["act"] = in_stats
    new_sites["q"] = sq
    with jax.named_scope("mla_latent"):
        ckv, new_sites["kv_a"] = qlinear.qdense_pre(
            xq, params["kv_a"], sites["kv_a"], policy,
            einsum_spec="bsd,dr->bsr", seed=seed + 1, step=step, qinfo=xqi)
        latent = apply_norm(ckv[..., :rank], params["kv_norm"], "rmsnorm",
                            norm_eps)
        kv, new_sites["kv_b"] = qlinear.qeinsum(
            "bsr,rhe->bshe", latent, params["kv_b"], sites["kv_b"], policy,
            seed=seed + 2, step=step)
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(s), (b, s))
    q_pe = apply_rope(rope_halves(q[..., nope:]), positions, rope_theta)
    k_pe = apply_rope(rope_halves(ckv[..., None, rank:]), positions,
                      rope_theta)                               # [B,S,1,rope]
    qh = jnp.concatenate([q[..., :nope], q_pe], axis=-1)[:, :, :, None]
    kh = jnp.concatenate(
        [kv[..., :nope], jnp.broadcast_to(k_pe, (b, s, n_heads, rope))], -1)
    v = kv[..., nope:]
    scale = (nope + rope) ** -0.5

    out, new_sites["core"] = backend.attention(
        policy, qh, kh, v, sites["core"], mode="causal", scale=scale,
        step=step)
    y, new_sites["o"] = qlinear.qeinsum("bshe,hed->bsd", out[:, :, :, 0],
                                        params["o_proj"], sites["o"], policy,
                                        seed=seed + 3, step=step)
    return y, new_sites
