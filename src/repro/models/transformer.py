"""Unified block-pattern transformer stack for the assigned architecture pool.

One engine covers all ten architectures via a *pattern* of block kinds:

  dense LMs        pattern ("attn",)                 starcoder2, nemotron, command-r
  MoE LMs          pattern ("moe",)                  qwen2-moe, moonlight
  RWKV-6           pattern ("rwkv",)                 rwkv6-7b
  hybrid           pattern ("rec","rec","local")     recurrentgemma (1:2 RG-LRU:local)
  enc-dec          enc pattern ("enc",), dec ("xattn",)   seamless-m4t
  VLM prefix-LM    pattern ("attn",) + image prefix  paligemma

The stack is compiled as a ``lax.scan`` over pattern *repeats* (MaxText-
style): the HLO contains one trace of the pattern unit regardless of depth,
which keeps 96-layer compiles tractable and makes the per-layer quant-range
states stack into ``[repeats, 3]`` leaves that ride the scan's xs/ys.  A
ragged tail (e.g. recurrentgemma's 38 = 12x3 + 2) is applied unrolled, and
so are ``cfg.first_k_dense`` leading dense layers before the scan (Moonlight:
one dense layer, then the expert layers).  A config with ``kv_lora_rank``
runs latent attention (``attention.mla_layer``) in its attention blocks.

Quantization sites mirror the parameter tree one-to-one; activation-site
updates come back through the scan ys, gradient-site statistics flow
through the cotangent channel (see ``repro.core.qlinear``).

The LM head evaluates cross-entropy in sequence chunks (``loss_chunk``) so
the full [B, S, V] logits tensor is never materialized — required for the
256k-vocab archs at 4k sequence.  The head's gradient quantizer ``Q_G``
sits on the head *input* (one tensor), keeping the paper's semantics while
chunking.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro.core import qlinear, quant
from repro.core.policy import QuantPolicy
from repro.runtime.sharding import hint

from . import attention as attn
from . import layers, moe as moe_mod, rglru, rwkv6

PyTree = Any

# Seed stride reserved per layer so no two quant sites share rounding noise.
_SEED_STRIDE = 64


# ===========================================================================
# Per-block init / apply.
# ===========================================================================
def zero_metrics(cfg) -> dict:
    """The per-step metrics a stack sums over its layers (all zero)."""
    names = ("aux_loss", "z_loss") + (moe_mod.COUNTERS if cfg.moe else ())
    return {k: jnp.float32(0.0) for k in names}


def _init_self_attn(key, cfg, dt) -> dict:
    if cfg.kv_lora_rank:
        return attn.init_mla(key, cfg.d_model, cfg.n_heads,
                             cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
                             cfg.v_head_dim, cfg.kv_lora_rank, dt)
    return attn.init_attention(key, cfg.d_model, cfg.n_heads, cfg.n_kv,
                               cfg.head_dim, cfg.use_bias, dt)


def _init_block(key, kind: str, cfg) -> dict:
    dt = jnp.dtype(cfg.param_dtype)
    k1, k2, k3, k4 = jax.random.split(key, 4)
    norm = lambda: layers.init_norm(cfg.d_model, cfg.norm_kind, cfg.use_bias)
    if kind in ("attn", "local", "enc"):
        return {
            "ln1": norm(),
            "attn": _init_self_attn(k1, cfg, dt),
            "ln2": norm(),
            "mlp": layers.init_mlp(k2, cfg.d_model, cfg.d_ff, cfg.mlp_kind,
                                   cfg.use_bias, dt),
        }
    if kind == "moe":
        return {
            "ln1": norm(),
            "attn": _init_self_attn(k1, cfg, dt),
            "ln2": norm(),
            "moe": moe_mod.init_moe(k2, cfg.d_model, cfg.moe, dt),
        }
    if kind == "xattn":
        return {
            "ln1": norm(),
            "attn": attn.init_attention(k1, cfg.d_model, cfg.n_heads, cfg.n_kv,
                                        cfg.head_dim, cfg.use_bias, dt),
            "lnx": norm(),
            "xattn": attn.init_attention(k3, cfg.d_model, cfg.n_heads, cfg.n_kv,
                                         cfg.head_dim, cfg.use_bias, dt),
            "ln2": norm(),
            "mlp": layers.init_mlp(k2, cfg.d_model, cfg.d_ff, cfg.mlp_kind,
                                   cfg.use_bias, dt),
        }
    if kind == "rwkv":
        return {
            "ln1": norm(),
            "time": rwkv6.init_rwkv_time_mix(k1, cfg.d_model, cfg.n_heads,
                                             dtype=dt),
            "ln2": norm(),
            "chan": rwkv6.init_rwkv_channel_mix(k2, cfg.d_model, cfg.d_ff, dt),
        }
    if kind == "rec":
        return {
            "ln1": norm(),
            "rglru": rglru.init_rglru(k1, cfg.d_model, cfg.lru_width, dt),
            "ln2": norm(),
            "mlp": layers.init_mlp(k2, cfg.d_model, cfg.d_ff, cfg.mlp_kind,
                                   cfg.use_bias, dt),
        }
    raise ValueError(kind)


def _init_block_sites(kind: str, cfg) -> dict:
    self_attn = attn.init_mla_sites() if cfg.kv_lora_rank \
        else attn.init_attention_sites()
    if kind in ("attn", "local", "enc"):
        return {"attn": self_attn,
                "mlp": layers.init_mlp_sites(cfg.mlp_kind)}
    if kind == "moe":
        return {"attn": self_attn,
                "moe": moe_mod.init_moe_sites(cfg.moe)}
    if kind == "xattn":
        return {"attn": attn.init_attention_sites(),
                "xattn": attn.init_attention_sites(),
                "mlp": layers.init_mlp_sites(cfg.mlp_kind)}
    if kind == "rwkv":
        return {"time": rwkv6.init_rwkv_time_sites(),
                "chan": rwkv6.init_rwkv_channel_sites()}
    if kind == "rec":
        return {"rglru": rglru.init_rglru_sites(),
                "mlp": layers.init_mlp_sites(cfg.mlp_kind)}
    raise ValueError(kind)


def _init_block_cache(kind: str, cfg, batch: int, cache_len: int) -> dict:
    """Decode-state pytree for one block (zeros; prefill fills it)."""
    cdt = jnp.dtype(cfg.cache_dtype)
    if cfg.kv_lora_rank:
        raise NotImplementedError(
            f"{cfg.name}: latent attention has no decode cache yet (it needs "
            f"a latent KV cache and absorbed decode projections)")
    if kind in ("attn", "moe", "local", "enc"):
        length = cache_len
        if kind == "local":
            length = min(cache_len, cfg.local_window)
        elif cfg.sliding_window is not None:
            length = min(cache_len, cfg.sliding_window)
        return {"kv": attn.init_kv_cache(batch, length, cfg.n_kv, cfg.head_dim, cdt)}
    if kind == "xattn":
        return {
            "kv": attn.init_kv_cache(batch, cache_len, cfg.n_kv, cfg.head_dim, cdt),
            "xkv": attn.init_kv_cache(batch, cfg.enc_len(cache_len), cfg.n_kv,
                                      cfg.head_dim, cdt),
        }
    if kind == "rwkv":
        hd = cfg.d_model // cfg.n_heads
        return {
            "state": jnp.zeros((batch, cfg.n_heads, hd, hd), jnp.float32),
            "x_time": jnp.zeros((batch, cfg.d_model), cdt),
            "x_chan": jnp.zeros((batch, cfg.d_model), cdt),
        }
    if kind == "rec":
        return {
            "h": jnp.zeros((batch, cfg.lru_width), jnp.float32),
            "conv": jnp.zeros((batch, 3, cfg.lru_width), cdt),
        }
    raise ValueError(kind)


def _apply_block(kind: str, params, sites, x, *, cfg, policy, seed, step,
                 positions, cache=None, enc_out=None, enc_len=None,
                 prefix_len=None):
    """Returns (x, new_sites, new_cache, metrics)."""
    new_sites: dict = {}
    new_cache: dict = {} if cache is not None else None
    metrics = zero_metrics(cfg)
    norm = functools.partial(layers.apply_norm, kind=cfg.norm_kind,
                             eps=cfg.norm_eps)

    if kind in ("attn", "moe", "local", "enc", "xattn"):
        # "enc" = bidirectional self-attention (RoPE still applies).
        mode = {"enc": "bidir", "local": "sliding"}.get(kind, "causal")
        window = cfg.local_window if kind == "local" else cfg.sliding_window
        if kind != "local" and window is not None:
            mode = "sliding"
        if prefix_len is not None and kind in ("attn", "moe"):
            mode = "prefix"
        h = norm(x, params["ln1"])
        if cfg.kv_lora_rank:
            if cache is not None or mode != "causal":
                raise NotImplementedError(
                    f"{cfg.name}: latent attention runs causal training "
                    f"and full-sequence forwards only (no cache)")
            a, new_sites["attn"] = attn.mla_layer(
                params["attn"], sites["attn"], h, n_heads=cfg.n_heads,
                nope=cfg.qk_nope_head_dim, rope=cfg.qk_rope_head_dim,
                v_dim=cfg.v_head_dim, rank=cfg.kv_lora_rank,
                rope_theta=cfg.rope_theta, norm_eps=cfg.norm_eps,
                positions=positions, policy=policy, seed=seed, step=step)
            kv = None
        else:
            a, new_sites["attn"], kv = attn.attention_layer(
                params["attn"], sites["attn"], h,
                n_heads=cfg.n_heads, n_kv=cfg.n_kv, head_dim=cfg.head_dim,
                mode=mode, window=window, prefix_len=prefix_len,
                rope_theta=cfg.rope_theta, positions=positions,
                cache=None if cache is None else cache["kv"],
                policy=policy, seed=seed, step=step)
        x = x + a
        if cache is not None:
            new_cache["kv"] = kv

        if kind == "xattn":
            h = norm(x, params["lnx"])
            a, new_sites["xattn"], xkv = attn.attention_layer(
                params["xattn"], sites["xattn"], h,
                n_heads=cfg.n_heads, n_kv=cfg.n_kv, head_dim=cfg.head_dim,
                mode="cross", rope_theta=None, positions=positions,
                kv_x=enc_out, kv_len=enc_len,
                cache=None if cache is None else cache["xkv"],
                policy=policy, seed=seed + 8, step=step)
            x = x + a
            if cache is not None:
                new_cache["xkv"] = xkv

        h = norm(x, params["ln2"])
        if kind == "moe":
            m, new_sites["moe"], metrics = moe_mod.apply_moe(
                params["moe"], sites["moe"], h, cfg.moe, policy=policy,
                seed=seed + 16, step=step)
        else:
            m, new_sites["mlp"] = layers.apply_mlp(
                params["mlp"], sites["mlp"], h, cfg.mlp_kind, policy,
                seed + 16, step)
        x = x + m
        return x, new_sites, new_cache, metrics

    if kind == "rwkv":
        h = norm(x, params["ln1"])
        st = None if cache is None else cache["state"]
        xp = None if cache is None else cache["x_time"].astype(h.dtype)
        a, new_sites["time"], (st, x_last) = rwkv6.rwkv_time_mix(
            params["time"], sites["time"], h, n_heads=cfg.n_heads,
            policy=policy, seed=seed, step=step, chunk=cfg.rwkv_chunk,
            state=st, x_prev=xp)
        x = x + a
        h = norm(x, params["ln2"])
        xp2 = None if cache is None else cache["x_chan"].astype(h.dtype)
        c, new_sites["chan"], c_last = rwkv6.rwkv_channel_mix(
            params["chan"], sites["chan"], h, policy=policy, seed=seed + 16,
            step=step, x_prev=xp2)
        x = x + c
        if cache is not None:
            new_cache = {"state": st,
                         "x_time": x_last.astype(cache["x_time"].dtype),
                         "x_chan": c_last.astype(cache["x_chan"].dtype)}
        return x, new_sites, new_cache, metrics

    if kind == "rec":
        h = norm(x, params["ln1"])
        st = None if cache is None else (cache["h"], cache["conv"].astype(h.dtype))
        a, new_sites["rglru"], (hstate, tail) = rglru.apply_rglru(
            params["rglru"], sites["rglru"], h, policy=policy, seed=seed,
            step=step, state=st)
        x = x + a
        h = norm(x, params["ln2"])
        m, new_sites["mlp"] = layers.apply_mlp(params["mlp"], sites["mlp"], h,
                                               cfg.mlp_kind, policy, seed + 16,
                                               step)
        x = x + m
        if cache is not None:
            new_cache = {"h": hstate, "conv": tail.astype(cache["conv"].dtype)}
        return x, new_sites, new_cache, metrics

    raise ValueError(kind)


# ===========================================================================
# Stack: scan over pattern repeats + unrolled tail.
# ===========================================================================
def _pattern_split(n_layers: int, pattern: tuple) -> tuple[int, tuple]:
    u = len(pattern)
    repeats = n_layers // u
    tail = pattern[: n_layers - repeats * u]
    return repeats, tail


def _lead(cfg, pattern) -> int:
    """Leading dense layers of this stack (the decoder's only)."""
    return cfg.first_k_dense if pattern == cfg.pattern else 0


def init_stack(key, cfg, pattern, n_layers: int) -> dict:
    lead = _lead(cfg, pattern)
    repeats, tail = _pattern_split(n_layers - lead, pattern)
    keys = jax.random.split(key, max(repeats, 1) * len(pattern) + len(tail) + 1)

    def unit(r):
        return {f"b{j}": _init_block(keys[r * len(pattern) + j], kind, cfg)
                for j, kind in enumerate(pattern)}

    if repeats == 0:
        stacked = {}
    elif repeats == 1:
        stacked = jax.tree_util.tree_map(lambda x: x[None], unit(0))
    else:
        stacked = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs),
                                         *[unit(r) for r in range(repeats)])
    tail_p = {f"t{j}": _init_block(keys[repeats * len(pattern) + j], kind, cfg)
              for j, kind in enumerate(tail)}
    out = {"blocks": stacked, "tail": tail_p}
    if lead:
        lead_keys = jax.random.split(keys[-1], lead)
        out["lead"] = {f"l{j}": _init_block(lead_keys[j], "attn", cfg)
                       for j in range(lead)}
    return out


def init_stack_sites(cfg, pattern, n_layers: int) -> dict:
    lead = _lead(cfg, pattern)
    repeats, tail = _pattern_split(n_layers - lead, pattern)
    unit = {f"b{j}": _init_block_sites(kind, cfg)
            for j, kind in enumerate(pattern)}
    stacked = {} if repeats == 0 else jax.tree_util.tree_map(
        lambda x: jnp.broadcast_to(x, (repeats,) + x.shape).copy(), unit)
    tail_s = {f"t{j}": _init_block_sites(kind, cfg)
              for j, kind in enumerate(tail)}
    out = {"blocks": stacked, "tail": tail_s}
    if lead:
        out["lead"] = {f"l{j}": _init_block_sites("attn", cfg)
                       for j in range(lead)}
    return out


def init_stack_cache(cfg, pattern, n_layers: int, batch: int,
                     cache_len: int) -> dict:
    if _lead(cfg, pattern):
        raise NotImplementedError(
            f"{cfg.name}: leading dense layers have no decode cache yet")
    repeats, tail = _pattern_split(n_layers, pattern)
    unit = {f"b{j}": _init_block_cache(kind, cfg, batch, cache_len)
            for j, kind in enumerate(pattern)}
    stacked = {} if repeats == 0 else jax.tree_util.tree_map(
        lambda x: jnp.broadcast_to(x, (repeats,) + x.shape).copy(), unit)
    tail_c = {f"t{j}": _init_block_cache(kind, cfg, batch, cache_len)
              for j, kind in enumerate(tail)}
    return {"blocks": stacked, "tail": tail_c}


def apply_stack(params, sites, x, *, cfg, pattern, policy, seed, step,
                positions, caches=None, enc_out=None, enc_len=None,
                prefix_len=None):
    """Returns (x, new_sites, new_caches, metrics)."""
    lead = _lead(cfg, pattern)
    repeats, tail = _pattern_split(_stack_depth(cfg, pattern) - lead, pattern)
    lead_metrics = zero_metrics(cfg)

    new_lead_sites = {}
    for j in range(lead):
        key = f"l{j}"
        block = functools.partial(
            _apply_block, "attn", cfg=cfg, policy=policy, step=step,
            positions=positions, enc_out=enc_out, enc_len=enc_len,
            prefix_len=prefix_len)
        if cfg.remat:
            block = jax.checkpoint(block)
        x, new_lead_sites[key], _, m = block(
            params["lead"][key], sites["lead"][key], x,
            seed=seed + j * _SEED_STRIDE)
        lead_metrics = {k: lead_metrics[k] + m[k] for k in lead_metrics}

    def unit_fn(x, unit_params, unit_sites, unit_caches, ridx):
        x = hint(x, "batch", "seq", "embed")
        new_sites, new_caches = {}, {}
        met = zero_metrics(cfg)
        for j, kind in enumerate(pattern):
            key = f"b{j}"
            layer = ridx * len(pattern) + j
            if lead:
                layer = layer + lead
            layer_seed = seed + layer * _SEED_STRIDE
            x, ns, nc, m = _apply_block(
                kind, unit_params[key], unit_sites[key], x, cfg=cfg,
                policy=policy, seed=layer_seed, step=step,
                positions=positions,
                cache=None if unit_caches is None else unit_caches[key],
                enc_out=enc_out, enc_len=enc_len, prefix_len=prefix_len)
            new_sites[key] = ns
            if nc is not None:
                new_caches[key] = nc
            met = {k: met[k] + m[k] for k in met}
        return x, new_sites, new_caches, met

    if cfg.remat:
        unit_fn = jax.checkpoint(unit_fn, static_argnums=())

    def body(carry, xs):
        x = carry
        if caches is None:
            unit_params, unit_sites, ridx = xs
            unit_caches = None
        else:
            unit_params, unit_sites, unit_caches, ridx = xs
        x, ns, nc, met = unit_fn(x, unit_params, unit_sites, unit_caches, ridx)
        return x, (ns, nc, met)

    metrics = zero_metrics(cfg)
    new_block_sites, new_block_caches = {}, {}
    if repeats > 0:
        xs = (params["blocks"], sites["blocks"], jnp.arange(repeats)) \
            if caches is None else (params["blocks"], sites["blocks"],
                                    caches["blocks"], jnp.arange(repeats))
        x, (new_block_sites, new_block_caches, mets) = jax.lax.scan(
            body, x, xs)
        metrics = jax.tree_util.tree_map(jnp.sum, mets)
    if lead:
        metrics = {k: lead_metrics[k] + metrics[k] for k in metrics}

    new_tail_sites, new_tail_caches = {}, {}
    for j, kind in enumerate(tail):
        key = f"t{j}"
        layer_seed = seed + (lead + repeats * len(pattern) + j) * _SEED_STRIDE
        x, ns, nc, m = _apply_block(
            kind, params["tail"][key], sites["tail"][key], x, cfg=cfg,
            policy=policy, seed=layer_seed, step=step, positions=positions,
            cache=None if caches is None else caches["tail"][key],
            enc_out=enc_out, enc_len=enc_len, prefix_len=prefix_len)
        new_tail_sites[key] = ns
        if nc is not None:
            new_tail_caches[key] = nc
        metrics = {k: metrics[k] + m[k] for k in metrics}

    new_sites = {"blocks": new_block_sites, "tail": new_tail_sites}
    new_caches = None if caches is None else {"blocks": new_block_caches,
                                              "tail": new_tail_caches}
    if lead:
        new_sites["lead"] = new_lead_sites
    return x, new_sites, new_caches, metrics


def _stack_depth(cfg, pattern) -> int:
    if cfg.family == "encdec" and pattern == cfg.enc_pattern:
        return cfg.enc_layers
    return cfg.n_layers
