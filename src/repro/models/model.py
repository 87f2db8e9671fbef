"""Model-level entry points: init, training forward/loss, prefill, decode.

Batch conventions (all arrays shardable on the batch axis):

  decoder-only LM   {"tokens": i32[B,S], "labels": i32[B,S], "mask": f32[B,S]}
  enc-dec           + {"frames": f[B,Senc,Df]}  (modality frontend STUB:
                      precomputed frame embeddings, projected by a quantized
                      linear — the assigned-arch spec mandates the stub)
  VLM prefix-LM     + {"patches": f[B,P,Df]}    (SigLIP patch embeddings stub)
  prefill           {"tokens": i32[B,S], ...}        -> (last_logits, cache)
  decode            {"token": i32[B,1], "pos": i32[B]} + cache -> next logits

The LM head evaluates the loss in sequence chunks so [B, S, V] logits are
never materialized.  Both head quantizers act on the head *input*: ``Q_Y``
fake-quantizes it on the way in, ``Q_G`` (the paper's activation-gradient
quantizer) sits on the same tensor so the cotangent that re-enters the
trunk — the head layer's G_X — is quantized exactly once, regardless of
chunking.
"""
from __future__ import annotations

from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro.core import backend as qbackend
from repro.core import qlinear, quant
from repro.core.policy import QuantPolicy

from . import layers, transformer

PyTree = Any


# ===========================================================================
# Init.
# ===========================================================================
def init_params(key, cfg) -> PyTree:
    dt = jnp.dtype(cfg.param_dtype)
    ks = jax.random.split(key, 6)
    p: dict = {"embed": layers.init_embedding(ks[0], cfg.vocab, cfg.d_model, dt)}
    if cfg.family == "encdec":
        p["enc_in"] = (jax.random.normal(ks[1], (cfg.frontend_dim, cfg.d_model))
                       * cfg.frontend_dim ** -0.5).astype(dt)
        p["encoder"] = transformer.init_stack(ks[2], cfg, cfg.enc_pattern,
                                              cfg.enc_layers)
        p["enc_norm"] = layers.init_norm(cfg.d_model, cfg.norm_kind, cfg.use_bias)
    if cfg.family == "vlm":
        p["patch_proj"] = (jax.random.normal(ks[1], (cfg.frontend_dim, cfg.d_model))
                           * cfg.frontend_dim ** -0.5).astype(dt)
    p["decoder"] = transformer.init_stack(ks[3], cfg, cfg.pattern, cfg.n_layers)
    p["final_norm"] = layers.init_norm(cfg.d_model, cfg.norm_kind, cfg.use_bias)
    if not cfg.tie_embeddings:
        p["head"] = (jax.random.normal(ks[4], (cfg.d_model, cfg.vocab))
                     * cfg.d_model ** -0.5).astype(dt)
    return p


def init_quant_state(cfg, policy: Optional[QuantPolicy] = None) -> PyTree:
    s: dict = {"decoder": transformer.init_stack_sites(cfg, cfg.pattern,
                                                       cfg.n_layers),
               "head": qlinear.init_site()}
    if cfg.family == "encdec":
        s["enc_in"] = qlinear.init_site()
        s["encoder"] = transformer.init_stack_sites(cfg, cfg.enc_pattern,
                                                    cfg.enc_layers)
    if cfg.family == "vlm":
        s["patch_proj"] = qlinear.init_site()
    if policy is not None and policy.stat_width != 3:
        # Telemetry-enabled policy: widen every site leaf once, here, so
        # no per-family site builder needs to know the extended layout.
        from repro.telemetry import metrics as _tm
        s = _tm.widen_state(s, policy.stat_width)
    return s


def init_cache(cfg, batch: int, cache_len: int) -> PyTree:
    c = {"decoder": transformer.init_stack_cache(cfg, cfg.pattern,
                                                 cfg.n_layers, batch, cache_len)}
    return c


# ===========================================================================
# Trunk: everything up to the final hidden state.
# ===========================================================================
def _embed_tokens(params, tokens, cfg, policy):
    table = qlinear.quantize_weight(params["embed"], policy)
    x = table[tokens].astype(jnp.dtype(cfg.compute_dtype))
    if cfg.embed_scale:
        x = x * jnp.asarray(cfg.d_model ** 0.5, x.dtype)
    return x


def _trunk(params, sites, batch, cfg, policy, seed, step, caches=None):
    """Returns (hidden [B,S,D], new_sites, new_caches, metrics)."""
    new_sites: dict = {}
    metrics = transformer.zero_metrics(cfg)
    enc_out = enc_len = None
    prefix_len = None

    if cfg.family == "encdec" and "frames" in batch:
        frames = batch["frames"].astype(jnp.dtype(cfg.compute_dtype))
        ex, new_sites["enc_in"] = qlinear.qdense(
            frames, params["enc_in"], sites["enc_in"], policy,
            seed=jnp.int32(seed + 1_000_000), step=step)
        epos = jnp.broadcast_to(jnp.arange(ex.shape[1]), ex.shape[:2])
        enc_out, enc_sites, _, emet = transformer.apply_stack(
            params["encoder"], sites["encoder"], ex, cfg=cfg,
            pattern=cfg.enc_pattern, policy=policy,
            seed=seed + 2_000_000, step=step, positions=epos)
        enc_out = layers.apply_norm(enc_out, params["enc_norm"], cfg.norm_kind,
                                   cfg.norm_eps)
        new_sites["encoder"] = enc_sites
        metrics = {k: metrics[k] + emet[k] for k in metrics}
        enc_len = batch.get("frame_len")

    if cfg.family == "vlm" and "patches" in batch:
        patches = batch["patches"].astype(jnp.dtype(cfg.compute_dtype))
        px, new_sites["patch_proj"] = qlinear.qdense(
            patches, params["patch_proj"], sites["patch_proj"], policy,
            seed=jnp.int32(seed + 3_000_000), step=step)
        tx = _embed_tokens(params, batch["tokens"], cfg, policy)
        x = jnp.concatenate([px, tx], axis=1)
        prefix_len = patches.shape[1]
    else:
        x = _embed_tokens(params, batch["tokens"], cfg, policy)

    positions = batch.get("positions")
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(x.shape[1]), x.shape[:2])

    x, dec_sites, new_caches, dmet = transformer.apply_stack(
        params["decoder"], sites["decoder"], x, cfg=cfg, pattern=cfg.pattern,
        policy=policy, seed=seed, step=step, positions=positions,
        caches=caches, enc_out=enc_out, enc_len=enc_len,
        prefix_len=prefix_len)
    new_sites["decoder"] = dec_sites
    metrics = {k: metrics[k] + dmet[k] for k in metrics}

    x = layers.apply_norm(x, params["final_norm"], cfg.norm_kind,
                          cfg.norm_eps)
    return x, new_sites, new_caches, metrics


def _head_weight_raw(params, cfg):
    return params["embed"].T if cfg.tie_embeddings else params["head"]


def _head_weight(params, cfg, policy):
    return qlinear.quantize_weight(_head_weight_raw(params, cfg), policy)


# ===========================================================================
# Training forward + chunked loss.
# ===========================================================================
def loss_fn(params, quant_state, batch, cfg, policy: QuantPolicy,
            seed, step):
    """Returns (loss, (new_quant_state_fwd, metrics)).

    ``new_quant_state_fwd`` carries the forward (activation-site) updates;
    gradient-site statistics arrive through the cotangent of
    ``quant_state`` (see runtime.steps.make_train_step).
    """
    seed = jnp.asarray(seed, jnp.int32)
    step = jnp.asarray(step, jnp.int32)
    x, new_sites, _, metrics = _trunk(params, quant_state, batch, cfg,
                                      policy, seed, step)

    labels = batch["labels"]
    mask = batch["mask"].astype(jnp.float32)
    if cfg.family == "vlm":
        # loss over the text suffix only; hidden states include the prefix.
        x = x[:, batch["patches"].shape[1]:]

    # --- chunked LM head --------------------------------------------------
    site = quant_state["head"]
    xq, new_head_act, xqi = qlinear.act_quant_site(x, site["act"], policy,
                                                   step)
    xq = qlinear.grad_quant_barrier(xq, site["grad"], policy,
                                    seed + 7_000_000, step)
    wq, wqt = qlinear.quantize_weight_q(_head_weight_raw(params, cfg), policy)
    wq = wq.astype(xq.dtype)

    b, s, d = xq.shape
    c = min(cfg.loss_chunk, s)
    assert s % c == 0, (s, c)
    nchunk = s // c
    xc = xq.reshape(b, nchunk, c, d).swapaxes(0, 1)
    lc = labels.reshape(b, nchunk, c).swapaxes(0, 1)
    mc = mask.reshape(b, nchunk, c).swapaxes(0, 1)

    def _chunk_loss(logits, lcb, mcb):
        logz = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, lcb[..., None], axis=-1)[..., 0]
        nll = jnp.sum((logz - gold) * mcb)
        zpen = jnp.sum(jnp.square(logz) * mcb)
        return nll, zpen

    # Each chunk's head projection goes through the backend contraction:
    # the int8 image chunks ride the scan alongside the fp chunks so the
    # fused backend keeps the MXU path (and quant registers) per chunk.
    use_int = (xqi is not None and wqt is not None
               and qbackend.int8_matmul_eligible(policy))
    if use_int:
        qc = xqi.q.reshape(b, nchunk, c, d).swapaxes(0, 1)

        def chunk_nll(carry, args):
            xcb, qcb, lcb, mcb = args
            logits = qbackend.qmatmul(
                policy, "bcd,dv->bcv", xcb,
                qlinear.QTensor(qcb, xqi.scale, xqi.zero_point),
                wq, wqt, out_dtype=jnp.float32)
            return carry, _chunk_loss(logits, lcb, mcb)

        xs = (xc, qc, lc, mc)
    else:
        def chunk_nll(carry, args):
            xcb, lcb, mcb = args
            logits = jnp.einsum("bcd,dv->bcv", xcb, wq,
                                preferred_element_type=jnp.float32)
            return carry, _chunk_loss(logits, lcb, mcb)

        xs = (xc, lc, mc)

    if cfg.remat:
        chunk_nll = jax.checkpoint(chunk_nll)
    _, (nlls, zpens) = jax.lax.scan(chunk_nll, 0.0, xs)
    denom = jnp.maximum(jnp.sum(mask), 1.0)
    loss = jnp.sum(nlls) / denom
    metrics["z_loss_head"] = cfg.logit_z_coef * jnp.sum(zpens) / denom

    total = loss + metrics["aux_loss"] + metrics["z_loss"] + \
        metrics["z_loss_head"]
    metrics["nll"] = loss

    new_quant_state = dict(new_sites)
    new_quant_state["head"] = {"act": new_head_act, "grad": site["grad"]}
    return total, (new_quant_state, metrics)


# ===========================================================================
# Serving: prefill + decode.
# ===========================================================================
def prefill(params, quant_state, batch, cfg, policy: QuantPolicy,
            cache_len: Optional[int] = None, return_stats: bool = False):
    """Run the full prompt, build the decode cache.

    Returns (last_logits [B, V], cache).  The cache's KV entries hold the
    *last* ``window`` tokens for sliding-window blocks (ring buffer), the
    full prompt otherwise.

    ``return_stats=True`` additionally returns the forward stats tree of
    the activation sites — with a telemetry-enabled policy this carries
    per-site clip/SQNR/utilization for the served batch (the serving-side
    quantization health signal; see ``repro.telemetry``).
    """
    seed = jnp.int32(0)
    step = jnp.int32(0)
    tokens = batch["tokens"]
    b, s = tokens.shape[0], tokens.shape[1]
    if cfg.family == "vlm":
        s = s + batch["patches"].shape[1]
    cache_len = cache_len or s

    caches = init_cache(cfg, b, cache_len)
    x, fwd_stats, new_caches, _ = _trunk(params, quant_state, batch, cfg,
                                         policy, seed, step,
                                         caches=caches["decoder"])
    logits = jnp.einsum("bd,dv->bv", x[:, -1].astype(jnp.float32),
                        _head_weight(params, cfg, policy).astype(jnp.float32))
    if return_stats:
        return logits, {"decoder": new_caches}, fwd_stats
    return logits, {"decoder": new_caches}


def decode_step(params, quant_state, token, pos, caches, cfg,
                policy: QuantPolicy):
    """One decode step: token i32[B,1] at absolute positions pos i32[B].

    Returns (logits [B, V], new_caches)."""
    seed = jnp.int32(0)
    step = jnp.int32(0)
    batch = {"tokens": token,
             "positions": jnp.broadcast_to(pos[:, None], token.shape)}
    x, _, new_caches, _ = _trunk(params, quant_state, batch, cfg, policy,
                                 seed, step, caches=caches["decoder"])
    new_caches = {"decoder": new_caches}
    logits = jnp.einsum("bd,dv->bv", x[:, -1].astype(jnp.float32),
                        _head_weight(params, cfg, policy).astype(jnp.float32))
    return logits, new_caches
