"""Step builders: training (with gradient accumulation), prefill, decode.

Train-state pytree:

    {"params": ..., "opt": ..., "quant": ..., "step": i32[]}

Quant-range plumbing per step (the paper's update loop, distributed):

  1. every quantizer uses the PRE-STEP state (in-hindsight: static ranges),
  2. each microbatch's forward emits activation-site statistics; each
     microbatch's backward emits gradient-site statistics through the
     cotangent channel of the quant state (``jax.value_and_grad`` argnums=1),
  3. microbatch statistics combine with (min, max, visited-or) — under
     pjit, per-shard partials reduce with one fused scalar all-reduce,
  4. ONE estimator update per optimizer step (eq. 2-3).

Gradient accumulation is a ``lax.scan`` over microbatches (constant HLO
size); parameter gradients average, statistics combine.  The optional
``compress`` hook replaces the (implicit) fp32 DP gradient all-reduce with
the int8 in-hindsight compressed reduction from ``runtime.compress`` —
the beyond-paper extension of the paper's estimator to the collective
layer.
"""
from __future__ import annotations

import functools
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp

from repro.core import backend as qbackend
from repro.core import qlinear
from repro.core.policy import QuantPolicy
from repro.models import model, moe, transformer
from repro.optim import apply_updates, clip_by_global_norm

PyTree = Any


def init_train_state(key, cfg, optimizer,
                     policy: Optional[QuantPolicy] = None) -> PyTree:
    """``policy`` only matters for its telemetry flag: a telemetry-enabled
    policy widens every quant-state leaf from 3 to 10 floats so the
    cotangent channel can carry the health counters."""
    params = model.init_params(key, cfg)
    return {
        "params": params,
        "opt": optimizer.init(params),
        "quant": model.init_quant_state(cfg, policy),
        "step": jnp.zeros((), jnp.int32),
    }


def make_train_step(
    cfg,
    policy: QuantPolicy,
    optimizer,
    lr_schedule: Callable,
    *,
    grad_accum: int = 1,
    clip_norm: Optional[float] = 1.0,
    compress=None,                      # runtime.compress.Compressor | None
) -> Callable:
    """Returns ``train_step(state, batch) -> (state, metrics)`` (jit-able).

    The step is backend-agnostic: ``policy.backend`` selects whether the
    quantization sites execute as simulated fake-quant or as the fused
    Pallas kernels, and the two produce bit-identical quant-state updates
    (see ``repro.core.backend``), so statistics combining, grad-accum,
    telemetry widening and checkpointing need no backend awareness.
    """
    qbackend.validate(policy)
    bias_rate = moe.BIAS_UPDATE_RATE \
        if cfg.moe and cfg.moe.scoring == "sigmoid" else 0.0

    def micro(params, quant, mb, step, midx):
        seed = step * 262144 + midx * 8192

        def lf(p, q):
            return model.loss_fn(p, q, mb, cfg, policy, seed, step)

        (loss, (fwd_stats, met)), (pg, qg) = jax.value_and_grad(
            lf, argnums=(0, 1), has_aux=True)(params, quant)
        stats = qlinear.merge_stats(fwd_stats, qg)
        return loss, pg, stats, met

    def train_step(state, batch):
        params, quant, step = state["params"], state["quant"], state["step"]

        if grad_accum == 1:
            loss, grads, stats, met = micro(params, quant, batch, step, 0)
        else:
            mbs = jax.tree_util.tree_map(
                lambda x: x.reshape((grad_accum, x.shape[0] // grad_accum)
                                    + x.shape[1:]), batch)

            def body(carry, xs):
                g_acc, s_acc, l_acc, m_acc = carry
                mb, midx = xs
                loss, pg, stats, met = micro(params, quant, mb, step, midx)
                g_acc = jax.tree_util.tree_map(jnp.add, g_acc, pg)
                s_acc = jax.tree_util.tree_map(qlinear.combine_stats,
                                               s_acc, stats)
                m_acc = jax.tree_util.tree_map(jnp.add, m_acc, met)
                return (g_acc, s_acc, l_acc + loss, m_acc), None

            zeros_g = jax.tree_util.tree_map(
                lambda p: jnp.zeros(p.shape, jnp.float32), params)
            zeros_s = qlinear.zero_stats_like(quant)
            zeros_m = dict(transformer.zero_metrics(cfg),
                           z_loss_head=jnp.float32(0.0), nll=jnp.float32(0.0))
            (grads, stats, loss, met), _ = jax.lax.scan(
                body, (zeros_g, zeros_s, jnp.float32(0.0), zeros_m),
                (mbs, jnp.arange(grad_accum)))
            inv = 1.0 / grad_accum
            grads = jax.tree_util.tree_map(lambda g: g * inv, grads)
            loss = loss * inv
            met = jax.tree_util.tree_map(lambda m: m * inv, met)

        if compress is not None:
            grads, stats = compress(grads, stats)

        # A sigmoid router's correction bias is no weight: its cotangent is
        # the step's routing load (models/moe.py), which moves it by the
        # aux-loss-free rule after the optimizer, in place of an update.
        loads = grads
        if bias_rate:
            grads = jax.tree_util.tree_map_with_path(
                lambda path, g: jnp.zeros_like(g)
                if getattr(path[-1], "key", None) == "router_bias" else g,
                grads)

        metrics = dict(met)
        if clip_norm is not None:
            grads, gnorm = clip_by_global_norm(grads, clip_norm)
            metrics["grad_norm"] = gnorm

        lr = lr_schedule(step)
        updates, new_opt = optimizer.update(grads, state["opt"], params, lr)
        new_params = apply_updates(params, updates)
        if bias_rate:
            new_params = moe.update_router_bias(new_params, params, loads,
                                                bias_rate)
        new_quant = qlinear.update_quant_state(policy, quant, stats)

        metrics["loss"] = loss
        metrics["lr"] = lr
        new_state = {"params": new_params, "opt": new_opt,
                     "quant": new_quant, "step": step + 1}
        return new_state, metrics

    return train_step


def make_prefill_step(cfg, policy: QuantPolicy,
                      cache_len: Optional[int] = None) -> Callable:
    def prefill_step(params, quant, batch):
        return model.prefill(params, quant, batch, cfg, policy,
                             cache_len=cache_len)
    return prefill_step


def make_decode_step(cfg, policy: QuantPolicy) -> Callable:
    def decode_step(params, quant, batch, caches):
        return model.decode_step(params, quant, batch["token"], batch["pos"],
                                 caches, cfg, policy)
    return decode_step
