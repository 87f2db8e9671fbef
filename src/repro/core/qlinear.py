"""Quantized linear algebra with the paper's training data path (Fig. 1).

A quantized matmul site does, per training step ``t``:

  forward:
      x_q  = Q_Y(x)          activation quantizer (estimator under study);
                             for ``hindsight`` the range is pre-computed
      w_q  = Q_W(w)          current min-max, symmetric (paper sec. 5.2)
      y    = x_q @ w_q + b   int8 x int8 -> int32/fp32 accumulate
      [y is tagged with the gradient barrier]

  backward (through the barrier's custom VJP):
      g_y_q = Q_G(dL/dy)     asymmetric uniform + stochastic rounding,
                             range from the gradient estimator
      dL/dx = g_y_q @ w_q^T  (propagated; quantized again at the previous
                              layer's barrier = the paper's G_X quantizer)
      dL/dw = x_q^T @ g_y_q  kept FP32 (paper keeps the weight gradient FP)

Range state is threaded functionally:

  * activation sites update in the forward pass — the new leaf is returned,
  * gradient sites update through the *cotangent channel*: the barrier's
    VJP returns the observed (min, max) statistics as the "gradient" of the
    state leaf, so ``jax.grad(..., argnums=grad_sites)`` delivers exactly
    the online statistics the paper's accumulator logic would emit.

Every quantizer and contraction dispatches through
:mod:`repro.core.backend` on ``policy.backend``: ``simulated`` evaluates
the quantizers in pure ``jnp``, ``fused`` executes the Pallas kernels from
``repro.kernels`` (interpret mode on CPU).  The two backends are
bit-reproducible against each other — see the backend module docstring
for the parity contract and ``tests/test_backend.py`` for the proof.
"""
from __future__ import annotations

from typing import Optional

import numpy as np

import jax
import jax.numpy as jnp

from . import backend, estimators, quant
from .backend import QTensor, stats_zeros  # re-exported for site callers
from .lru import LruCache
from .policy import QuantPolicy
from .state import INITED, QMAX, QMIN, init_range_state

_F0 = jax.dtypes.float0


def _float0_like(x):
    return np.zeros(np.shape(x), dtype=_F0)


def _site_key(seed: jax.Array, salt: int) -> jax.Array:
    return backend.site_key(seed, salt)


# ---------------------------------------------------------------------------
# Q_W: weight quantizer — current min-max, no state.
# ---------------------------------------------------------------------------
def quantize_weight(w: jax.Array, policy: QuantPolicy) -> jax.Array:
    return quantize_weight_q(w, policy)[0]


def quantize_weight_q(
    w: jax.Array, policy: QuantPolicy
) -> tuple[jax.Array, Optional[QTensor]]:
    """Quantize a weight; returns ``(wq, qtensor)``.

    ``qtensor`` is the int8 image + registers the backend matmul consumes;
    it is ``None`` when weight quantization is off or when the
    ``int8_weight_gather`` sharding optimisation owns the int8 form (its
    integer tensor is pinned to the all-gather inside the STE and the
    matmul must consume the gathered fp values).
    """
    if not (policy.enabled and policy.quantize_weights):
        return w, None
    if policy.int8_weight_gather and policy.weight_spec.bits <= 8:
        mn, mx = quant.tensor_minmax(w)
        return _fake_quant_ste_gathered(w, mn, mx, policy.weight_spec), None
    return backend.weight_quantize(policy, w)


_GATHERED_STE_CACHE = LruCache()


def _fake_quant_ste_gathered(w, qmin, qmax, spec):
    """fake_quant_ste whose forward CONSTRAINS the int8 intermediate to be
    fully replicated: the SPMD partitioner then performs the (FSDP) weight
    all-gather on the 1-byte tensor and dequantizes AFTER the gather —
    2-4x less gather wire traffic.  Numerically identical to
    fake_quant_ste; same clipped-STE gradient."""
    def build():
        @jax.custom_vjp
        def ste(x, mn, mx):
            return _gathered_fwd(x, mn, mx, spec)[0]

        def fwd(x, mn, mx):
            y, mask = _gathered_fwd(x, mn, mx, spec)
            return y, mask

        def bwd(mask, g):
            z = jnp.zeros((), jnp.float32)
            return jnp.where(mask, g, 0.0).astype(g.dtype), z, z

        ste.defvjp(fwd, bwd)
        return ste

    fn = _GATHERED_STE_CACHE.get_or_build(spec, build)
    return fn(w, jnp.asarray(qmin, jnp.float32), jnp.asarray(qmax, jnp.float32))


def _gathered_fwd(x, qmin, qmax, spec):
    from repro.runtime import sharding as _sh   # leaf module; lazy to be safe
    q = quant.quantize(x, qmin, qmax, spec)
    q = q.astype(jnp.int8 if spec.symmetric else jnp.uint8)
    q = _sh.replicate_hint(q)                    # <- gather lands HERE (int8)
    y = quant.dequantize(q, qmin, qmax, spec).astype(x.dtype)
    scale, zp = quant.scale_zero_point(qmin, qmax, spec)
    lo = (spec.int_min - zp) * scale
    hi = (spec.int_max - zp) * scale
    mask = jnp.logical_and(x >= lo, x <= hi)
    return y, mask


# ---------------------------------------------------------------------------
# Q_Y: activation quantizer site.
#
# The site emits the observed STATISTICS (min, max, visited) rather than an
# updated leaf: the training step combines statistics across gradient-
# accumulation microbatches (min of mins / max of maxes) and applies the
# estimator update ONCE per optimizer step — matching the paper's
# one-update-per-iteration semantics under grad accumulation.
# ---------------------------------------------------------------------------
def act_quant_site(
    x: jax.Array,
    leaf: jax.Array,
    policy: QuantPolicy,
    step: jax.Array,
) -> tuple[jax.Array, jax.Array, Optional[QTensor]]:
    """Quantize an activation tensor via the policy's backend.

    Returns ``(x_q, observed stats, qtensor)``; ``qtensor`` (the int8
    image + quant registers, ``None`` when activation quantization is off)
    lets a downstream matmul consume the integer form directly — pass it
    to :func:`qdense_pre` so shared-input projections stay single-pass.
    """
    if not (policy.enabled and policy.quantize_acts):
        return x, stats_zeros(policy), None
    xq, st, qt = backend.act_quantize(policy, x, leaf, step)
    return xq, jax.lax.stop_gradient(st), qt


# ---------------------------------------------------------------------------
# Q_G: gradient quantizer barrier (backward quantization + stats emission).
# ---------------------------------------------------------------------------
_BARRIER_CACHE = LruCache()


def _make_barrier(policy: QuantPolicy):
    @jax.custom_vjp
    def barrier(y, leaf, seed, step):
        return y

    def fwd(y, leaf, seed, step):
        return y, (leaf, seed, step)

    def bwd(res, g):
        leaf, seed, step = res
        gq, stats = backend.grad_quantize(policy, g, leaf, seed, step)
        return gq, stats, _float0_like(seed), _float0_like(step)

    barrier.defvjp(fwd, bwd)
    return barrier


def grad_quant_barrier(
    y: jax.Array,
    leaf: jax.Array,
    policy: QuantPolicy,
    seed: jax.Array,
    step: jax.Array,
) -> jax.Array:
    """Identity in the forward pass; quantizes the cotangent in the backward
    pass and emits the observed (min, max) as the cotangent of ``leaf``."""
    if not (policy.enabled and policy.quantize_grads):
        return y
    fn = _BARRIER_CACHE.get_or_build(policy, lambda: _make_barrier(policy))
    return fn(y, leaf, seed.astype(jnp.int32), step.astype(jnp.int32))


# ---------------------------------------------------------------------------
# Site containers.
# ---------------------------------------------------------------------------
def init_site(policy: Optional[QuantPolicy] = None) -> dict:
    """State for one quantized matmul: activation-in + grad-out leaves.

    Model builders call this without ``policy`` (width-3 leaves); a
    telemetry-enabled policy widens the assembled tree once at the top
    (see ``repro.telemetry.metrics.widen_state``), so only entry points
    like ``model.init_quant_state`` need to thread the policy."""
    width = 3 if policy is None else policy.stat_width
    return {"act": init_range_state(width), "grad": init_range_state(width)}


def qdense_pre(
    xq: jax.Array,
    w: jax.Array,
    site: dict,
    policy: QuantPolicy,
    *,
    einsum_spec: str = "...k,kn->...n",
    bias: Optional[jax.Array] = None,
    seed: jax.Array,
    step: jax.Array,
    qinfo: Optional[QTensor] = None,
) -> tuple[jax.Array, dict]:
    """Quantized matmul whose input was ALREADY quantized by a shared
    activation site (see :func:`act_quant_site`).

    The paper quantizes each layer output Y exactly once; when several
    projections consume the same tensor (q/k/v, MLP up/gate, RG-LRU
    in/gate, MoE up/gate) re-quantizing it per consumer would both deviate
    from the paper and triple the fake-quant memory traffic (measured in
    EXPERIMENTS.md §Perf).  This entry point shares one quantized input and
    keeps a per-projection gradient site.  ``qinfo`` is the shared site's
    :class:`QTensor`; with it the contraction consumes the int8 image
    directly (required for the fused backend's single-pass dataflow)."""
    wq, wqt = quantize_weight_q(w, policy)
    wq = wq.astype(xq.dtype)
    y = backend.qmatmul(policy, einsum_spec, xq, qinfo, wq, wqt)
    if bias is not None:
        y = y + bias.astype(xq.dtype)
    y = grad_quant_barrier(y, site["grad"], policy, seed, step)
    return y, {"act": stats_zeros(policy), "grad": stats_zeros(policy)}


def qdense(
    x: jax.Array,
    w: jax.Array,
    site: dict,
    policy: QuantPolicy,
    *,
    bias: Optional[jax.Array] = None,
    seed: jax.Array,
    step: jax.Array,
) -> tuple[jax.Array, dict]:
    """Quantized ``x @ w (+ bias)`` over the last axis of ``x``.

    Returns ``(y, new_site)`` where ``new_site['act']`` is the forward-
    updated activation leaf and ``new_site['grad']`` is passed through
    unchanged (its update arrives via the cotangent channel).
    """
    xq, act_stats, xqt = act_quant_site(x, site["act"], policy, step)
    wq, wqt = quantize_weight_q(w, policy)
    wq = wq.astype(x.dtype)
    # int32/fp32 accumulation regardless of storage dtype — the MAC-array
    # accumulator of the paper's hardware (and the MXU); see backend.qmatmul.
    y = backend.qmatmul(policy, "...k,kn->...n", xq, xqt, wq, wqt)
    if bias is not None:
        y = y + bias.astype(x.dtype)
    y = grad_quant_barrier(y, site["grad"], policy, seed, step)
    # grad-site statistics arrive via the cotangent channel; the forward
    # stats tree marks that slot "not visited" (zeros).
    return y, {"act": act_stats, "grad": stats_zeros(policy)}


def qeinsum(
    spec: str,
    x: jax.Array,
    w: jax.Array,
    site: dict,
    policy: QuantPolicy,
    *,
    seed: jax.Array,
    step: jax.Array,
) -> tuple[jax.Array, dict]:
    """Quantized einsum for non-2D contractions (attention proj, MoE experts).

    Same data path as :func:`qdense`; per-tensor ranges over the whole
    operand (the paper's per-tensor setting).
    """
    xq, act_stats, xqt = act_quant_site(x, site["act"], policy, step)
    wq, wqt = quantize_weight_q(w, policy)
    wq = wq.astype(x.dtype)
    y = backend.qmatmul(policy, spec, xq, xqt, wq, wqt)
    y = grad_quant_barrier(y, site["grad"], policy, seed, step)
    return y, {"act": act_stats, "grad": stats_zeros(policy)}


# ---------------------------------------------------------------------------
# Train-step-side state plumbing.
# ---------------------------------------------------------------------------
def merge_stats(fwd_stats, cot_stats):
    """Merge the forward (activation) stats tree with the cotangent-channel
    (gradient) stats tree into one tree shaped like the quant state.

    Both trees have 'act'/'grad' leaves; the forward tree carries real act
    stats + zero grad slots, the cotangent tree vice-versa, so an
    element-wise combine is exact."""
    return jax.tree_util.tree_map(combine_stats, fwd_stats, cot_stats)


def combine_stats(a: jax.Array, b: jax.Array) -> jax.Array:
    """Combine two observations of the same site (e.g. two grad-accum
    microbatches): min of mins, max of maxes, visited-or.  Slots never
    visited carry zeros, which must not contaminate the min/max — mask by
    each side's own visited flag.

    Width-10 (telemetry) vectors additionally sum the clip/count/err/sig
    counters and max-combine the utilization/drift/streak slots, so the
    per-step aggregate is exact across microbatches and shards."""
    av = a[..., INITED:INITED + 1] > 0.5
    bv = b[..., INITED:INITED + 1] > 0.5
    big = jnp.float32(3.4e38)
    amin = jnp.where(av[..., 0], a[..., QMIN], big)
    bmin = jnp.where(bv[..., 0], b[..., QMIN], big)
    amax = jnp.where(av[..., 0], a[..., QMAX], -big)
    bmax = jnp.where(bv[..., 0], b[..., QMAX], -big)
    visited = jnp.maximum(a[..., INITED], b[..., INITED])
    mn = jnp.where(visited > 0.5, jnp.minimum(amin, bmin), 0.0)
    mx = jnp.where(visited > 0.5, jnp.maximum(amax, bmax), 0.0)
    base = jnp.stack([mn, mx, visited], axis=-1)
    if a.shape[-1] == 3:
        return base
    from repro.telemetry import metrics as _tm
    sums, maxes = _tm.combine_tail(a, b)
    return jnp.concatenate([base, sums, maxes], axis=-1)


def update_quant_state(policy: QuantPolicy, quant_state, stats):
    """One estimator update per site from the step's combined statistics.
    Activation leaves use the act estimator, gradient leaves the grad one
    (leaf kind determined by its dict key)."""
    def upd(path, leaf, st):
        kind = None
        for p in reversed(path):
            k = getattr(p, "key", None)
            if k in ("act", "grad"):
                kind = k
                break
        cfg = policy.act_estimator if kind == "act" else policy.grad_estimator
        return estimators.update(cfg, leaf, st, telemetry=policy.telemetry)

    return jax.tree_util.tree_map_with_path(upd, quant_state, stats)


def zero_stats_like(state):
    """Stats tree meaning "no site visited" (state passes through unchanged)."""
    return jax.tree_util.tree_map(jnp.zeros_like, state)
