"""Execution-backend dispatch for the quantized training data path.

The paper's claim (Fig. 4) is that *in-hindsight* ranges make single-pass
static quantization possible on the accelerator: with the quantization
registers known before the tensor exists, each accumulator tile can be
requantized and written once (fp read + int8 write), with the next step's
min/max statistics taken from the same resident tile.  This module makes
that claim executable end to end by giving every quantization site two
interchangeable implementations:

  ``simulated``  today's fake-quant path: pure ``jnp`` quantize/dequantize
                 with clipped-STE gradients.  Runs anywhere, default.
  ``fused``      the Pallas kernels from ``repro.kernels`` (interpret mode
                 on CPU): ``fused_quantize`` for activations,
                 ``stochastic_quantize`` for gradient cotangents, and the
                 int8 MXU matmul for the contraction itself.  Legal only
                 for fully-static policies (``policy.is_fully_static``) —
                 a dynamic estimator needs the full tensor before it can
                 pick a range, which is precisely the two-pass dataflow
                 the kernels exist to avoid.

Backend parity contract
-----------------------
A training step is **bit-reproducible across backends**: identical quant
state trees, losses and parameter updates.  This holds because every
site-level operation is integer-exact or arithmetic-order-pinned:

  * quantize: both backends evaluate ``round/floor(x / s + zp [+ u])``
    with *pre-computed* ``(s, zp)`` registers — same fp32 ops, same
    rounding, bit-equal integer images (``tests/test_backend.py``).
  * statistics: min/max reductions are exact in any association, so the
    kernels' per-tile partials reduce to the same bits as
    ``tensor_minmax``.
  * matmul: when both operands carry an int8 image on the kernel layout
    (asymmetric uint8 activations x symmetric int8 weights) BOTH backends
    evaluate the accelerator-exact form ``alpha * (int32 contraction)``:
    the simulated backend with an int32 XLA einsum, the fused backend
    with the Pallas MXU kernel.  The int32 accumulation is exact, the
    fp32 epilogue is a single pinned multiply.  (Before this layer the
    simulated path accumulated dequantized fp32 values — an ulp-level
    difference that made cross-backend bit-parity impossible; the int32
    form is also the more faithful model of the paper's MAC array.)

Sites whose operands have no int8 image (quantizer disabled for one
family, non-8-bit specs, ``int8_weight_gather``) fall back to the fp
einsum of the on-grid tensors on both backends — still bit-identical
across backends, just not integer-executed.
"""
from __future__ import annotations

from typing import NamedTuple, Optional

import numpy as np

import jax
import jax.numpy as jnp

from . import estimators, quant
from .lru import LruCache
from .state import INITED, QMAX, QMIN, pack_stats

SIMULATED = "simulated"
FUSED = "fused"
BACKENDS = (SIMULATED, FUSED)

# Pallas wrappers are imported lazily so that simulated-only sessions (and
# environments without a working pallas install) never pay for them.
def _ops():
    from repro.kernels import ops
    return ops


class QTensor(NamedTuple):
    """Integer image of an on-grid fp tensor plus its quant registers.

    ``values`` stay in the differentiable fp graph (STE); ``q`` is the
    int8/uint8 storage form the MXU kernel consumes, bit-consistent with
    ``values == dequantize(q, scale, zero_point)``.
    """

    q: jax.Array           # uint8 (asymmetric) / int8 (symmetric) storage
    scale: jax.Array       # fp32 scalar register
    zero_point: jax.Array  # fp32 scalar register (integral-valued)


# ---------------------------------------------------------------------------
# Policy validation.
# ---------------------------------------------------------------------------
def validate(policy) -> None:
    """Raise ``ValueError`` if the policy's backend selection is illegal."""
    if policy.backend not in BACKENDS:
        raise ValueError(
            f"unknown backend {policy.backend!r}; expected one of {BACKENDS}")
    if policy.backend != FUSED:
        return
    dynamic = []
    if policy.quantize_acts and not policy.act_estimator.is_static:
        dynamic.append(f"act_estimator={policy.act_estimator.kind!r}")
    if policy.quantize_grads and not policy.grad_estimator.is_static:
        dynamic.append(f"grad_estimator={policy.grad_estimator.kind!r}")
    if dynamic:
        raise ValueError(
            "backend='fused' requires fully-static quantization ranges "
            "(the single-pass kernels consume pre-computed quant registers; "
            "a dynamic estimator needs the whole tensor before choosing a "
            f"range — the two-pass dataflow of paper eq. 5). Dynamic: "
            f"{', '.join(dynamic)}. Use estimators from "
            f"{estimators.STATIC_ESTIMATORS} or backend='simulated'.")
    tele = policy.telemetry
    if tele.enabled and tele.guard and tele.mode == "dynamic":
        raise ValueError(
            "backend='fused' cannot honor the overflow guard's 'dynamic' "
            "fallback mode (it re-quantizes with current min-max, which is "
            "a dynamic range). Use guard mode='widen', which keeps ranges "
            "static, or backend='simulated'.")


def int8_matmul_eligible(policy) -> bool:
    """True iff this policy's act/weight quantizers produce operands on
    the int8 MXU kernel layout (asymmetric uint8 x symmetric int8)."""
    return bool(
        policy.enabled
        and policy.quantize_acts and policy.quantize_weights
        and policy.act_spec.bits == 8 and not policy.act_spec.symmetric
        and policy.weight_spec.bits == 8 and policy.weight_spec.symmetric
        and not policy.int8_weight_gather
    )


# ---------------------------------------------------------------------------
# Per-site PRNG key derivation (shared by both backends so the stochastic
# rounding noise — and therefore the quantized gradients — are identical).
# ---------------------------------------------------------------------------
def site_key(seed: jax.Array, salt: int) -> jax.Array:
    """Cheap deterministic per-site PRNG key derivation from an int32 seed."""
    s = seed.astype(jnp.uint32) ^ jnp.uint32(salt * 0x9E3779B9 & 0xFFFFFFFF)
    return jax.random.PRNGKey(s.astype(jnp.int32))


def stats_zeros(policy) -> jax.Array:
    """A "site not visited" stats vector of the policy's stat width."""
    return jnp.zeros((policy.stat_width,), jnp.float32)


def float0_like(x):
    return np.zeros(np.shape(x), dtype=jax.dtypes.float0)


# ---------------------------------------------------------------------------
# Fake-quant with STE, integer image and fused statistics.
#
# One custom_vjp per (spec, backend): forward returns the on-grid fp
# tensor, its integer storage image and the observed (min, max); backward
# is the standard clipped STE.  The fused variant runs the whole forward
# as the single-pass Pallas kernel.
# ---------------------------------------------------------------------------
_QUANTIZER_CACHE = LruCache()


def canonical(x: jax.Array) -> jax.Array:
    """fp32 view of ``x`` rounded to its NOMINAL dtype precision.

    XLA propagates excess precision through narrow-dtype casts (an
    ``f32 -> bf16 -> f32`` round trip may be elided), so a plain
    ``x.astype(float32)`` can observe *unrounded* values — and whether it
    does depends on fusion decisions, i.e. on unrelated graph context.
    That is fatal for the backend parity contract (a ``pallas_call`` is a
    compilation barrier that materializes true bf16) and it silently made
    the simulated estimator statistics compilation-dependent.
    ``lax.reduce_precision`` is semantically binding: both backends —
    and any two compilations of the same program — see identical values.
    """
    xf = x.astype(jnp.float32)
    if x.dtype in (jnp.float32, jnp.float64):
        return xf
    fi = jnp.finfo(x.dtype)
    return jax.lax.reduce_precision(xf, fi.nexp, fi.nmant)


def _quantizer_fwd(x, qmin, qmax, spec: quant.QuantSpec, fused: bool):
    xf = canonical(x)
    if fused:
        q, mn, mx = _ops().fused_quantize(xf, qmin, qmax, spec=spec)
    else:
        q = quant.quantize(xf, qmin, qmax, spec)
        if spec.bits <= 8:  # narrow storage only when the grid fits
            q = q.astype(jnp.int8 if spec.symmetric else jnp.uint8)
        mn, mx = quant.tensor_minmax(xf)
    xq = quant.dequantize(q, qmin, qmax, spec).astype(x.dtype)
    scale, zp = quant.scale_zero_point(qmin, qmax, spec)
    lo = (spec.int_min - zp) * scale
    hi = (spec.int_max - zp) * scale
    mask = jnp.logical_and(xf >= lo, xf <= hi)
    return (xq, q, mn, mx), mask


def _make_quantizer(spec: quant.QuantSpec, fused: bool):
    @jax.custom_vjp
    def fq(x, qmin, qmax):
        return _quantizer_fwd(x, qmin, qmax, spec, fused)[0]

    def fwd(x, qmin, qmax):
        out, mask = _quantizer_fwd(x, qmin, qmax, spec, fused)
        return out, mask

    def bwd(mask, cts):
        g_xq = cts[0]  # cotangents of (q, mn, mx) are ignored
        gx = jnp.where(mask, g_xq, 0.0).astype(g_xq.dtype)
        z = jnp.zeros((), jnp.float32)
        return gx, z, z

    fq.defvjp(fwd, bwd)
    return fq


def get_quantizer(spec: quant.QuantSpec, fused: bool):
    """STE fake-quant returning ``(xq, q, obs_min, obs_max)``.

    The Pallas kernels store int8 — wider grids (e.g. the 16-bit
    calibration observation policy) always run the jnp math.
    """
    fused = bool(fused) and spec.bits <= 8
    key = (spec, fused)
    return _QUANTIZER_CACHE.get_or_build(
        key, lambda: _make_quantizer(spec, fused))


# ---------------------------------------------------------------------------
# Q_Y: activation quantizer.
# ---------------------------------------------------------------------------
def act_quantize(policy, x: jax.Array, leaf: jax.Array, step: jax.Array):
    """Full activation-quantizer site.  Returns ``(xq, stats, qtensor)``.

    Simulated: ranges (estimator) -> fake-quant STE -> stats reduction.
    Fused: ONE pass of the ``fused_quantize`` kernel with the leaf's
    pre-computed range; the next-step statistics come from the kernel's
    per-tile partials, so no separate ``tensor_minmax`` reduction of ``x``
    is emitted.  The paper's first-batch initialisation (an uninitialized
    leaf quantizes with its own min/max) re-runs the kernel with the
    observed range under ``lax.cond`` — paid only while uninitialized.
    """
    return site_quantize(policy, x, leaf, step, name="act")


def site_quantize(policy, x: jax.Array, leaf: jax.Array, step: jax.Array,
                  *, cfg=None, spec=None, name: str = "act"):
    """The activation-quantizer site with an overridable (estimator, spec,
    scope-name) triple — :func:`act_quantize` with ``name='act'`` is the
    classic Q_Y site; the attention core reuses the same machinery for its
    q/k/v operand sites (``attn_q`` on the act spec, ``attn_k``/``attn_v``
    on the symmetric :data:`KV_SPEC` grid)."""
    cfg = policy.act_estimator if cfg is None else cfg
    spec = policy.act_spec if spec is None else spec
    tele = policy.telemetry
    # named_scope: device profiles / HLO dumps show this quant site as
    # "quant_<name>/..." instead of an anonymous fusion (pure metadata —
    # the computation, and therefore backend parity, is unchanged).
    with jax.named_scope(f"quant_{name}_{policy.backend}"):
        xf = canonical(x)  # nominal-precision view shared by every consumer
        if policy.backend == FUSED:
            xq, q, used_qmin, used_qmax, obs = _fused_static_quant(
                cfg, spec, x, leaf, step, tele)
        else:
            used_qmin, used_qmax = estimators.ranges(
                cfg, leaf, xf, spec, step, telemetry=tele)
            fq = get_quantizer(spec, fused=False)
            xq, q, mn, mx = fq(x, used_qmin, used_qmax)
            obs = (mn, mx)
        st = estimators.stats(cfg, xf, used_qmin, used_qmax, observed=obs)
        if tele.enabled:
            from repro.telemetry import metrics as _tm
            st = _tm.site_stats(xf, used_qmin, used_qmax, spec, st,
                                tele.sample)
        scale, zp = quant.scale_zero_point(used_qmin, used_qmax, spec)
        qt = QTensor(jax.lax.stop_gradient(q),
                     jax.lax.stop_gradient(scale),
                     jax.lax.stop_gradient(zp))
        return xq, st, qt


def _fused_static_quant(cfg, spec, x, leaf, step, tele):
    fq = get_quantizer(spec, fused=True)
    if cfg.kind == estimators.FIXED:
        qmin = jnp.float32(cfg.fixed_min)
        qmax = jnp.float32(cfg.fixed_max)
        xq, q, mn, mx = fq(x, qmin, qmax)
        return xq, q, qmin, qmax, (mn, mx)
    # HINDSIGHT: static pass with the pre-computed range; the kernel's
    # stats partials double as the estimator's online statistics AND the
    # uninitialized-leaf fallback range.
    xq0, q0, mn, mx = fq(x, leaf[QMIN], leaf[QMAX])
    qmin, qmax = estimators.ranges(cfg, leaf, x, spec, step, telemetry=tele,
                                   observed=(mn, mx))
    xq, q = jax.lax.cond(
        leaf[INITED] > 0.5,
        lambda: (xq0, q0),
        lambda: fq(x, mn, mx)[:2],
    )
    return xq, q, qmin, qmax, (mn, mx)


# ---------------------------------------------------------------------------
# Q_W: weight quantizer (current min-max — the range is data-dependent but
# known before the matmul, so the fused backend only saves the quantize
# pass, not the reduction; the paper accepts this for weights).
# ---------------------------------------------------------------------------
def weight_quantize(policy, w: jax.Array):
    """Returns ``(wq, qtensor)`` on the weight spec's symmetric grid."""
    spec = policy.weight_spec
    with jax.named_scope(f"quant_weight_{policy.backend}"):
        mn, mx = quant.tensor_minmax(canonical(w))
        fq = get_quantizer(spec, fused=(policy.backend == FUSED))
        wq, q, _, _ = fq(w, mn, mx)
        scale, zp = quant.scale_zero_point(mn, mx, spec)
        qt = QTensor(jax.lax.stop_gradient(q),
                     jax.lax.stop_gradient(scale),
                     jax.lax.stop_gradient(zp))
        return wq, qt


# ---------------------------------------------------------------------------
# Q_G: gradient quantizer (runs inside the barrier's backward pass).
# ---------------------------------------------------------------------------
def grad_quantize(policy, g: jax.Array, leaf: jax.Array,
                  seed: jax.Array, step: jax.Array):
    """Quantize a cotangent; returns ``(gq, stats)``.

    Both backends draw the stochastic-rounding noise from the same
    counter-based key, so the quantized gradients are bit-identical.  On
    a real TPU the fused path would switch to on-chip
    ``pltpu.prng_random_bits`` (see ``kernels/stochastic_quantize.py``).
    """
    cfg, spec = policy.grad_estimator, policy.grad_spec
    tele = policy.telemetry
    with jax.named_scope(f"quant_grad_{policy.backend}"):
        noise = None
        if spec.stochastic:
            noise = jax.random.uniform(site_key(seed, 1), g.shape,
                                       jnp.float32)
        gf = canonical(g)
        if policy.backend == FUSED and spec.bits <= 8:
            gq, used_qmin, used_qmax, obs = _fused_grad_quant(
                cfg, spec, g, gf, leaf, step, tele, noise)
        else:
            used_qmin, used_qmax = estimators.ranges(
                cfg, leaf, gf, spec, step, telemetry=tele)
            gq = quant.fake_quant_raw(gf, used_qmin, used_qmax, spec,
                                      noise).astype(g.dtype)
            obs = None
        st = estimators.stats(cfg, gf, used_qmin, used_qmax, observed=obs)
        if tele.enabled:
            from repro.telemetry import metrics as _tm
            st = _tm.site_stats(gf, used_qmin, used_qmax, spec, st,
                                tele.sample)
        return gq, st


def _kernel_quant(spec, xf, qmin, qmax, noise):
    ops = _ops()
    if noise is not None:
        return ops.stochastic_quantize(xf, qmin, qmax, noise, spec=spec)
    return ops.fused_quantize(xf, qmin, qmax, spec=spec)


def _fused_grad_quant(cfg, spec, g, gf, leaf, step, tele, noise):
    if cfg.kind == estimators.FIXED:
        qmin = jnp.float32(cfg.fixed_min)
        qmax = jnp.float32(cfg.fixed_max)
        q, mn, mx = _kernel_quant(spec, gf, qmin, qmax, noise)
        gq = quant.dequantize(q, qmin, qmax, spec).astype(g.dtype)
        return gq, qmin, qmax, (mn, mx)
    q0, mn, mx = _kernel_quant(spec, gf, leaf[QMIN], leaf[QMAX], noise)
    qmin, qmax = estimators.ranges(cfg, leaf, gf, spec, step, telemetry=tele,
                                   observed=(mn, mx))
    gq = jax.lax.cond(
        leaf[INITED] > 0.5,
        lambda: quant.dequantize(q0, leaf[QMIN], leaf[QMAX],
                                 spec).astype(g.dtype),
        lambda: quant.dequantize(_kernel_quant(spec, gf, mn, mx, noise)[0],
                                 mn, mx, spec).astype(g.dtype),
    )
    return gq, qmin, qmax, (mn, mx)


# ---------------------------------------------------------------------------
# The contraction: int8 MXU path when both operands carry an image,
# fp einsum of the on-grid tensors otherwise.
# ---------------------------------------------------------------------------
_QMATMUL_CACHE = LruCache()

_ELLIPSIS_POOL = "ZYXWVUTSRQPO"  # fresh labels for "..." expansion


def resolve_einsum_spec(espec: str, x_ndim: int) -> str:
    """Expand a ``...`` in the activation operand / output to explicit
    labels.  Single source of truth for the expansion — both this
    module's cache keys and ``repro.kernels.ops.plan_einsum`` use it."""
    lhs, y = espec.replace(" ", "").split("->")
    xs, ws = lhs.split(",")
    if "..." in xs:
        fill = _ELLIPSIS_POOL[: x_ndim - (len(xs) - 3)]
        xs = xs.replace("...", fill)
        y = y.replace("...", fill)
    return f"{xs},{ws}->{y}"


def _make_qmatmul(espec: str, fused: bool):
    lhs, y = espec.split("->")
    xs, ws = lhs.split(",")
    dx_spec = f"{y},{ws}->{xs}"
    dw_spec = f"{xs},{y}->{ws}"

    def fwd_math(xq, wq, q_x, q_w, x_zp, alpha):
        if fused:
            ops = _ops()
            plan = ops.plan_einsum(espec, q_x.ndim, q_w.ndim)
            y_fp, _, _ = ops.int8_matmul_fp(q_x, q_w, x_zp, alpha, plan=plan)
        else:
            rx = q_x.astype(jnp.int32) - jnp.round(x_zp).astype(jnp.int32)
            acc = jnp.einsum(espec, rx, q_w.astype(jnp.int32),
                             preferred_element_type=jnp.int32)
            y_fp = alpha * acc.astype(jnp.float32)
        return y_fp

    @jax.custom_vjp
    def qmm(xq, wq, q_x, q_w, x_zp, alpha):
        return fwd_math(xq, wq, q_x, q_w, x_zp, alpha)

    def fwd(xq, wq, q_x, q_w, x_zp, alpha):
        return fwd_math(xq, wq, q_x, q_w, x_zp, alpha), (xq, wq, q_x, q_w)

    def bwd(res, g):
        xq, wq, q_x, q_w = res
        gf = g.astype(jnp.float32)
        dx = jnp.einsum(dx_spec, gf, wq.astype(jnp.float32),
                        preferred_element_type=jnp.float32).astype(xq.dtype)
        dw = jnp.einsum(dw_spec, xq.astype(jnp.float32), gf,
                        preferred_element_type=jnp.float32).astype(wq.dtype)
        z = jnp.zeros((), jnp.float32)
        return dx, dw, float0_like(q_x), float0_like(q_w), z, z

    qmm.defvjp(fwd, bwd)
    return qmm


# ---------------------------------------------------------------------------
# The convolution site: same contract as qmatmul, for NHWC x HWIO convs.
# ---------------------------------------------------------------------------
_QCONV_CACHE = LruCache()

_CONV_DN = ("NHWC", "HWIO", "NHWC")


def _make_qconv(plan, fused: bool):
    """One custom_vjp per (ConvPlan, backend): forward is the
    accelerator-exact ``alpha * int32-contraction`` (simulated: an int32
    XLA conv with the zero point subtracted up front, so XLA's implicit
    zero padding IS the zero-point padding; fused: im2col onto the
    batched int8 MXU matmul kernel) — identical int32 accumulations,
    identical single fp32 epilogue multiply, bit-equal outputs.

    Backward is shared by both backends and expressed in the LOWERED
    (im2col) space: after lowering, the conv site *is* the batched matmul
    site ``[G,M,K] x [G,K,Fg]``, so its cotangents are the matmul
    cotangent dots plus the (deterministic, order-pinned) col2im scatter.
    ``lax.conv`` transposes are deliberately avoided here: their CPU/XLA
    lowering is layout- and fusion-context sensitive, which re-associates
    the fp accumulation differently in the two backend programs and
    breaks full-step parameter parity at the ulp level.  Dot-generals +
    ``conv_unpatch`` pin the order."""
    conv_kw = dict(window_strides=plan.stride, padding=plan.pads,
                   rhs_dilation=plan.dilation, dimension_numbers=_CONV_DN,
                   feature_group_count=plan.groups)

    def fwd_math(xq, wq, q_x, q_w, x_zp, alpha):
        if fused:
            y, _, _ = _ops().int8_conv_fp(q_x, q_w, x_zp, alpha, plan=plan)
        else:
            zp = jnp.round(x_zp).astype(jnp.int32)
            rx = q_x.astype(jnp.int32) - zp
            acc = jax.lax.conv_general_dilated(
                rx, q_w.astype(jnp.int32),
                preferred_element_type=jnp.int32, **conv_kw)
            y = alpha * acc.astype(jnp.float32)
        return y

    @jax.custom_vjp
    def qcv(xq, wq, q_x, q_w, x_zp, alpha):
        return fwd_math(xq, wq, q_x, q_w, x_zp, alpha)

    def fwd(xq, wq, q_x, q_w, x_zp, alpha):
        return fwd_math(xq, wq, q_x, q_w, x_zp, alpha), (xq, wq, q_x, q_w)

    def bwd(res, g):
        # Both backends run this same lowered-space backward: the
        # cotangent dots in the im2col layout plus the order-pinned
        # col2im scatter (``ops.conv_unpatch``) — a deliberately
        # conv-free formulation, because ``lax.conv`` transposes compile
        # with context-dependent layouts/tilings and would re-associate
        # the fp accumulation differently in the two backend programs.
        xq, wq, q_x, q_w = res
        ops = _ops()
        gl = ops.conv_lower_output(g.astype(jnp.float32), plan)  # [G,M,Fg]
        xl = ops.conv_patches(xq.astype(jnp.float32), plan, 0.0)  # [G,M,K]
        wl = ops.conv_lower_weights(wq.astype(jnp.float32), plan)  # [G,K,Fg]
        dw = ops.conv_unlower_weights(
            jnp.einsum("gmk,gmn->gkn", xl, gl,
                       preferred_element_type=jnp.float32), plan)
        dx = ops.conv_unpatch(
            jnp.einsum("gmn,gkn->gmk", gl, wl,
                       preferred_element_type=jnp.float32), plan)
        z = jnp.zeros((), jnp.float32)
        return (dx.astype(xq.dtype), dw.astype(wq.dtype),
                float0_like(q_x), float0_like(q_w), z, z)

    qcv.defvjp(fwd, bwd)
    return qcv


def qconv(policy, xq: jax.Array, xqt: Optional[QTensor],
          wq: jax.Array, wqt: Optional[QTensor], *,
          stride=1, padding="SAME", dilation=1, groups: int = 1,
          out_dtype=None) -> jax.Array:
    """Quantized-site convolution (NHWC x HWIO -> NHWC).

    The conv analogue of :func:`qmatmul`: with int8 images for both
    operands the contraction runs integer-exact on either backend (the
    fused backend im2col-lowers onto the batched int8 MXU matmul kernel —
    depthwise/grouped convs ride the kernel's batch dimension); without
    them it is the fp conv of the on-grid tensors.
    """
    out_dtype = out_dtype or xq.dtype
    if xqt is None or wqt is None or not int8_matmul_eligible(policy):
        sh, sw = (stride, stride) if isinstance(stride, int) else stride
        dh, dw = (dilation, dilation) if isinstance(dilation, int) \
            else dilation
        with jax.named_scope("qconv_fp"):
            return jax.lax.conv_general_dilated(
                xq, wq, (sh, sw), padding, rhs_dilation=(dh, dw),
                dimension_numbers=_CONV_DN, feature_group_count=groups,
                preferred_element_type=jnp.float32).astype(out_dtype)
    plan = _ops().plan_conv(xq.shape, wq.shape, stride, padding, dilation,
                            groups)
    fused = policy.backend == FUSED
    qcv = _QCONV_CACHE.get_or_build(
        (plan, fused), lambda: _make_qconv(plan, fused))
    alpha = (xqt.scale * wqt.scale).astype(jnp.float32)
    with jax.named_scope(f"qconv_int8_{policy.backend}"):
        y = qcv(xq, wq, xqt.q, wqt.q, xqt.zero_point, alpha)
    return y.astype(out_dtype)


def qmatmul(policy, espec: str, xq: jax.Array, xqt: Optional[QTensor],
            wq: jax.Array, wqt: Optional[QTensor],
            out_dtype=None) -> jax.Array:
    """Quantized-site contraction ``einsum(espec, xq, wq)``.

    With int8 images for both operands the contraction runs integer-exact
    (see module docstring); otherwise it is the fp einsum of the on-grid
    tensors — today's simulated semantics — on either backend.
    """
    out_dtype = out_dtype or xq.dtype
    if xqt is None or wqt is None or not int8_matmul_eligible(policy):
        with jax.named_scope("qmatmul_fp"):
            return jnp.einsum(
                espec, xq, wq,
                preferred_element_type=jnp.float32).astype(out_dtype)
    resolved = resolve_einsum_spec(espec, xq.ndim)
    fused = policy.backend == FUSED
    qmm = _QMATMUL_CACHE.get_or_build(
        (resolved, fused), lambda: _make_qmatmul(resolved, fused))
    alpha = (xqt.scale * wqt.scale).astype(jnp.float32)
    with jax.named_scope(f"qmatmul_int8_{policy.backend}"):
        y = qmm(xq, wq, xqt.q, wqt.q, xqt.zero_point, alpha)
    return y.astype(out_dtype)


# ---------------------------------------------------------------------------
# The attention core: QK^T -> online softmax -> PV as ONE backend-dispatched
# quant site (ROADMAP 3b).  Four hindsight ranges — q (act spec), k and v
# (symmetric int8), and the softmax PROBABILITIES — feed a flash-style
# int8 core; the probability statistics come back from the kernel's
# resident tiles, so the site performs zero standalone min/max reductions.
# Every other policy runs the fp core on the same block plan.
# ---------------------------------------------------------------------------
KV_SPEC = quant.QuantSpec(bits=8, symmetric=True, stochastic=False)
P_SPEC = quant.QuantSpec(bits=8, symmetric=False, stochastic=False)

_QATTN_CACHE = LruCache()


def _attn_mod():
    from repro.kernels import int8_attention
    return int8_attention


def qattention_eligible(policy) -> bool:
    """True iff the attention core can run as an int8 quant site.

    Requires STATIC activation ranges on an (at most) 8-bit grid: the
    probability range is consumed *mid-kernel*, before the tensor exists,
    so — unlike every other site — it has no dynamic first-batch fallback
    (its leaf is initialized a-priori to the softmax codomain [0, 1]).
    Every other policy runs the fp core (``attention``).
    """
    return bool(
        policy.enabled and policy.quantize_acts
        and policy.act_estimator.is_static
        and policy.act_spec.bits == 8
    )


def _pstats_vector(policy, stats6, p_lo, p_hi):
    """Pack the kernel's probability-site statistics partials reduction
    ``[mn, mx, clip, n, err, sig]`` as a stats vector of the policy's
    width.  Unlike ``site_stats`` (which estimates on a sample prefix),
    these counters are EXACT full-tensor values — the kernel already sees
    every element on its resident tiles."""
    mn, mx, clip, n, err, sig = (stats6[i] for i in range(6))
    base = pack_stats(mn, mx)
    if not policy.telemetry.enabled:
        return base
    util = (mx - mn) / jnp.maximum(p_hi - p_lo, 1e-12)
    tail = jnp.stack([clip, n, err, sig, util,
                      jnp.float32(0.0), jnp.float32(0.0)])
    return jnp.concatenate([base, tail])


def _make_qattention(sched, fused: bool):
    """One custom_vjp per (AttnSchedule, backend).

    Forward: the fused backend runs the Pallas flash kernel
    (``ops.int8_attention_fp``); the simulated backend runs the
    order-pinned reference that replays the identical block schedule and
    recurrence — bit-equal outputs, softmax residuals and statistics.
    Both reduce the per-(head, q block) statistics partials with the ONE
    shared ``reduce_pstats``.

    Backward is shared by both backends (the qconv precedent): a
    recompute-based flash backward over the same int8 QK^T contraction,
    fed bit-identical residuals, expressed in deterministic dot-generals —
    so full-step parameter parity holds across backends.
    """
    mod = _attn_mod()

    def full(q_q, k_q, v_q, regs, kvlen):
        if fused:
            out, ml, ps = _ops().int8_attention_fp(
                q_q, k_q, v_q, regs, kvlen, sched=sched)
        else:
            out, ml, ps = mod.attention_core_reference(
                q_q, k_q, v_q, regs, kvlen, sched=sched)
        stats6 = jnp.stack(mod.reduce_pstats(ps))
        return out, ml, stats6

    @jax.custom_vjp
    def qat(qh, kh, vh, q_q, k_q, v_q, regs, kvlen):
        out, _, stats6 = full(q_q, k_q, v_q, regs, kvlen)
        return out, stats6

    def fwd(qh, kh, vh, q_q, k_q, v_q, regs, kvlen):
        out, ml, stats6 = full(q_q, k_q, v_q, regs, kvlen)
        return ((out, stats6),
                (qh, kh, vh, q_q, k_q, v_q, regs, kvlen, out, ml))

    def bwd(res, cts):
        qh, kh, vh, q_q, k_q, v_q, regs, kvlen, out, ml = res
        g_out = cts[0].astype(jnp.float32)   # stats cotangent is ignored
        dq, dk, dv = mod.attention_core_backward(
            qh, kh, vh, q_q, k_q, v_q, regs, kvlen, out, ml, g_out,
            sched=sched)
        return (dq.astype(qh.dtype), dk.astype(kh.dtype),
                dv.astype(vh.dtype),
                float0_like(q_q), float0_like(k_q), float0_like(v_q),
                jnp.zeros_like(regs), float0_like(kvlen))

    qat.defvjp(fwd, bwd)
    return qat


def attention(policy, q: jax.Array, k: jax.Array, v: jax.Array,
              sites: dict, *, mode: str, window=None, prefix_len=None,
              kv_len=None, scale: float, step: jax.Array):
    """The attention core site: the one place that chooses the core.

    ``q [B, S, KV, G, hd]`` x ``k [B, Skv, KV, hd]``, ``v [B, Skv, KV,
    hdv]`` -> ``out [B, S, KV, G, hdv]``.  ``sites`` is the ``{"q"/"k"/
    "v"/"p": {"act": leaf}}`` core-site tree (see
    ``models.attention.init_attention_sites``); returns ``(out, stats)``
    with a stats tree of the same structure.

    The block plan is resolved ONCE here (``kernels.tuning``, env
    ``REPRO_ATTN_BLOCK``) and both cores walk it.  A policy with static
    8-bit activation ranges (``qattention_eligible``) runs the int8 core:
    int8 QK^T / online fp32 softmax / int8 PV with in-hindsight ranges for
    q, k, v and the probabilities, on the backend's kernel or its replay;
    tile choice changes speed, never results.  So is which probability
    statistics it computes: only the (min, max) the range update reads,
    unless the policy's telemetry reads the clip/SQNR counters too.  Any
    other policy runs the fp core on the on-grid q/k/v and marks every
    core site "not visited", so its state passes through the estimator
    update unchanged.
    """
    b, s, kvh, g, hd = q.shape
    skv, hdv = k.shape[1], v.shape[-1]
    mod = _attn_mod()
    from repro.kernels import tuning as _tuning
    bq, bkv = _tuning.attention_block(s, skv, hd)
    sched = mod.make_schedule(
        sq=s, skv=skv, hd=hd, bq=bq, bkv=bkv, groups=g, mode=mode,
        window=int(window or 0), prefix_len=int(prefix_len or 0),
        sm_scale=float(scale), hdv=hdv,
        stats="full" if policy.telemetry.enabled else "range")

    # Head-major flatten (exact: transposes/reshapes move values, not
    # bits): q -> [B*KV*G, S, hd], k/v -> [B*KV, Skv, hd].  The outer AD
    # differentiates through these, so the cores only see the flattened
    # layout.
    def qflat(t):
        return jnp.transpose(t, (0, 2, 3, 1, 4)).reshape(b * kvh * g, s, hd)

    def kvflat(t):
        return jnp.transpose(t, (0, 2, 1, 3)).reshape(
            b * kvh, skv, t.shape[-1])

    def unflat(out3):
        return jnp.transpose(out3.reshape(b, kvh, g, s, hdv),
                             (0, 3, 1, 2, 4)).astype(q.dtype)

    def kvlen():
        if kv_len is None:
            return jnp.full((1, 1), skv, jnp.int32)
        return jnp.asarray(kv_len, jnp.int32).reshape(1, 1)

    if not qattention_eligible(policy):
        with jax.named_scope("attn_fp"):
            out = unflat(mod.attention_core_fp(
                qflat(q), kvflat(k), kvflat(v), kvlen(), sched=sched))
        return out, jax.tree_util.tree_map(lambda _: stats_zeros(policy),
                                           sites)

    cfg = policy.act_estimator
    with jax.named_scope(f"qattn_int8_{policy.backend}"):
        qh, q_st, q_qt = site_quantize(policy, q, sites["q"]["act"], step,
                                       name="attn_q")
        kh, k_st, k_qt = site_quantize(policy, k, sites["k"]["act"], step,
                                       cfg=cfg, spec=KV_SPEC, name="attn_k")
        vh, v_st, v_qt = site_quantize(policy, v, sites["v"]["act"], step,
                                       cfg=cfg, spec=KV_SPEC, name="attn_v")
        p_leaf = sites["p"]["act"]
        p_lo, p_hi = estimators.static_ranges(cfg, p_leaf)
        p_lo = jax.lax.stop_gradient(p_lo.astype(jnp.float32))
        p_hi = jax.lax.stop_gradient(p_hi.astype(jnp.float32))
        scale_p, zp_p = quant.scale_zero_point(p_lo, p_hi, P_SPEC)

        # The pre-computed quant registers (the accelerator's "programmed
        # before the tensor exists" form): softmax scale and q/k scales
        # fold into ONE fp32 multiplier per contraction.
        alpha_qk = (jnp.float32(scale) * q_qt.scale * k_qt.scale)
        alpha_pv = (scale_p * v_qt.scale)
        regs = jnp.stack([
            q_qt.zero_point, alpha_qk, scale_p, zp_p, alpha_pv,
            p_lo, p_hi, jnp.float32(0.0),
        ]).astype(jnp.float32).reshape(1, 8)
        kvl = kvlen()

        fused = policy.backend == FUSED
        qat = _QATTN_CACHE.get_or_build(
            (sched, fused), lambda: _make_qattention(sched, fused))
        out3, stats6 = qat(qflat(qh), kvflat(kh), kvflat(vh),
                           qflat(q_qt.q), kvflat(k_qt.q), kvflat(v_qt.q),
                           jax.lax.stop_gradient(regs), kvl)
        out = unflat(out3)
        p_st = _pstats_vector(policy, stats6, p_lo, p_hi)
        sg = jax.lax.stop_gradient
        stats = {"q": {"act": sg(q_st)}, "k": {"act": sg(k_st)},
                 "v": {"act": sg(v_st)}, "p": {"act": sg(p_st)}}
        return out, stats


# ---------------------------------------------------------------------------
# The grouped contraction of an expert layer: the rows routed to each held
# expert, sorted by expert, times that expert's weights (see
# ``kernels/int8_grouped_matmul.py`` for the row layout and its tables).
# ---------------------------------------------------------------------------
_QGMM_CACHE = LruCache()

_TGMM_DIMS = jax.lax.RaggedDotDimensionNumbers(
    dot_dimension_numbers=(((0,), (0,)), ((), ())),
    lhs_ragged_dimensions=[0], rhs_group_dimensions=[])


def _gmm_mod():
    from repro.kernels import int8_grouped_matmul
    return int8_grouped_matmul


def _make_qgmm(fused: bool):
    """One custom_vjp per backend.  Forward: the ``alpha * int32`` grouped
    contraction, on the Pallas kernel (fused) or its jnp reference
    (simulated), bit-equal.  Backward, shared: fp32 ragged contractions of
    the on-grid operands, ``dX = g W_e^T`` and ``dW_e = X_e^T g`` over each
    group's rows (padding and dead rows carry no cotangent)."""
    mod = _gmm_mod()

    def fwd_math(q_x, q_w, x_zp, alpha, tiles):
        if fused:
            return _ops().int8_gmm_fp(q_x, q_w, x_zp, alpha, tiles)
        return mod.grouped_matmul_reference(q_x, q_w, x_zp, alpha, tiles)

    @jax.custom_vjp
    def qg(xq, wq, q_x, q_w, x_zp, alpha, tiles):
        return fwd_math(q_x, q_w, x_zp, alpha, tiles)

    def fwd(xq, wq, q_x, q_w, x_zp, alpha, tiles):
        return fwd_math(q_x, q_w, x_zp, alpha, tiles), (xq, wq, q_x, q_w,
                                                         tiles)

    def bwd(res, g):
        xq, wq, q_x, q_w, tiles = res
        gf = jnp.where(mod.row_valid(tiles)[:, None], g.astype(jnp.float32),
                       0.0)
        dx = jax.lax.ragged_dot(
            gf, jnp.swapaxes(wq.astype(jnp.float32), 1, 2), tiles.group_rows,
            preferred_element_type=jnp.float32)
        dw = jax.lax.ragged_dot_general(
            xq.astype(jnp.float32), gf, tiles.group_rows, _TGMM_DIMS,
            preferred_element_type=jnp.float32)
        z = jnp.zeros((), jnp.float32)
        return (dx.astype(xq.dtype), dw.astype(wq.dtype), float0_like(q_x),
                float0_like(q_w), z, z,
                jax.tree_util.tree_map(float0_like, tiles))

    qg.defvjp(fwd, bwd)
    return qg


def qgmm(policy, xq: jax.Array, xqt: Optional[QTensor], wq: jax.Array,
         wqt: Optional[QTensor], tiles, out_dtype=None) -> jax.Array:
    """Quantized-site grouped contraction: row ``r`` of ``xq [R, K]`` times
    ``wq[group(r)]`` of ``wq [G, K, N]``, rows laid out as ``tiles``
    (``kernels.int8_grouped_matmul.GmmTiles``) say.

    With int8 images for both operands it runs integer-exact on either
    backend (the fused backend on the grouped MXU kernel); otherwise it is
    the fp ragged contraction of the on-grid tensors.
    """
    out_dtype = out_dtype or xq.dtype
    if xqt is None or wqt is None or not int8_matmul_eligible(policy):
        with jax.named_scope("qgmm_fp"):
            y = jax.lax.ragged_dot(xq, wq, tiles.group_rows,
                                   preferred_element_type=jnp.float32)
            valid = _gmm_mod().row_valid(tiles)[:, None]
            return jnp.where(valid, y, 0.0).astype(out_dtype)
    fused = policy.backend == FUSED
    qg = _QGMM_CACHE.get_or_build(fused, lambda: _make_qgmm(fused))
    alpha = (xqt.scale * wqt.scale).astype(jnp.float32)
    with jax.named_scope(f"qgmm_int8_{policy.backend}"):
        y = qg(xq, wq, xqt.q, wqt.q, xqt.zero_point, alpha, tiles)
    return y.astype(out_dtype)
