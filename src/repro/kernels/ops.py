"""Public, jit-friendly wrappers around the Pallas kernels.

Handles shape plumbing (arbitrary rank -> 2-D tiles -> back), the
int8-storage convention (asymmetric [0, 255] grids are stored shifted by
-128 so all storage/compute stays int8) and the partial-statistics
reduction.  Off the TPU the kernels run in Pallas interpret mode (see
``repro.kernels.platform.interpret_mode``), which is how the CPU test
suite checks them against the ``ref.py`` oracles.

All wrappers return *core-convention* integers (uint8 asymmetric / int8
symmetric) so results are directly comparable with
``repro.core.quant.quantize`` and ``repro.kernels.ref``.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.core.quant import QuantSpec, scale_zero_point

from . import stats_values, tuning
from .fused_quantize import DEFAULT_BLOCK, fused_quantize_kernel
from .int8_attention import AttnSchedule, attention_kernel
from .int8_matmul import int8_matmul_fp_kernel, int8_matmul_fused_kernel
from .stochastic_quantize import stochastic_quantize_kernel


def _qparams(qmin, qmax, spec: QuantSpec) -> jax.Array:
    """Pre-compute the (scale, zero_point) quantization registers exactly as
    the core quantizer does — the kernels consume these as operands, the way
    a fixed-point accelerator consumes pre-programmed quant registers."""
    scale, zp = scale_zero_point(
        jnp.asarray(qmin, jnp.float32), jnp.asarray(qmax, jnp.float32), spec
    )
    return jnp.stack([scale, zp]).reshape(1, 2)


def _as_2d(x: jax.Array) -> tuple[jax.Array, tuple]:
    shape = x.shape
    if x.ndim == 0:
        return x.reshape(1, 1), shape
    if x.ndim == 1:
        return x.reshape(1, -1), shape
    return x.reshape(-1, shape[-1]), shape


def _unshift(q_i8: jax.Array, spec: QuantSpec) -> jax.Array:
    if spec.symmetric:
        return q_i8
    return (q_i8.astype(jnp.int16) + 128).astype(jnp.uint8)


def _reduce_partials(partials: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Site (min, max) from ``[..., 8, 128]`` per-tile statistics tiles."""
    mn_mx = stats_values(partials, 2)
    return jnp.min(mn_mx[..., 0]), jnp.max(mn_mx[..., 1])


@functools.partial(jax.jit, static_argnames=("spec", "block"))
def fused_quantize(
    x: jax.Array,
    qmin: jax.Array,
    qmax: jax.Array,
    *,
    spec: QuantSpec = QuantSpec(bits=8, symmetric=False),
    block=DEFAULT_BLOCK,
):
    """Single-pass static quantize + stats.  Returns ``(q, obs_min, obs_max)``.

    ``q`` is on the in-hindsight grid ``[qmin, qmax]``; the stats are the
    FP min/max of ``x`` for the next-step range update.
    """
    # named_scope so device profiles / HLO dumps show the kernel call as a
    # named quant site rather than an anonymous pallas_call.
    with jax.named_scope("k_fused_quantize"):
        x2, shape = _as_2d(x)
        q, partials = fused_quantize_kernel(
            x2, _qparams(qmin, qmax, spec), spec=spec, block=block)
        mn, mx = _reduce_partials(partials)
        return _unshift(q, spec).reshape(shape), mn, mx


@functools.partial(jax.jit, static_argnames=("spec", "block", "on_chip_prng"))
def stochastic_quantize(
    x: jax.Array,
    qmin: jax.Array,
    qmax: jax.Array,
    noise: Optional[jax.Array],
    *,
    spec: QuantSpec = QuantSpec(bits=8, symmetric=False, stochastic=True),
    block=DEFAULT_BLOCK,
    on_chip_prng: bool = False,
    seed=None,
):
    """Gradient path: stochastic rounding onto a static in-hindsight grid.

    ``on_chip_prng=True`` (real TPU only — rejected in interpret mode)
    draws the rounding noise from the on-chip ``pltpu.prng_random_bits``
    seeded by ``seed`` instead of reading the ``noise`` operand from HBM;
    pass ``noise=None`` in that mode.
    """
    with jax.named_scope("k_stochastic_quantize"):
        x2, shape = _as_2d(x)
        if on_chip_prng:
            q, partials = stochastic_quantize_kernel(
                x2, _qparams(qmin, qmax, spec), None, spec=spec, block=block,
                on_chip_prng=True, seed=seed,
            )
        else:
            n2, _ = _as_2d(noise)
            q, partials = stochastic_quantize_kernel(
                x2, _qparams(qmin, qmax, spec), n2, spec=spec, block=block,
            )
        mn, mx = _reduce_partials(partials)
        return _unshift(q, spec).reshape(shape), mn, mx


@functools.partial(
    jax.jit, static_argnames=("out_spec", "block", "has_bias")
)
def _int8_matmul_fused(
    x_q: jax.Array,
    w_q: jax.Array,
    x_scale: jax.Array,
    x_zp: jax.Array,
    w_scale: jax.Array,
    bias: jax.Array,
    out_qmin: jax.Array,
    out_qmax: jax.Array,
    *,
    out_spec: QuantSpec,
    block,
    has_bias: bool,
):
    m, k = x_q.shape
    _, n = w_q.shape
    with jax.named_scope("k_int8_matmul_fused"):
        # Shift asymmetric activations onto the MXU-native signed grid.
        xs = (x_q.astype(jnp.int16) - 128).astype(jnp.int8)
        alpha = (x_scale * w_scale).astype(jnp.float32).reshape(1, 1)
        # Integer epilogue correction: zero-point term + int32-requantized
        # bias (bias is added at the accumulator in the alpha grid — the
        # fixed-point-accelerator convention; keeps the whole correction
        # exact in int32).
        colsum = jnp.sum(w_q.astype(jnp.int32), axis=0, keepdims=True)
        corr = jnp.round(128.0 - x_zp).astype(jnp.int32) * colsum
        if has_bias:
            corr = corr + jnp.round(
                bias.astype(jnp.float32).reshape(1, n) / alpha
            ).astype(jnp.int32)
        q, partials = int8_matmul_fused_kernel(
            xs, w_q, alpha, corr, _qparams(out_qmin, out_qmax, out_spec),
            out_spec=out_spec, block=block,
        )
        mn, mx = _reduce_partials(partials)
        return _unshift(q, out_spec), mn, mx


# ---------------------------------------------------------------------------
# Einsum plumbing: map an arbitrary quantized-site einsum onto the batched
# 3-D [B, M, K] x [B, K, N] layout the matmul kernels execute.
# ---------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class EinsumPlan:
    """How to run ``einsum(spec, x, w)`` on the 3-D matmul kernel.

    Every quantized site in this repo contracts an activation against a
    weight, with at most one *shared batch* group (MoE experts: labels in
    x, w AND y).  The plan records the label split and the permutations
    that take x to ``[batch, x_free, contract]``, w to ``[batch, contract,
    w_free]`` and the kernel's ``[batch, x_free, w_free]`` result back to
    the einsum output order.  Hashable -> usable as a static jit arg.
    """

    spec: str               # ellipsis-resolved "x,w->y"
    x_perm: tuple           # x transpose -> (batch..., x_free..., contract...)
    w_perm: tuple           # w transpose -> (batch..., contract..., w_free...)
    y_perm: tuple           # [batch..., x_free..., w_free...] -> y label order
    n_batch: int
    n_x_free: int
    n_contract: int
    n_w_free: int


@functools.lru_cache(maxsize=256)
def plan_einsum(spec: str, x_ndim: int, w_ndim: int) -> EinsumPlan:
    """Parse a two-operand einsum into an :class:`EinsumPlan`.

    Supported: no repeated labels inside one operand, every contraction
    label shared by x and w, batch labels (in x, w and y) allowed.  An
    ``...`` in the x operand / output expands to the leading x dims
    (via the shared ``repro.core.backend.resolve_einsum_spec``).
    """
    from repro.core.backend import resolve_einsum_spec
    lhs, y = resolve_einsum_spec(spec, x_ndim).split("->")
    xs, ws = lhs.split(",")
    if "..." in ws or "..." in y:
        raise ValueError(f"unsupported ellipsis placement in {spec!r}")
    if len(set(xs)) != len(xs) or len(set(ws)) != len(ws):
        raise ValueError(f"repeated labels unsupported: {spec!r}")
    if len(xs) != x_ndim or len(ws) != w_ndim:
        raise ValueError(f"{spec!r} does not match ranks ({x_ndim}, {w_ndim})")

    batch = [c for c in xs if c in ws and c in y]
    contract = [c for c in xs if c in ws and c not in y]
    x_free = [c for c in xs if c not in ws]
    w_free = [c for c in ws if c not in xs]
    if sorted(y) != sorted(batch + x_free + w_free):
        raise ValueError(f"output labels of {spec!r} not derivable")

    x_order = batch + x_free + contract
    w_order = batch + contract + w_free
    kernel_y = batch + x_free + w_free
    return EinsumPlan(
        spec=f"{xs},{ws}->{y}",
        x_perm=tuple(xs.index(c) for c in x_order),
        w_perm=tuple(ws.index(c) for c in w_order),
        y_perm=tuple(kernel_y.index(c) for c in y),
        n_batch=len(batch),
        n_x_free=len(x_free),
        n_contract=len(contract),
        n_w_free=len(w_free),
    )


def _prod(dims) -> int:
    out = 1
    for d in dims:
        out *= int(d)
    return out


def _int8_fp_batched(x3, w3, x_zp, alpha, block):
    """Shared int8 epilogue for the batched fp-out MXU kernel: shift the
    asymmetric uint8 activations onto the signed grid, fold the
    zero-point correction into the integer ``corr`` operand, run the
    kernel, reduce the stats partials.  ``x3`` is uint8 ``[B, M, K]``,
    ``w3`` int8 ``[B, K, N]``.  This arithmetic is the bit-parity
    contract shared with the Pallas kernel and the ``ref`` oracles —
    single source of truth for the matmul AND conv entry points."""
    xs = (x3.astype(jnp.int16) - 128).astype(jnp.int8)
    alpha2 = jnp.asarray(alpha, jnp.float32).reshape(1, 1)
    colsum = jnp.sum(w3.astype(jnp.int32), axis=1, keepdims=True)
    corr = jnp.round(128.0 - jnp.asarray(x_zp, jnp.float32)
                     ).astype(jnp.int32) * colsum
    y3, partials = int8_matmul_fp_kernel(xs, w3, alpha2, corr,
                                         block=tuple(block))
    mn, mx = _reduce_partials(partials)
    return y3, mn, mx


def _einsum_dims(plan: EinsumPlan, x_shape, w_shape):
    """(b, m, k, n) kernel extents for ``einsum(plan.spec, x, w)`` without
    materializing the transposes — used to resolve the tuned block size
    OUTSIDE the jit boundary (env overrides must be read eagerly)."""
    nb, nxf, nc = plan.n_batch, plan.n_x_free, plan.n_contract
    xt = [x_shape[i] for i in plan.x_perm]
    wt = [w_shape[i] for i in plan.w_perm]
    return (_prod(xt[:nb]), _prod(xt[nb:nb + nxf]),
            _prod(xt[nb + nxf:]), _prod(wt[nb + nc:]))


def int8_matmul_fp(
    x_q: jax.Array,          # uint8, asymmetric [0, 255] grid
    w_q: jax.Array,          # int8, symmetric
    x_zp: jax.Array,
    alpha: jax.Array,        # s_x * s_w
    *,
    plan: EinsumPlan,
    block=None,
):
    """Quantized-site einsum on the int8 MXU path with an fp32 result.

    Computes ``alpha * einsum(plan.spec, x_q - zp_x, w_q)`` with the
    contraction exact in int32 (the zero-point correction folded into the
    integer ``corr`` operand, accelerator-style), plus the fused min/max
    statistics of the fp accumulator output.  Returns ``(y fp32 in einsum
    output layout, obs_min, obs_max)``.

    ``block=None`` resolves the tile through :mod:`repro.kernels.tuning`
    (``REPRO_MM_BLOCK`` / ``REPRO_TUNE`` aware).  Resolution happens in
    this eager wrapper, before the jitted inner function, so an env
    override is honoured even when an identically-shaped call was already
    traced with a different tile.
    """
    if block is None:
        _, m, k, n = _einsum_dims(plan, x_q.shape, w_q.shape)
        block = tuning.matmul_block(m, n, k, dtype=str(x_q.dtype))
    return _int8_matmul_fp_jit(x_q, w_q, x_zp, alpha, plan=plan,
                               block=tuple(block))


@functools.partial(jax.jit, static_argnames=("plan", "block"))
def _int8_matmul_fp_jit(
    x_q: jax.Array,
    w_q: jax.Array,
    x_zp: jax.Array,
    alpha: jax.Array,
    *,
    plan: EinsumPlan,
    block,
):
    with jax.named_scope("k_int8_matmul_fp"):
        nb, nxf, nc, nwf = (plan.n_batch, plan.n_x_free, plan.n_contract,
                            plan.n_w_free)
        xt = jnp.transpose(x_q, plan.x_perm)
        wt = jnp.transpose(w_q, plan.w_perm)
        bdims = xt.shape[:nb]
        mdims = xt.shape[nb:nb + nxf]
        kdims = xt.shape[nb + nxf:]
        ndims = wt.shape[nb + nc:]
        b, m, k, n = _prod(bdims), _prod(mdims), _prod(kdims), _prod(ndims)

        y3, mn, mx = _int8_fp_batched(xt.reshape(b, m, k),
                                      wt.reshape(b, k, n),
                                      x_zp, alpha, block)
        y = jnp.transpose(y3.reshape(bdims + mdims + ndims), plan.y_perm)
        return y, mn, mx


# ---------------------------------------------------------------------------
# Convolution plumbing: lower an NHWC x HWIO conv onto the batched 3-D
# [B, M, K] x [B, K, N] matmul kernel (B carries the groups; depthwise is
# the G == C_in, K == KH*KW, N == multiplier corner of the same form).
# ---------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class ConvPlan:
    """How to run an NHWC x HWIO conv on the 3-D matmul kernel.

    The conv analogue of :class:`EinsumPlan`: a hashable (static-arg)
    record of the geometry — batch/spatial/channel extents, stride,
    kernel dilation, resolved padding pairs and group split — plus the
    derived output extents.  ``conv_patches`` uses it to im2col the
    activation image into ``[G, N*OH*OW, KH*KW*Cg]`` and
    ``conv_lower_weights`` to fold the HWIO kernel into ``[G, KH*KW*Cg,
    Fg]``; the contraction is then exactly the batched matmul the MXU
    kernel executes.
    """

    n: int                   # batch
    h: int                   # input height
    w: int                   # input width
    cin: int                 # input channels (total, all groups)
    kh: int                  # kernel height
    kw: int                  # kernel width
    cout: int                # output channels (total, all groups)
    groups: int              # feature_group_count
    stride: tuple            # (sh, sw)
    dilation: tuple          # (dh, dw) — kernel (rhs/atrous) dilation
    pads: tuple              # ((ph0, ph1), (pw0, pw1)) resolved padding
    oh: int                  # output height
    ow: int                  # output width

    @property
    def cin_g(self) -> int:
        return self.cin // self.groups

    @property
    def cout_g(self) -> int:
        return self.cout // self.groups

    @property
    def m(self) -> int:
        return self.n * self.oh * self.ow

    @property
    def k(self) -> int:
        return self.kh * self.kw * self.cin_g


def _pair(v) -> tuple:
    return tuple(v) if isinstance(v, (tuple, list)) else (int(v), int(v))


@functools.lru_cache(maxsize=256)
def _plan_conv_cached(x_shape, w_shape, stride, padding, dilation,
                      groups) -> ConvPlan:
    n, h, w, cin = x_shape
    kh, kw, cin_g, cout = w_shape
    if cin_g * groups != cin or cout % groups:
        raise ValueError(
            f"conv geometry mismatch: x channels {cin}, kernel input "
            f"channels {cin_g} x groups {groups}, out channels {cout}")
    sh, sw = stride
    dh, dw = dilation
    eff = ((kh - 1) * dh + 1, (kw - 1) * dw + 1)   # dilated kernel extent
    if isinstance(padding, str):
        pads = jax.lax.padtype_to_pads((h, w), eff, (sh, sw), padding)
        pads = tuple((int(lo), int(hi)) for lo, hi in pads)
    else:
        pads = tuple((int(lo), int(hi)) for lo, hi in padding)
    oh = (h + pads[0][0] + pads[0][1] - eff[0]) // sh + 1
    ow = (w + pads[1][0] + pads[1][1] - eff[1]) // sw + 1
    if oh <= 0 or ow <= 0:
        raise ValueError(f"empty conv output ({oh}, {ow}) for input "
                         f"{x_shape} kernel {w_shape} pads {pads}")
    return ConvPlan(n=n, h=h, w=w, cin=cin, kh=kh, kw=kw, cout=cout,
                    groups=groups, stride=(sh, sw), dilation=(dh, dw),
                    pads=pads, oh=oh, ow=ow)


def plan_conv(x_shape, w_shape, stride=1, padding="SAME", dilation=1,
              groups: int = 1) -> ConvPlan:
    """Resolve an NHWC x HWIO conv into a :class:`ConvPlan`.

    ``padding`` is ``"SAME"`` / ``"VALID"`` (resolved with XLA's rules,
    via ``lax.padtype_to_pads`` on the dilated kernel extent, so the
    lowered conv matches ``lax.conv_general_dilated`` exactly) or an
    explicit ``((ph0, ph1), (pw0, pw1))``.
    """
    return _plan_conv_cached(tuple(map(int, x_shape)),
                             tuple(map(int, w_shape)),
                             _pair(stride), padding if isinstance(padding, str)
                             else tuple((int(a), int(b)) for a, b in padding),
                             _pair(dilation), int(groups))


def conv_patches(x: jax.Array, plan: ConvPlan, pad_value) -> jax.Array:
    """im2col: NHWC image -> ``[G, N*OH*OW, KH*KW*Cg]`` patch matrix.

    Dtype-generic (runs on the uint8 integer image as well as fp), which
    is what lets the int8 conv pad in *integer* space: padding with the
    activation zero point makes every padded tap contribute exactly
    ``(zp - zp) * w == 0`` after the kernel's zero-point correction —
    bit-identical to fp zero padding.  K is laid out ``(kh, kw, cg)`` to
    match :func:`conv_lower_weights`.
    """
    (sh, sw), (dh, dw) = plan.stride, plan.dilation
    xp = jnp.pad(x, ((0, 0), plan.pads[0], plan.pads[1], (0, 0)),
                 constant_values=pad_value)
    taps = []
    for i in range(plan.kh):
        for j in range(plan.kw):
            r0, c0 = i * dh, j * dw
            taps.append(jax.lax.slice(
                xp,
                (0, r0, c0, 0),
                (plan.n, r0 + (plan.oh - 1) * sh + 1,
                 c0 + (plan.ow - 1) * sw + 1, plan.cin),
                (1, sh, sw, 1)))                     # [N, OH, OW, C]
    p = jnp.stack(taps, axis=3)                      # [N, OH, OW, KHKW, C]
    p = p.reshape(plan.n, plan.oh, plan.ow, plan.kh * plan.kw,
                  plan.groups, plan.cin_g)
    p = jnp.transpose(p, (4, 0, 1, 2, 3, 5))         # [G, N, OH, OW, KHKW, Cg]
    return p.reshape(plan.groups, plan.m, plan.k)


def conv_lower_weights(w: jax.Array, plan: ConvPlan) -> jax.Array:
    """HWIO kernel -> ``[G, KH*KW*Cg, Fg]`` (XLA group convention: output
    feature ``f`` belongs to group ``f // Fg``)."""
    wk = w.reshape(plan.kh * plan.kw * plan.cin_g, plan.groups, plan.cout_g)
    return jnp.transpose(wk, (1, 0, 2))


def conv_unlower_output(y3: jax.Array, plan: ConvPlan) -> jax.Array:
    """Kernel output ``[G, N*OH*OW, Fg]`` -> NHWC ``[N, OH, OW, G*Fg]``."""
    y = y3.reshape(plan.groups, plan.n, plan.oh, plan.ow, plan.cout_g)
    return jnp.transpose(y, (1, 2, 3, 0, 4)).reshape(
        plan.n, plan.oh, plan.ow, plan.cout)


def conv_lower_output(y: jax.Array, plan: ConvPlan) -> jax.Array:
    """NHWC ``[N, OH, OW, F]`` -> ``[G, N*OH*OW, Fg]`` (inverse of
    :func:`conv_unlower_output`; used for output cotangents)."""
    y = y.reshape(plan.n, plan.oh, plan.ow, plan.groups, plan.cout_g)
    return jnp.transpose(y, (3, 0, 1, 2, 4)).reshape(
        plan.groups, plan.m, plan.cout_g)


def conv_unlower_weights(wl: jax.Array, plan: ConvPlan) -> jax.Array:
    """``[G, KH*KW*Cg, Fg]`` -> HWIO (inverse of
    :func:`conv_lower_weights`; used for weight cotangents)."""
    return jnp.transpose(wl, (1, 0, 2)).reshape(
        plan.kh, plan.kw, plan.cin_g, plan.cout)


def conv_unpatch(dp: jax.Array, plan: ConvPlan) -> jax.Array:
    """col2im: the linear transpose of :func:`conv_patches` (zero pad).

    Scatter-adds each kernel tap's cotangent slab back onto the padded
    image and crops the padding.  Taps accumulate in a fixed (python
    loop) order and each tap is a disjoint strided add, so the fp
    accumulation order is pinned — the conv backward stays bit-identical
    across backends/compilations, which ``lax.conv`` transposes are not
    (their CPU lowering is layout/fusion sensitive).
    """
    (sh, sw), (dh, dw) = plan.stride, plan.dilation
    (ph0, _), (pw0, _) = plan.pads
    dp = dp.reshape(plan.groups, plan.n, plan.oh, plan.ow,
                    plan.kh * plan.kw, plan.cin_g)
    dp = jnp.transpose(dp, (1, 2, 3, 4, 0, 5)).reshape(
        plan.n, plan.oh, plan.ow, plan.kh * plan.kw, plan.cin)
    hp = plan.h + plan.pads[0][0] + plan.pads[0][1]
    wp = plan.w + plan.pads[1][0] + plan.pads[1][1]
    xp = jnp.zeros((plan.n, hp, wp, plan.cin), dp.dtype)
    for i in range(plan.kh):
        for j in range(plan.kw):
            r0, c0 = i * dh, j * dw
            xp = xp.at[:, r0:r0 + (plan.oh - 1) * sh + 1:sh,
                       c0:c0 + (plan.ow - 1) * sw + 1:sw, :].add(
                dp[..., i * plan.kw + j, :])
    return xp[:, ph0:ph0 + plan.h, pw0:pw0 + plan.w, :]


def int8_conv_fp(
    x_q: jax.Array,          # uint8 NHWC, asymmetric [0, 255] grid
    w_q: jax.Array,          # int8 HWIO, symmetric
    x_zp: jax.Array,
    alpha: jax.Array,        # s_x * s_w
    *,
    plan: ConvPlan,
    block=None,
):
    """Eager tile-resolving wrapper — see :func:`int8_matmul_fp` for why
    tuning happens outside jit.  The lowered conv is the [G, M, K] x
    [G, K, Fg] batched matmul, so it shares the matmul tile table."""
    if block is None:
        block = tuning.matmul_block(plan.m, plan.cout_g, plan.k,
                                    dtype=str(x_q.dtype))
    return _int8_conv_fp_jit(x_q, w_q, x_zp, alpha, plan=plan,
                             block=tuple(block))


@functools.partial(jax.jit, static_argnames=("plan", "block"))
def _int8_conv_fp_jit(
    x_q: jax.Array,
    w_q: jax.Array,
    x_zp: jax.Array,
    alpha: jax.Array,
    *,
    plan: ConvPlan,
    block,
):
    """Quantized conv on the int8 MXU path with an fp32 result.

    im2col-lowers the integer image (padding with the activation zero
    point, see :func:`conv_patches`) and the HWIO kernel onto the batched
    ``[G, M, K] x [G, K, Fg]`` layout of :func:`int8_matmul_fp_kernel`,
    with the zero-point correction folded into the integer ``corr``
    operand.  Contraction exact in int32, one fp32 multiply epilogue —
    the same arithmetic contract as :func:`int8_matmul_fp`.  Returns
    ``(y fp32 NHWC, obs_min, obs_max)`` where the stats are the fused
    min/max partials of the fp accumulator output.
    """
    with jax.named_scope("k_int8_conv_fp"):
        pad_q = jnp.round(jnp.asarray(x_zp, jnp.float32)).astype(x_q.dtype)
        patches = conv_patches(x_q, plan, pad_q)     # fp 0.0 == integer zp
        ws = conv_lower_weights(w_q, plan)
        y3, mn, mx = _int8_fp_batched(patches, ws, x_zp, alpha, block)
        return conv_unlower_output(y3, plan), mn, mx


@functools.partial(jax.jit, static_argnames=("sched",))
def int8_attention_fp(
    q_u8: jax.Array,         # uint8 [BH, sq, hd], asymmetric grid
    k_i8: jax.Array,         # int8  [ZB, skv, hd], symmetric
    v_i8: jax.Array,         # int8  [ZB, skv, hd], symmetric
    regs: jax.Array,         # fp32 [1, 8] quant registers (see int8_attention)
    kvlen: jax.Array,        # int32 [1, 1] runtime kv length bound
    *,
    sched: AttnSchedule,
):
    """Fused flash-style int8 attention core with in-kernel p-site stats.

    Returns ``(out fp32 [BH, sq, hd], ml fp32 [BH, sq, 2] final softmax
    (max, denom) residuals, pstats fp32 [BH, nq, 8, 128] per-(head, q
    block) probability statistics tiles)``.  The block plan is baked into
    ``sched`` at dispatch (resolved via :mod:`repro.kernels.tuning`), so
    both backends replay the identical schedule.
    """
    with jax.named_scope("k_attn_fwd"):
        return attention_kernel(q_u8, k_i8, v_i8, regs, kvlen, sched=sched)


def int8_matmul_fused(
    x_q: jax.Array,          # uint8 [M, K] on the asymmetric [0, 255] grid
    w_q: jax.Array,          # int8  [K, N] symmetric
    x_scale, x_zp, w_scale,
    bias: Optional[jax.Array],
    out_qmin, out_qmax,
    *,
    out_spec: QuantSpec = QuantSpec(bits=8, symmetric=False),
    block=(256, 256, 256),
):
    """Full paper layer data path: int8 GEMM + fused dequant/stats/requant.

    Matches ``ref.ref_int8_matmul_fused`` exactly (integer outputs bit-for-
    bit, stats to fp32 rounding).
    """
    has_bias = bias is not None
    if bias is None:
        bias = jnp.zeros((w_q.shape[1],), jnp.float32)
    return _int8_matmul_fused(
        x_q, w_q,
        jnp.asarray(x_scale, jnp.float32), jnp.asarray(x_zp, jnp.float32),
        jnp.asarray(w_scale, jnp.float32), bias,
        jnp.asarray(out_qmin, jnp.float32), jnp.asarray(out_qmax, jnp.float32),
        out_spec=out_spec, block=tuple(block), has_bias=has_bias,
    )
