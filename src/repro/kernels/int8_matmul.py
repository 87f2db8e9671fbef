"""Pallas TPU kernel: int8 x int8 -> int32 matmul with the paper's fused
epilogue (dequant -> bias -> ONLINE STATS -> static requantization).

This kernel is the whole of paper Fig. 2 + Fig. 3 as one TPU program:

    HBM reads : x int8 [M, K], w int8 [K, N], corr int32 [N]
    MXU       : int8 x int8 -> int32 accumulation over K tiles (VMEM scratch)
    epilogue  : acc += corr                       (zp corr + int32 bias, EXACT)
                y    = alpha * acc                (dequant, one fp32 rounding)
                stats <- (min y, max y)           (the "accumulator logic")
                q = round(y / s_out + zp_out)     (STATIC requant, range is
                                                   the in-hindsight estimate)
    HBM write : q int8 [M, N] + per-tile stats partials

The fp32 accumulator output never touches HBM — with a dynamic estimator
that is impossible, because the requant scale would depend on all of ``y``.

Layout conventions (see ``ops.py``):
  * activations are asymmetric uint8 [0,255] stored as int8 via a -128
    shift (MXU-native);  the zero-point correction term
    ``(128 - zp_x) * colsum(w)`` plus the int32-requantized bias
    ``round(bias / alpha)`` are folded into the integer ``corr`` operand —
    exactly how fixed-point accelerators add bias at the accumulator.
  * weights are symmetric int8.
  * keeping every epilogue correction in exact int32 leaves a single fp32
    multiply + a division + an add in the fp path, so no mul+add pair
    exists for XLA to contract into an FMA — the oracle and the kernel are
    bit-exact even across backends with different fusion choices.

Grid: (gm, gn, gk), K innermost ("arbitrary" — sequential accumulation
into a VMEM scratch tile); (i, j) are parallel.  Stats partials are one
``STATS_TILE`` per (i, j), so no cross-core races.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.quant import QuantSpec

from . import STATS_TILE, platform, stats_tile

DEFAULT_BLOCK = (256, 256, 256)  # (bm, bn, bk)


def _kernel(x_ref, w_ref, alpha_ref, corr_ref, outqp_ref,
            q_ref, stats_ref, acc_ref, *,
            out_spec: QuantSpec, m: int, n: int, kdim: int,
            bm: int, bn: int, bk: int, gk: int, out_shift: int):
    i = pl.program_id(0)
    j = pl.program_id(1)
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x = x_ref[...]
    if kdim % bk != 0:
        # K-edge block: out-of-bounds reads are unspecified (interpret mode
        # pads with a sentinel, hardware with whatever is resident), so the
        # ragged tail of the contraction axis must be masked to zero.  Rows
        # (M) / cols (N) raggedness needs no masking here: those lanes land
        # outside the output write window and outside the stats mask.
        kcol = jax.lax.broadcasted_iota(jnp.int32, (bm, bk), 1) + k * bk
        x = jnp.where(kcol < kdim, x, jnp.int8(0))

    # int8 x int8 -> int32 on the MXU (the chip's matmul takes no int32
    # operands); the accumulation is exact.
    acc_ref[...] += jax.lax.dot_general(
        x,
        w_ref[...],
        (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32,
    )

    @pl.when(k == gk - 1)
    def _epilogue():
        alpha = alpha_ref[0, 0]
        # Integer-exact epilogue correction, then ONE fp32 rounding.
        y = alpha * (acc_ref[...] + corr_ref[...]).astype(jnp.float32)

        rows = jax.lax.broadcasted_iota(jnp.int32, (bm, bn), 0) + i * bm
        cols = jax.lax.broadcasted_iota(jnp.int32, (bm, bn), 1) + j * bn
        valid = jnp.logical_and(rows < m, cols < n)
        big = jnp.float32(jnp.finfo(jnp.float32).max)
        stats_ref[0, 0] = stats_tile(jnp.min(jnp.where(valid, y, big)),
                                     jnp.max(jnp.where(valid, y, -big)))

        scale = outqp_ref[0, 0]  # pre-computed (scale, zp) requant registers
        zp = outqp_ref[0, 1]
        q = jnp.clip(jnp.round(y / scale + zp), out_spec.int_min, out_spec.int_max)
        q_ref[...] = (q - out_shift).astype(q_ref.dtype)


def _fp_kernel(x_ref, w_ref, alpha_ref, corr_ref,
               y_ref, stats_ref, acc_ref, *,
               m: int, n: int, kdim: int,
               bm: int, bn: int, bk: int, gk: int):
    i = pl.program_id(1)
    j = pl.program_id(2)
    k = pl.program_id(3)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x = x_ref[0]
    if kdim % bk != 0:
        # Ragged contraction tail: see the requant kernel above.
        kcol = jax.lax.broadcasted_iota(jnp.int32, (bm, bk), 1) + k * bk
        x = jnp.where(kcol < kdim, x, jnp.int8(0))

    acc_ref[...] += jax.lax.dot_general(
        x,
        w_ref[0],
        (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32,
    )

    @pl.when(k == gk - 1)
    def _epilogue():
        alpha = alpha_ref[0, 0]
        y = alpha * (acc_ref[...] + corr_ref[0]).astype(jnp.float32)

        rows = jax.lax.broadcasted_iota(jnp.int32, (bm, bn), 0) + i * bm
        cols = jax.lax.broadcasted_iota(jnp.int32, (bm, bn), 1) + j * bn
        valid = jnp.logical_and(rows < m, cols < n)
        big = jnp.float32(jnp.finfo(jnp.float32).max)
        stats_ref[0, 0, 0] = stats_tile(jnp.min(jnp.where(valid, y, big)),
                                        jnp.max(jnp.where(valid, y, -big)))
        y_ref[...] = y[None]


def int8_matmul_fp_kernel(
    x_q: jax.Array,       # int8 [B, M, K]  (asymmetric grid shifted by -128)
    w_q: jax.Array,       # int8 [B, K, N]  (symmetric)
    alpha: jax.Array,     # fp32 [1, 1]  = s_x * s_w
    corr: jax.Array,      # int32 [B, 1, N] = (128 - zp_x) * colsum(w)
    *,
    block=DEFAULT_BLOCK,
):
    """Variant of :func:`int8_matmul_fused_kernel` for matmul sites whose
    output feeds a *non-linear* consumer (norm, activation, attention core)
    rather than the next quantizer: same int8 x int8 -> int32 MXU data path
    and integer-exact epilogue correction, but the accumulator leaves in
    fp32 instead of being requantized in place.  HBM traffic per output
    element is ``4 B`` (fp32 write) vs the fake-quant path's fp read +
    fp write — still single-pass, and the stats partials (min/max of ``y``)
    come out for free exactly as in the requant variant.

    The extra leading dimension ``B`` batches per-slice weights (MoE
    experts); pass ``B == 1`` for plain 2-D matmuls.  Returns
    ``(y fp32 [B, M, N], partials fp32 [B, gm, gn, 8, 128])``.
    """
    b, m, k = x_q.shape
    b2, k2, n = w_q.shape
    assert (b, k) == (b2, k2), (x_q.shape, w_q.shape)
    bm, bn, bk = min(block[0], m), min(block[1], n), min(block[2], k)
    gm, gn, gk = pl.cdiv(m, bm), pl.cdiv(n, bn), pl.cdiv(k, bk)

    kernel = functools.partial(
        _fp_kernel, m=m, n=n, kdim=k, bm=bm, bn=bn, bk=bk, gk=gk,
    )
    return pl.pallas_call(
        kernel,
        grid=(b, gm, gn, gk),
        in_specs=[
            pl.BlockSpec((1, bm, bk), lambda b, i, j, k: (b, i, k)),
            pl.BlockSpec((1, bk, bn), lambda b, i, j, k: (b, k, j)),
            pl.BlockSpec((1, 1), lambda b, i, j, k: (0, 0)),
            pl.BlockSpec((1, 1, bn), lambda b, i, j, k: (b, 0, j)),
        ],
        out_specs=[
            pl.BlockSpec((1, bm, bn), lambda b, i, j, k: (b, i, j)),
            pl.BlockSpec((1, 1, 1) + STATS_TILE,
                         lambda b, i, j, k: (b, i, j, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, m, n), jnp.float32),
            jax.ShapeDtypeStruct((b, gm, gn) + STATS_TILE, jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary"),
        ),
        interpret=platform.interpret_mode(),
    )(x_q, w_q, alpha, corr)


def int8_matmul_fused_kernel(
    x_q: jax.Array,       # int8 [M, K]  (asymmetric grid shifted by -128)
    w_q: jax.Array,       # int8 [K, N]  (symmetric)
    alpha: jax.Array,     # fp32 [1, 1]  = s_x * s_w
    corr: jax.Array,      # int32 [1, N] = (128 - zp_x)*colsum(w) + round(bias/alpha)
    out_qparams: jax.Array,  # fp32 [1, 2] = [[scale, zp]] from the hindsight range
    *,
    out_spec: QuantSpec,
    block=DEFAULT_BLOCK,
):
    m, k = x_q.shape
    k2, n = w_q.shape
    assert k == k2, (x_q.shape, w_q.shape)
    bm, bn, bk = min(block[0], m), min(block[1], n), min(block[2], k)
    gm, gn, gk = pl.cdiv(m, bm), pl.cdiv(n, bn), pl.cdiv(k, bk)
    out_shift = 0 if out_spec.symmetric else 128

    kernel = functools.partial(
        _kernel, out_spec=out_spec, m=m, n=n, kdim=k, bm=bm, bn=bn, bk=bk,
        gk=gk, out_shift=out_shift,
    )
    return pl.pallas_call(
        kernel,
        grid=(gm, gn, gk),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((bk, bn), lambda i, j, k: (k, j)),
            pl.BlockSpec((1, 1), lambda i, j, k: (0, 0)),
            pl.BlockSpec((1, bn), lambda i, j, k: (0, j)),
            pl.BlockSpec((1, 2), lambda i, j, k: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
            pl.BlockSpec((1, 1) + STATS_TILE, lambda i, j, k: (i, j, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((m, n), jnp.int8),
            jax.ShapeDtypeStruct((gm, gn) + STATS_TILE, jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=platform.interpret_mode(),
    )(x_q, w_q, alpha, corr, out_qparams)
