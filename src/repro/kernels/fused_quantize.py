"""Pallas TPU kernel: single-pass static quantization + online statistics.

This is the paper's hardware insight mapped onto the TPU memory hierarchy.
With an *in-hindsight* (pre-computed) range, quantization is a pure
elementwise map, so each VMEM tile can be quantized and written to HBM in
int8 **once**, while the same tile — still resident in VMEM — is reduced to
its (min, max) for the next step's range update (paper eq. 2-3).  Dynamic
quantization cannot do this: the range is a function of the full tensor,
forcing the fp32 tensor out to HBM, a reduce, and a second read (paper
Fig. 4, eq. 5).

HBM traffic per element:  static  = read fp + write int8        (~5 B)
                          dynamic = read fp + write fp + read fp
                                    + write int8                (~13 B)

Grid: 2-D over (M, N) tiles.  Each grid cell writes its own (min, max)
partial as one ``STATS_TILE`` of a ``[gm, gn, 8, 128]`` buffer; the tiny
final reduction happens in the jit wrapper (``ops.fused_quantize``).  Per-tile partials keep every
grid dimension ``parallel`` (no cross-iteration carries), which is both
TPU-core-safe and megacore-friendly.

Nearest rounding only — the stochastic-rounding gradient variant (which
needs a randomness operand) lives in ``stochastic_quantize.py``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.quant import QuantSpec

from . import STATS_TILE, platform, stats_tile

DEFAULT_BLOCK = (256, 256)


def _kernel(x_ref, qparams_ref, q_ref, stats_ref, *, spec: QuantSpec,
            m: int, n: int, bm: int, bn: int, shift: int):
    i = pl.program_id(0)
    j = pl.program_id(1)

    x = x_ref[...].astype(jnp.float32)
    # (scale, zero_point) are *pre-computed* operands — exactly like the
    # quantization registers of a fixed-point accelerator.  Deriving them
    # in-kernel would also risk fp boundary disagreement with the host
    # (zp = round(-qmin/scale) sits on a .5 boundary for symmetric ranges).
    scale = qparams_ref[0, 0]
    zp = qparams_ref[0, 1]

    q = jnp.clip(jnp.round(x / scale + zp), spec.int_min, spec.int_max) - shift
    q_ref[...] = q.astype(q_ref.dtype)

    # Online statistics of the *unquantized* tile (the accumulator-side
    # min/max of the paper).  Mask out block padding.
    rows = jax.lax.broadcasted_iota(jnp.int32, (bm, bn), 0) + i * bm
    cols = jax.lax.broadcasted_iota(jnp.int32, (bm, bn), 1) + j * bn
    valid = jnp.logical_and(rows < m, cols < n)
    big = jnp.float32(jnp.finfo(jnp.float32).max)
    stats_ref[0, 0] = stats_tile(jnp.min(jnp.where(valid, x, big)),
                                 jnp.max(jnp.where(valid, x, -big)))


def fused_quantize_kernel(
    x: jax.Array,
    qparams: jax.Array,          # fp32 [1, 2] = [[scale, zero_point]]
    *,
    spec: QuantSpec,
    block=DEFAULT_BLOCK,
):
    """Raw pallas_call over a 2-D view (shape plumbing in ``ops``).

    Returns ``(q, partials)`` with ``q`` int8 (symmetric grid directly, or
    the asymmetric [0, 255] grid stored shifted by -128 so storage stays
    int8/MXU-native) and ``partials`` fp32 ``[gm, gn, 8, 128]`` per-tile
    (min, max) in rows 0 and 1.
    """
    m, n = x.shape
    bm, bn = min(block[0], m), min(block[1], n)
    gm, gn = pl.cdiv(m, bm), pl.cdiv(n, bn)
    shift = 0 if spec.symmetric else 128

    kernel = functools.partial(
        _kernel, spec=spec, m=m, n=n, bm=bm, bn=bn, shift=shift
    )
    return pl.pallas_call(
        kernel,
        grid=(gm, gn),
        in_specs=[
            pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
            pl.BlockSpec((1, 2), lambda i, j: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
            pl.BlockSpec((1, 1) + STATS_TILE, lambda i, j: (i, j, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((m, n), jnp.int8),
            jax.ShapeDtypeStruct((gm, gn) + STATS_TILE, jnp.float32),
        ],
        interpret=platform.interpret_mode(),
    )(x, qparams)
