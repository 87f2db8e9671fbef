"""Pallas TPU kernel: single-pass *stochastic* quantization + statistics.

The gradient variant of ``fused_quantize``: the paper quantizes activation
gradients with asymmetric uniform quantization and **stochastic rounding**
(Gupta et al. 2015), range supplied in-hindsight.  Rounding noise
``u ~ U[0,1)`` enters as an explicit operand so the kernel is bit-exact
reproducible and portable (CPU interpret mode == TPU) — the default.

On a real TPU the operand can instead be generated on-chip
(``on_chip_prng=True``): the kernel seeds the per-core PRNG from an int32
operand (decorrelated per grid tile) and draws ``pltpu.prng_random_bits``,
which removes the 4 B/elem noise read from HBM — the last off-chip stream
of the single-pass gradient dataflow.  The flag is rejected off the TPU
(in interpret mode): interpret-mode support for the TPU PRNG is not
guaranteed, and the operand form's determinism is required for the
checkpoint-resume and backend-parity tests.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.quant import QuantSpec

from . import STATS_TILE, platform, stats_tile

DEFAULT_BLOCK = (256, 256)


def _kernel(x_ref, qparams_ref, noise_ref, q_ref, stats_ref, *, spec: QuantSpec,
            m: int, n: int, bm: int, bn: int, shift: int):
    i = pl.program_id(0)
    j = pl.program_id(1)

    x = x_ref[...].astype(jnp.float32)
    scale = qparams_ref[0, 0]   # pre-computed (scale, zp) — see fused_quantize
    zp = qparams_ref[0, 1]

    v = jnp.floor(x / scale + zp + noise_ref[...].astype(jnp.float32))
    q = jnp.clip(v, spec.int_min, spec.int_max) - shift
    q_ref[...] = q.astype(q_ref.dtype)

    rows = jax.lax.broadcasted_iota(jnp.int32, (bm, bn), 0) + i * bm
    cols = jax.lax.broadcasted_iota(jnp.int32, (bm, bn), 1) + j * bn
    valid = jnp.logical_and(rows < m, cols < n)
    big = jnp.float32(jnp.finfo(jnp.float32).max)
    stats_ref[0, 0] = stats_tile(jnp.min(jnp.where(valid, x, big)),
                                 jnp.max(jnp.where(valid, x, -big)))


def _kernel_onchip(x_ref, qparams_ref, seed_ref, q_ref, stats_ref, *,
                   spec: QuantSpec, m: int, n: int, bm: int, bn: int,
                   gn: int, shift: int):
    i = pl.program_id(0)
    j = pl.program_id(1)

    x = x_ref[...].astype(jnp.float32)
    scale = qparams_ref[0, 0]
    zp = qparams_ref[0, 1]

    # Decorrelate tiles: one PRNG stream per (site seed, grid cell).
    # The site seed is spread by a Weyl constant before the tile index is
    # added (same mixing as ``backend.site_key``): adjacent sites use
    # consecutive integer seeds by repo convention, so a plain
    # ``seed + tile`` would alias site A's tile 1 with site B's tile 0.
    # The raw bits map to U[0,1) via the top 24 bits (exactly
    # representable in fp32), the standard uniform-from-bits form.
    mixed = seed_ref[0, 0] * jnp.int32(-0x61C88647)   # 0x9E3779B9 as int32
    pltpu.prng_seed(mixed + i * gn + j)
    # A logical shift keeps the top 24 bits as a non-negative int32 (the
    # TPU has no uint32 -> f32 conversion).
    bits = pltpu.bitcast(pltpu.prng_random_bits((bm, bn)), jnp.int32)
    u = jax.lax.shift_right_logical(bits, 8).astype(jnp.float32) * (
        1.0 / (1 << 24))

    v = jnp.floor(x / scale + zp + u)
    q = jnp.clip(v, spec.int_min, spec.int_max) - shift
    q_ref[...] = q.astype(q_ref.dtype)

    rows = jax.lax.broadcasted_iota(jnp.int32, (bm, bn), 0) + i * bm
    cols = jax.lax.broadcasted_iota(jnp.int32, (bm, bn), 1) + j * bn
    valid = jnp.logical_and(rows < m, cols < n)
    big = jnp.float32(jnp.finfo(jnp.float32).max)
    stats_ref[0, 0] = stats_tile(jnp.min(jnp.where(valid, x, big)),
                                 jnp.max(jnp.where(valid, x, -big)))


def stochastic_quantize_kernel(
    x: jax.Array,
    qparams: jax.Array,  # fp32 [1, 2] = [[scale, zero_point]]
    noise: jax.Array,    # fp32 [M, N] in [0, 1); ignored with on_chip_prng
    *,
    spec: QuantSpec,
    block=DEFAULT_BLOCK,
    on_chip_prng: bool = False,
    seed=None,           # int32 scalar; required with on_chip_prng
):
    m, n = x.shape
    bm, bn = min(block[0], m), min(block[1], n)
    gm, gn = pl.cdiv(m, bm), pl.cdiv(n, bn)
    shift = 0 if spec.symmetric else 128

    interpret = platform.interpret_mode()
    stats_spec = pl.BlockSpec((1, 1) + STATS_TILE, lambda i, j: (i, j, 0, 0))
    out_shape = [
        jax.ShapeDtypeStruct((m, n), jnp.int8),
        jax.ShapeDtypeStruct((gm, gn) + STATS_TILE, jnp.float32),
    ]
    if on_chip_prng:
        if seed is None:
            raise ValueError("on_chip_prng=True requires a `seed` scalar")
        if interpret:
            raise ValueError(
                "on_chip_prng=True requires a real TPU (interpret-mode "
                "support for pltpu.prng_random_bits is not guaranteed, and "
                "the deterministic noise-operand form is what the "
                "checkpoint-resume / backend-parity tests rely on)")
        kernel = functools.partial(
            _kernel_onchip, spec=spec, m=m, n=n, bm=bm, bn=bn, gn=gn,
            shift=shift,
        )
        return pl.pallas_call(
            kernel,
            grid=(gm, gn),
            in_specs=[
                pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
                pl.BlockSpec((1, 2), lambda i, j: (0, 0)),
                pl.BlockSpec((1, 1), lambda i, j: (0, 0)),
            ],
            out_specs=[pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
                       stats_spec],
            out_shape=out_shape,
        )(x, qparams, jnp.asarray(seed, jnp.int32).reshape(1, 1))

    kernel = functools.partial(
        _kernel, spec=spec, m=m, n=n, bm=bm, bn=bn, shift=shift
    )
    return pl.pallas_call(
        kernel,
        grid=(gm, gn),
        in_specs=[
            pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
            pl.BlockSpec((1, 2), lambda i, j: (0, 0)),
            pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
        ],
        out_specs=[pl.BlockSpec((bm, bn), lambda i, j: (i, j)), stats_spec],
        out_shape=out_shape,
        interpret=interpret,
    )(x, qparams, noise)
