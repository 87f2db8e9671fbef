"""Pallas TPU kernel: grouped int8 x int8 -> int32 matmul over the rows
routed to each held expert, with the shared ``alpha * int32`` epilogue.

An expert layer gathers the rows routed to the experts it holds into one
buffer sorted by expert (a *group*), each group starting on a
``GMM_ROWS`` boundary, so that every row tile of the buffer belongs to one
group.  The buffer is sized for the worst case (every assignment on a held
expert); the live row tiles come first and the rest are dead.  Three
scalar-prefetched tables steer the grid:

    tile_group[t]  the group whose weights row tile ``t`` multiplies
    tile_rows[t]   rows of tile ``t`` that hold an assignment (the rest of
                   a group's last tile is padding; dead tiles hold none)
    live[0]        the number of live row tiles

Grid ``(tiles, N / bn, K / bk)``, the contraction innermost.  A dead tile
computes nothing and writes zeros; its index maps repeat the blocks of the
last live step, so the pipeline fetches nothing for it.  Rows past
``tile_rows`` come out as exact zeros, so padding never reaches the next
site's statistics.

Arithmetic, shared with :func:`grouped_matmul_reference` (the
``simulated`` backend): activations are asymmetric uint8 shifted onto the
signed grid (``q - 128``) and the zero-point term ``(128 - zp) *
colsum(w_g)`` is folded into an int32 ``corr`` per group, so the int32
accumulator equals ``(q - zp) @ w_g`` exactly and one fp32 multiply by
``alpha = s_x * s_w`` gives the output.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import platform

GMM_ROWS = 256          # row tile, and the alignment of each group's start


class GmmTiles(NamedTuple):
    """The kernel's scalar tables (int32) and the padded group sizes."""

    tile_group: jax.Array   # [tiles]
    tile_rows: jax.Array    # [tiles]
    live: jax.Array         # [1]
    group_rows: jax.Array   # [groups] rows of each group, padding included


def plan_tiles(counts: jax.Array, n_tiles: int) -> GmmTiles:
    """The tables for groups of ``counts`` rows each, laid out one after
    another, each padded to a multiple of ``GMM_ROWS``, in a buffer of
    ``n_tiles`` row tiles."""
    counts = counts.astype(jnp.int32)
    per_group = -(-counts // GMM_ROWS)                      # tiles of a group
    ends = jnp.cumsum(per_group)
    live = ends[-1]
    t = jnp.arange(n_tiles, dtype=jnp.int32)
    # A dead tile presents the group of the last live tile (no new fetch).
    g = jnp.searchsorted(ends, jnp.minimum(t, jnp.maximum(live - 1, 0)),
                         side="right").astype(jnp.int32)
    g = jnp.minimum(g, counts.shape[0] - 1)
    first = ends[g] - per_group[g]                          # group's 1st tile
    rows = jnp.clip(counts[g] - (t - first) * GMM_ROWS, 0, GMM_ROWS)
    rows = jnp.where(t < live, rows, 0).astype(jnp.int32)
    return GmmTiles(tile_group=g, tile_rows=rows,
                    live=live.reshape(1).astype(jnp.int32),
                    group_rows=per_group * GMM_ROWS)


def row_valid(tiles: GmmTiles) -> jax.Array:
    """bool [tiles * GMM_ROWS]: rows that hold an assignment."""
    r = jnp.arange(tiles.tile_rows.shape[0] * GMM_ROWS, dtype=jnp.int32)
    return (r % GMM_ROWS) < tiles.tile_rows[r // GMM_ROWS]


def gmm_block(k: int, n: int) -> tuple:
    """Column and contraction tiles: whole dims up to 1536 (the expert
    widths: K 2048 -> 512, N 1408 whole, N 2048 -> 1024), else the largest
    of 1024 / 512 / 256 that divides.  Returns ``(bn, bk)``."""
    def pick(d):
        if d <= 1536:
            return d
        for b in (1024, 512, 256):
            if d % b == 0:
                return b
        return d
    bk = k if k <= 1536 else next((b for b in (512, 256) if k % b == 0), k)
    return pick(n), bk


def _gmm_kernel(group_ref, rows_ref, live_ref, x_ref, w_ref, corr_ref,
                alpha_ref, y_ref, acc_ref, *, kdim: int, bk: int, gk: int):
    t = pl.program_id(0)
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(t < live_ref[0])
    def _step():
        x = x_ref[...]
        if kdim % bk != 0:
            kcol = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1) + k * bk
            x = jnp.where(kcol < kdim, x, jnp.int8(0))
        acc_ref[...] += jax.lax.dot_general(
            x, w_ref[0], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.int32)

    @pl.when(k == gk - 1)
    def _epilogue():
        y = alpha_ref[0, 0] * (acc_ref[...] + corr_ref[0]).astype(jnp.float32)
        rows = jax.lax.broadcasted_iota(jnp.int32, y.shape, 0)
        y_ref[...] = jnp.where(rows < rows_ref[t], y, 0.0)


def grouped_matmul_kernel(x_s8, w_q, corr, alpha, tiles: GmmTiles, *,
                          block: tuple):
    """Raw pallas_call.  ``x_s8`` int8 [tiles * GMM_ROWS, K] (uint8 grid
    shifted by -128), ``w_q`` int8 [G, K, N], ``corr`` int32 [G, 1, N],
    ``alpha`` fp32 [1, 1].  Returns y fp32 [tiles * GMM_ROWS, N]."""
    r, kdim = x_s8.shape
    n = w_q.shape[2]
    nt = r // GMM_ROWS
    bn, bk = min(block[0], n), min(block[1], kdim)
    gn, gk = pl.cdiv(n, bn), pl.cdiv(kdim, bk)

    def clamp(t, j, k, live):
        dead = t >= live[0]
        return (jnp.where(dead, jnp.maximum(live[0] - 1, 0), t),
                jnp.where(dead, gn - 1, j), jnp.where(dead, gk - 1, k))

    def x_map(t, j, k, group, rows, live):
        tt, _, kk = clamp(t, j, k, live)
        return tt, kk

    def w_map(t, j, k, group, rows, live):
        tt, jj, kk = clamp(t, j, k, live)
        return group[tt], kk, jj

    def corr_map(t, j, k, group, rows, live):
        tt, jj, _ = clamp(t, j, k, live)
        return group[tt], 0, jj

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(nt, gn, gk),
        in_specs=[
            pl.BlockSpec((GMM_ROWS, bk), x_map),
            pl.BlockSpec((1, bk, bn), w_map),
            pl.BlockSpec((1, 1, bn), corr_map),
            pl.BlockSpec((1, 1), lambda t, j, k, *_: (0, 0)),
        ],
        out_specs=pl.BlockSpec((GMM_ROWS, bn), lambda t, j, k, *_: (t, j)),
        scratch_shapes=[pltpu.VMEM((GMM_ROWS, bn), jnp.int32)],
    )
    return pl.pallas_call(
        functools.partial(_gmm_kernel, kdim=kdim, bk=bk, gk=gk),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((r, n), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=platform.interpret_mode(),
    )(tiles.tile_group, tiles.tile_rows, tiles.live, x_s8, w_q, corr, alpha)


def _corr(w_q, x_zp):
    colsum = jnp.sum(w_q.astype(jnp.int32), axis=1, keepdims=True)
    return jnp.round(128.0 - jnp.asarray(x_zp, jnp.float32)
                     ).astype(jnp.int32) * colsum          # [G, 1, N]


def grouped_matmul(x_q, w_q, x_zp, alpha, tiles: GmmTiles):
    """``alpha * (x_q - zp) @ w_q[group]`` per row on the kernel; ``x_q``
    uint8 [R, K], ``w_q`` int8 [G, K, N]; fp32 [R, N]."""
    block = gmm_block(x_q.shape[1], w_q.shape[2])
    xs = (x_q.astype(jnp.int16) - 128).astype(jnp.int8)
    return grouped_matmul_kernel(
        xs, w_q, _corr(w_q, x_zp),
        jnp.asarray(alpha, jnp.float32).reshape(1, 1), tiles, block=block)


def grouped_matmul_reference(x_q, w_q, x_zp, alpha, tiles: GmmTiles):
    """The same arithmetic in ``jnp``: an int32 ragged contraction of the
    zero-point-corrected image, the one fp32 multiply, padding and dead
    rows zero.  Bit-equal to :func:`grouped_matmul`."""
    rx = x_q.astype(jnp.int32) - jnp.round(x_zp).astype(jnp.int32)
    acc = jax.lax.ragged_dot(rx, w_q.astype(jnp.int32), tiles.group_rows,
                             preferred_element_type=jnp.int32)
    y = jnp.asarray(alpha, jnp.float32) * acc.astype(jnp.float32)
    return jnp.where(row_valid(tiles)[:, None], y, 0.0)
