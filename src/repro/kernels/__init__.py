"""Pallas TPU kernels for the paper's single-pass quantized data path.

On a TPU the kernels compile with Mosaic; on any other backend they run in
Pallas interpret mode, which executes the kernel bodies with XLA ops so the
CPU test suite can check them against the ``ref.py`` oracles.  The choice is
made per trace by :func:`repro.kernels.platform.interpret_mode`.

Every kernel writes its per-grid-step statistics as one lane-dense f32
``STATS_TILE``: row ``r`` holds statistic ``r`` in every lane.  The TPU
compiler only accepts output blocks whose last two dims are multiples of
(8, 128) (or the full array dims), so a ``(1, 1, 2)`` partials block is
refused while an ``(8, 128)`` tile is not.
"""
import jax
import jax.numpy as jnp

STATS_TILE = (8, 128)


def stats_tile(*values) -> jax.Array:
    """An ``STATS_TILE`` f32 tile whose row ``r`` is ``values[r]``."""
    rows = jax.lax.broadcasted_iota(jnp.int32, STATS_TILE, 0)
    tile = jnp.zeros(STATS_TILE, jnp.float32)
    for r, v in enumerate(values):
        tile = jnp.where(rows == r, v, tile)
    return tile


def stats_values(partials: jax.Array, n: int) -> jax.Array:
    """``[..., n]`` statistics 0..n-1 of every ``[..., 8, 128]`` tile."""
    return partials[..., :n, 0]
