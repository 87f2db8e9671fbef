"""Production mesh construction.

Target hardware: TPU v5e pods — 256 chips/pod (16x16 ICI torus),
197 bf16 TFLOP/s, 16 GiB HBM @ 819 GB/s, ~50 GB/s/link ICI per chip.

``make_production_mesh`` is a FUNCTION (never a module constant) so that
importing this module touches no jax device state: ``launch.dryrun``'s
``main`` pins itself to the CPU with 512 host devices before its first
backend use, and tests/benches must keep seeing 1 device.
"""
from __future__ import annotations

import jax

# TPU v5e hardware constants (used by the roofline analysis).
PEAK_FLOPS_BF16 = 197e12      # per chip
HBM_BW = 819e9                # bytes/s per chip
ICI_BW = 50e9                 # bytes/s per link


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    # Auto axes: the model places activations with sharding constraints.
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def dp_axes(multi_pod: bool = False) -> tuple:
    """The data-parallel (batch) mesh axes."""
    return ("pod", "data") if multi_pod else ("data",)


def num_chips(multi_pod: bool = False) -> int:
    return 512 if multi_pod else 256
