"""Conv lowering (PR 5): plan resolution, im2col/col2im, the int8 conv
kernel vs its int32-XLA-conv oracle, and the flag-gated on-chip PRNG.

Separate from ``test_kernels.py`` so these run without ``hypothesis``.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.quant import QuantSpec
from repro.kernels import ops, ref


def _rand(shape, seed):
    return jax.random.normal(jax.random.PRNGKey(seed), shape)


@pytest.mark.parametrize("geom", [
    dict(shape=(2, 9, 9, 8), k=3, cout=12, stride=2, padding="SAME", g=1, d=1),
    dict(shape=(2, 8, 8, 8), k=3, cout=12, stride=1, padding="VALID", g=1, d=1),
    dict(shape=(2, 8, 8, 8), k=3, cout=8, stride=2, padding="SAME", g=8, d=1),
    dict(shape=(1, 10, 10, 4), k=3, cout=8, stride=1, padding="SAME", g=1, d=2),
])
def test_int8_conv_fp_matches_ref(geom):
    n, h, w, cin = geom["shape"]
    xq = jax.random.randint(jax.random.PRNGKey(0), geom["shape"], 0,
                            256).astype(jnp.uint8)
    wq = jax.random.randint(jax.random.PRNGKey(1),
                            (geom["k"], geom["k"], cin // geom["g"],
                             geom["cout"]), -127, 128).astype(jnp.int8)
    zp, alpha = jnp.float32(117.0), jnp.float32(3e-4)
    plan = ops.plan_conv(xq.shape, wq.shape, geom["stride"], geom["padding"],
                         geom["d"], geom["g"])
    y, mn, mx = ops.int8_conv_fp(xq, wq, zp, alpha, plan=plan)
    yr, mnr, mxr = ref.ref_int8_conv_fp(
        xq, wq, zp, alpha, stride=(geom["stride"],) * 2,
        padding=geom["padding"], dilation=(geom["d"],) * 2, groups=geom["g"])
    np.testing.assert_array_equal(np.asarray(y), np.asarray(yr))
    assert float(mn) == float(mnr) and float(mx) == float(mxr)


def test_plan_matches_lax_conv_output_shape():
    for padding in ("SAME", "VALID"):
        plan = ops.plan_conv((2, 11, 9, 6), (3, 3, 6, 10), 2, padding, 1, 1)
        y = jax.lax.conv_general_dilated(
            jnp.zeros((2, 11, 9, 6)), jnp.zeros((3, 3, 6, 10)), (2, 2),
            padding, dimension_numbers=("NHWC", "HWIO", "NHWC"))
        assert y.shape == (plan.n, plan.oh, plan.ow, plan.cout), padding


def test_conv_patch_roundtrip_transpose():
    """conv_unpatch is the exact linear transpose of conv_patches:
    <patches(x), d> == <x, unpatch(d)> for all x, d."""
    plan = ops.plan_conv((2, 7, 7, 6), (3, 3, 3, 8), 2, "SAME", 1, 2)
    x = _rand((2, 7, 7, 6), 0)
    d = _rand((plan.groups, plan.m, plan.k), 1)
    lhs = jnp.vdot(ops.conv_patches(x, plan, 0.0), d)
    rhs = jnp.vdot(x, ops.conv_unpatch(d, plan))
    np.testing.assert_allclose(float(lhs), float(rhs), rtol=1e-5)


def test_conv_weight_lowering_roundtrip():
    plan = ops.plan_conv((1, 5, 5, 8), (3, 3, 2, 12), 1, "SAME", 1, 4)
    w = _rand((3, 3, 2, 12), 2)
    np.testing.assert_array_equal(
        np.asarray(ops.conv_unlower_weights(ops.conv_lower_weights(w, plan),
                                            plan)),
        np.asarray(w))


def test_plan_conv_validates_geometry():
    with pytest.raises(ValueError, match="geometry"):
        ops.plan_conv((2, 8, 8, 7), (3, 3, 4, 8), 1, "SAME", 1, 2)


def test_stochastic_on_chip_prng_rejected_in_interpret_mode():
    """The on-chip PRNG path is TPU-only; interpret mode (every backend but
    the TPU, so this CPU run) must keep the deterministic noise-operand
    form (backend parity depends on it)."""
    from repro.kernels import platform
    assert platform.interpret_mode()
    x = _rand((8, 8), 0)
    spec = QuantSpec(bits=8, symmetric=False, stochastic=True)
    with pytest.raises(ValueError, match="real TPU"):
        ops.stochastic_quantize(x, -1.0, 1.0, None, spec=spec,
                                on_chip_prng=True, seed=3)
    with pytest.raises(ValueError, match="seed"):
        from repro.kernels.stochastic_quantize import (
            stochastic_quantize_kernel,
        )
        stochastic_quantize_kernel(x, jnp.ones((1, 2)), None, spec=spec,
                                   on_chip_prng=True)
