"""Pallas kernels vs pure-jnp oracles: shape/dtype/spec sweeps (bit-exact
integer outputs, fp32-tolerance statistics).  Off the TPU the kernels run
in interpret mode, which executes the kernel bodies on CPU."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
pytest.importorskip("hypothesis")
from hypothesis import given, settings, strategies as st

from repro.core.quant import QuantSpec
from repro.kernels import ops, ref

SPECS = [QuantSpec(bits=8, symmetric=False),
         QuantSpec(bits=8, symmetric=True)]


def _rand(shape, seed, scale=3.0):
    return jax.random.normal(jax.random.PRNGKey(seed), shape) * scale


@pytest.mark.parametrize("shape", [(8, 16), (33, 70), (128, 257), (1, 1),
                                   (256, 256), (5, 1024)])
@pytest.mark.parametrize("spec", SPECS)
def test_fused_quantize_matches_ref(shape, spec):
    x = _rand(shape, sum(shape))
    lo, hi = jnp.float32(float(x.min())), jnp.float32(float(x.max()))
    qk, mnk, mxk = ops.fused_quantize(x, lo, hi, spec=spec, block=(32, 32))
    qr, mnr, mxr = ref.ref_fused_quantize(x, lo, hi, spec)
    np.testing.assert_array_equal(np.asarray(qk), np.asarray(qr))
    np.testing.assert_allclose(float(mnk), float(mnr), rtol=1e-6)
    np.testing.assert_allclose(float(mxk), float(mxr), rtol=1e-6)


@pytest.mark.parametrize("shape", [(16, 16), (40, 100)])
def test_stochastic_quantize_matches_ref(shape):
    spec = QuantSpec(bits=8, symmetric=False, stochastic=True)
    x = _rand(shape, 7)
    noise = jax.random.uniform(jax.random.PRNGKey(9), shape)
    lo, hi = jnp.float32(-5.0), jnp.float32(5.0)
    qk, mnk, mxk = ops.stochastic_quantize(x, lo, hi, noise, spec=spec,
                                           block=(16, 32))
    qr, mnr, mxr = ref.ref_stochastic_quantize(x, lo, hi, noise, spec)
    np.testing.assert_array_equal(np.asarray(qk), np.asarray(qr))
    np.testing.assert_allclose(float(mnk), float(mnr), rtol=1e-6)


@settings(max_examples=12, deadline=None)
@given(st.integers(1, 70), st.integers(1, 90), st.integers(1, 60),
       st.booleans(), st.floats(0.0, 255.0))
def test_int8_matmul_fused_property(m, k, n, bias, zp):
    """Random ragged shapes: kernel output must be BIT-EXACT vs oracle."""
    xq = jax.random.randint(jax.random.PRNGKey(m * 7 + n), (m, k), 0,
                            256).astype(jnp.uint8)
    wq = jax.random.randint(jax.random.PRNGKey(k), (k, n), -127,
                            128).astype(jnp.int8)
    b = _rand((n,), 5, 1.0) if bias else None
    spec = QuantSpec(bits=8, symmetric=False)
    out = ops.int8_matmul_fused(xq, wq, 0.01, zp, 0.02, b, -1.5, 2.5,
                                block=(16, 16, 32))
    r = ref.ref_int8_matmul_fused(
        xq, wq, jnp.float32(0.01), jnp.float32(zp), jnp.float32(0.02), b,
        jnp.float32(-1.5), jnp.float32(2.5), spec)
    np.testing.assert_array_equal(np.asarray(out[0]), np.asarray(r[0]))
    np.testing.assert_allclose(float(out[1]), float(r[1]), rtol=1e-5,
                               atol=1e-5)
    np.testing.assert_allclose(float(out[2]), float(r[2]), rtol=1e-5,
                               atol=1e-5)


@pytest.mark.parametrize("block", [(16, 16, 16), (64, 64, 64),
                                   (128, 128, 128)])
def test_int8_matmul_block_invariance(block):
    """Result must not depend on the BlockSpec tiling."""
    xq = jax.random.randint(jax.random.PRNGKey(1), (96, 160), 0,
                            256).astype(jnp.uint8)
    wq = jax.random.randint(jax.random.PRNGKey(2), (160, 80), -127,
                            128).astype(jnp.int8)
    out = ops.int8_matmul_fused(xq, wq, 0.02, 117.0, 0.01, None, -4.0, 4.0,
                                block=block)
    base = ops.int8_matmul_fused(xq, wq, 0.02, 117.0, 0.01, None, -4.0, 4.0,
                                 block=(32, 32, 32))
    np.testing.assert_array_equal(np.asarray(out[0]), np.asarray(base[0]))


def test_stats_tile_roundtrip():
    """Each kernel's per-step statistics tile carries statistic r in row r
    of every lane; the wrappers read them back exactly."""
    from repro.kernels import STATS_TILE, stats_tile, stats_values
    vals = [jnp.float32(v) for v in (-3.5, 7.25, 0.0, 1e-30, 3e38, -0.0)]
    tile = stats_tile(*vals)
    assert tile.shape == STATS_TILE == (8, 128)
    for r, v in enumerate(vals):
        np.testing.assert_array_equal(np.asarray(tile[r]),
                                      np.full(128, v, np.float32))
    np.testing.assert_array_equal(np.asarray(tile[len(vals):]), 0.0)
    np.testing.assert_array_equal(
        np.asarray(stats_values(tile[None], len(vals))),
        np.asarray(jnp.stack(vals))[None])


def test_kernel_quant_matches_core_quantizer():
    """The kernel implements EXACTLY repro.core.quant's grid (single source
    of truth between the simulation path and the TPU path)."""
    from repro.core import quant
    spec = QuantSpec(bits=8, symmetric=False)
    x = _rand((64, 64), 3)
    lo, hi = jnp.float32(-2.0), jnp.float32(2.0)
    qk, _, _ = ops.fused_quantize(x, lo, hi, spec=spec)
    qc = quant.quantize(x, lo, hi, spec)
    np.testing.assert_array_equal(np.asarray(qk, np.int32), np.asarray(qc))


def test_dynamic_two_pass_ref():
    spec = QuantSpec(bits=8, symmetric=False)
    x = _rand((32, 32), 11)
    q, mn, mx = ref.ref_dynamic_quantize_two_pass(x, spec)
    assert float(mn) == float(x.min()) and float(mx) == float(x.max())
