"""The main-path Pallas kernels compile for a TPU v5e chip.

Each test compiles one kernel at starcoder2-3b widths (d_model 3072,
d_ff 12288, head_dim 128, 24 q / 2 kv heads, window 4096; batch 4 x seq
4096) for a *described* v5e chip: the TPU compiler is installed with JAX
and compiles for a topology that is not attached.  What it refuses here
(blocks off the (8, 128) tiling, too much VMEM) the Pallas interpreter
accepts, so these tests guard the kernels on the chip at no chip time.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, and under pytest-xdist
every worker imports this file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.quant import QuantSpec
from repro.kernels import int8_attention, ops, platform, tuning

D_MODEL, D_FF, HEAD_DIM, N_KV, GROUPS, WINDOW = 3072, 12288, 128, 2, 12, 4096
BATCH, SEQ = 4, 4096
ACT = QuantSpec(bits=8, symmetric=False)
GRAD = QuantSpec(bits=8, symmetric=False, stochastic=True)


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture()
def chip(one_chip, monkeypatch):
    """Kernels traced for the chip (this process's backend is the CPU), and
    no persistent cache: a TPU executable cannot be read back here."""
    from jax.experimental.compilation_cache import compilation_cache
    monkeypatch.setattr(platform, "interpret_mode", lambda: False)
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield lambda shape, dtype: jax.ShapeDtypeStruct(shape, jnp.dtype(dtype),
                                                    sharding=one_chip)
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compile_has_kernel(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_fused_quantize_compiles(chip):
    _compile_has_kernel(
        lambda x, lo, hi: ops.fused_quantize(x, lo, hi, spec=ACT),
        chip((BATCH * SEQ, D_MODEL), "float32"), chip((), "float32"),
        chip((), "float32"))


def test_stochastic_quantize_compiles(chip):
    _compile_has_kernel(
        lambda x, lo, hi, u: ops.stochastic_quantize(x, lo, hi, u, spec=GRAD),
        chip((BATCH * SEQ, D_MODEL), "float32"), chip((), "float32"),
        chip((), "float32"), chip((BATCH * SEQ, D_MODEL), "float32"))


def test_stochastic_quantize_on_chip_prng_compiles(chip):
    _compile_has_kernel(
        lambda x, lo, hi, seed: ops.stochastic_quantize(
            x, lo, hi, None, spec=GRAD, on_chip_prng=True, seed=seed),
        chip((BATCH * SEQ, D_MODEL), "float32"), chip((), "float32"),
        chip((), "float32"), chip((), "int32"))


# ``block=None`` is the tile the tuner picks; the others are every
# candidate it may pick.
@pytest.mark.parametrize("block", [None, *tuning.MATMUL_CANDIDATES])
@pytest.mark.parametrize("x_shape", [(BATCH, SEQ, D_MODEL), (8, 1, D_MODEL)],
                         ids=["prefill", "decode"])
def test_int8_matmul_fp_compiles(chip, x_shape, block):
    plan = ops.plan_einsum("bsd,df->bsf", 3, 2)
    _compile_has_kernel(
        lambda x, w, zp, alpha: ops.int8_matmul_fp(x, w, zp, alpha,
                                                   plan=plan, block=block),
        chip(x_shape, "uint8"), chip((D_MODEL, D_FF), "int8"),
        chip((), "float32"), chip((), "float32"))


def test_int8_matmul_fused_compiles(chip):
    _compile_has_kernel(
        lambda x, w, b: ops.int8_matmul_fused(x, w, 0.01, 117.0, 0.02, b,
                                              -4.0, 4.0),
        chip((BATCH * SEQ, D_MODEL), "uint8"), chip((D_MODEL, D_FF), "int8"),
        chip((D_FF,), "float32"))


@pytest.mark.parametrize("block", [None, *tuning.ATTN_CANDIDATES])
def test_int8_attention_compiles(chip, block):
    bq, bkv = block or tuning.attention_block(SEQ, SEQ, HEAD_DIM)
    sched = int8_attention.make_schedule(
        sq=SEQ, skv=SEQ, hd=HEAD_DIM, bq=bq, bkv=bkv, groups=GROUPS,
        mode="sliding", window=WINDOW, sm_scale=HEAD_DIM ** -0.5)
    bh, zb = BATCH * N_KV * GROUPS, BATCH * N_KV
    _compile_has_kernel(
        lambda q, k, v, regs, kvlen: ops.int8_attention_fp(
            q, k, v, regs, kvlen, sched=sched),
        chip((bh, SEQ, HEAD_DIM), "uint8"), chip((zb, SEQ, HEAD_DIM), "int8"),
        chip((zb, SEQ, HEAD_DIM), "int8"), chip((1, 8), "float32"),
        chip((1, 1), "int32"))
