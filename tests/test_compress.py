"""In-hindsight int8 gradient collective: correctness + unbiasedness
(subprocess with 8 host devices)."""
import os
import subprocess
import sys
import textwrap

import pytest

_SUBPROC = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, jax.numpy as jnp, numpy as np
    from repro.runtime import compress

    mesh = jax.make_mesh((8,), ("data",))
    reduce_fn, update_fn, init_fn = compress.make_compressor(mesh, ("data",))
    reduce_jit = jax.jit(reduce_fn)

    # per-replica gradients: [8, ...] stacked
    key = jax.random.PRNGKey(0)
    grads = {
        "a": jax.random.normal(key, (8, 64, 32)) * 0.01,
        "b": jax.random.normal(jax.random.fold_in(key, 1), (8, 128)) * 0.1,
    }
    state = init_fn({"a": grads["a"][0], "b": grads["b"][0]})

    true_mean = jax.tree_util.tree_map(lambda g: jnp.mean(g, 0), grads)

    # first call: ranges fall back to local absmax -> still close
    out, stats = reduce_jit(grads, state, 0)
    for k in grads:
        scale = float(jnp.max(jnp.abs(true_mean[k])))
        err = float(jnp.max(jnp.abs(out[k] - true_mean[k])))
        assert err < 0.2 * scale + 1e-3, (k, err, scale)

    # unbiasedness: average over many seeds converges to the true mean
    state = update_fn(state, stats)
    acc = jax.tree_util.tree_map(jnp.zeros_like, true_mean)
    R = 30
    for s in range(R):
        out, _ = reduce_jit(grads, state, s + 1)
        acc = jax.tree_util.tree_map(lambda a, o: a + o / R, acc, out)
    for k in grads:
        scale = float(jnp.max(jnp.abs(true_mean[k]))) + 1e-9
        bias = float(jnp.max(jnp.abs(acc[k] - true_mean[k]))) / scale
        assert bias < 0.05, (k, bias)

    # the range state tracked the reduced gradient
    assert float(jax.tree_util.tree_leaves(state)[0][2]) == 1.0
    print("COMPRESS_OK")
""")


@pytest.mark.slow
def test_compressed_psum_correct_and_unbiased():
    r = subprocess.run([sys.executable, "-c", _SUBPROC],
                       capture_output=True, text=True, timeout=600,
                       env={**os.environ, "PYTHONPATH": "src"})
    assert r.returncode == 0, r.stderr[-3000:]
    assert "COMPRESS_OK" in r.stdout
