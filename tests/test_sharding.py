"""Sharding rules + a miniature multi-device dry-run in a subprocess.

The subprocess sets ``--xla_force_host_platform_device_count`` BEFORE
importing jax (this test process must keep seeing 1 device).
"""
import os
import subprocess
import sys
import textwrap

import jax
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro import configs
from repro.models import model
from repro.runtime import sharding


def test_param_rules_cover_every_arch():
    """Every leaf of every reduced arch gets a VALID spec (rank matches)."""
    for name in configs.names():
        cfg = configs.get_reduced(name)
        params = jax.eval_shape(lambda k: model.init_params(k, cfg),
                                jax.random.PRNGKey(0))
        specs = sharding.param_pspecs(params)
        leaves = jax.tree_util.tree_flatten_with_path(params)[0]
        spec_leaves = jax.tree_util.tree_leaves(
            specs, is_leaf=lambda x: isinstance(x, P))
        assert len(leaves) == len(spec_leaves)
        for (path, leaf), sp in zip(leaves, spec_leaves):
            assert len(sp) <= len(leaf.shape), (path, sp, leaf.shape)


def test_full_arch_params_shard_everything_big():
    """On the production mesh sizes, no parameter leaf of the 340B arch may
    stay fully replicated above 64 MB (it would not fit HBM)."""
    cfg = configs.get("nemotron-4-340b")
    params = jax.eval_shape(lambda k: model.init_params(k, cfg),
                            jax.random.PRNGKey(0))
    specs = sharding.param_pspecs(params)
    leaves = jax.tree_util.tree_flatten_with_path(params)[0]
    spec_leaves = jax.tree_util.tree_leaves(
        specs, is_leaf=lambda x: isinstance(x, P))
    for (path, leaf), sp in zip(leaves, spec_leaves):
        nbytes = np.prod(leaf.shape) * 4
        if nbytes > 64 * 2**20:
            assert any(ax is not None for ax in sp), \
                f"{sharding._path_str(path)} ({nbytes/2**20:.0f} MB) replicated"


def test_hint_noop_without_context():
    import jax.numpy as jnp
    x = jnp.zeros((4, 4))
    assert sharding.hint(x, "batch", None) is x


def test_choose_head_axis():
    assert sharding.choose_head_axis(16, 6, 16) == "kv"
    assert sharding.choose_head_axis(4, 16, 16) == "g"
    assert sharding.choose_head_axis(4, 9, 16) == "g"    # padded, larger
    assert sharding.choose_head_axis(8, 2, 16) == "kv"


_SUBPROC = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, jax.numpy as jnp
    from repro import configs
    from repro.core.policy import QuantPolicy
    from repro.models import model
    from repro.optim import adamw
    from repro.optim.schedules import constant
    from repro.runtime import sharding, steps
    from jax.sharding import NamedSharding, PartitionSpec as P

    cfg = configs.get_reduced("qwen2-moe-a2.7b")
    # Auto axes: the model places activations with sharding constraints.
    mesh = jax.make_mesh((2, 4), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
    opt = adamw()
    state = steps.init_train_state(jax.random.PRNGKey(0), cfg, opt)
    import repro.data as data
    stream = data.for_arch(cfg, seq_len=32, global_batch=4)
    ts = steps.make_train_step(cfg, QuantPolicy.w8a8g8(), opt,
                               constant(1e-3))
    specs = sharding.train_state_pspecs(state, mesh)
    batch = stream.batch(0)
    bspecs = sharding.batch_pspecs(batch, mesh, ("data",))
    hints = {"batch": "data", "seq": None, "embed": None,
             "model": "model", "model_size": 4}
    with mesh, sharding.activation_hints(hints):
        jfn = jax.jit(ts, in_shardings=(sharding.named(specs, mesh),
                                        sharding.named(bspecs, mesh)))
        new_state, met = jfn(state, batch)
    assert float(met["loss"]) > 0 and jnp.isfinite(met["loss"])
    # compare against single-device execution (loss must match closely)
    s2, met2 = jax.jit(ts)(state, batch)
    import numpy as np
    assert abs(float(met["loss"]) - float(met2["loss"])) < 1e-2, (
        float(met["loss"]), float(met2["loss"]))
    print("SPMD_OK", float(met["loss"]))
""")


@pytest.mark.slow
def test_spmd_train_step_matches_single_device(tmp_path):
    """A real 8-device SPMD train step must produce the same loss as the
    single-device run (MoE arch: exercises EP + dispatch sharding)."""
    r = subprocess.run([sys.executable, "-c", _SUBPROC],
                       capture_output=True, text=True, timeout=900,
                       env={**os.environ, "PYTHONPATH": "src"})
    assert r.returncode == 0, r.stderr[-3000:]
    assert "SPMD_OK" in r.stdout
