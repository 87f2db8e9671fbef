"""System-level behaviour: the training driver end-to-end (resume path),
serving driver, and the paper's headline property at system scope —
in-hindsight (static) training tracks dynamic quantization."""
import json
import os

import jax
import numpy as np
import pytest

from repro.launch import serve as serve_mod
from repro.launch import train as train_mod


def test_train_driver_end_to_end(tmp_path):
    log = str(tmp_path / "log.jsonl")
    train_mod.main([
        "--arch", "starcoder2-3b", "--reduced", "--steps", "12",
        "--batch", "4", "--seq", "32", "--ckpt-dir", str(tmp_path / "ck"),
        "--ckpt-every", "5", "--log", log, "--policy", "hindsight",
    ])
    rows = [json.loads(l) for l in open(log)]
    assert len(rows) == 12
    assert rows[-1]["loss"] < rows[0]["loss"] + 0.5
    # checkpoints exist and resumed training continues
    from repro import checkpoint
    assert checkpoint.latest_step(str(tmp_path / "ck")) == 12
    train_mod.main([
        "--arch", "starcoder2-3b", "--reduced", "--steps", "15",
        "--batch", "4", "--seq", "32", "--ckpt-dir", str(tmp_path / "ck"),
        "--resume", "--log", log, "--policy", "hindsight",
    ])
    rows = [json.loads(l) for l in open(log)]
    assert rows[-1]["step"] == 14   # resumed at 12, ran to 15


def test_serve_driver_runs(capsys):
    serve_mod.main(["--arch", "starcoder2-3b", "--reduced", "--batch", "2",
                    "--prompt-len", "16", "--gen", "4"])
    out = capsys.readouterr().out
    assert "prefill" in out and "tok/s" in out


def test_serve_fails_when_restore_fails(tmp_path):
    """A --ckpt-dir that cannot be restored ends serving with an error
    instead of serving random weights."""
    with pytest.raises(SystemExit) as e:
        serve_mod.main(["--arch", "starcoder2-3b", "--reduced", "--batch",
                        "2", "--prompt-len", "16", "--gen", "4",
                        "--ckpt-dir", str(tmp_path / "empty")])
    assert e.value.code not in (0, None)


@pytest.mark.parametrize("env_dir", [None, "given"])
def test_compile_cache_location(tmp_path, monkeypatch, env_dir):
    """$JAX_COMPILATION_CACHE_DIR wins and nothing else is set; otherwise
    the cache sits at the fixed <checkout>/.jax_cache."""
    from repro.launch import compile_cache
    was = jax.config.jax_compilation_cache_dir
    sentinel = str(tmp_path / "unchanged")
    jax.config.update("jax_compilation_cache_dir", sentinel)
    try:
        if env_dir is None:
            monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
            want = os.path.join(
                os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                ".jax_cache")
            assert compile_cache.enable() == want
            assert jax.config.jax_compilation_cache_dir == want
        else:
            given = str(tmp_path / env_dir)
            monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", given)
            assert compile_cache.enable() == given
            assert jax.config.jax_compilation_cache_dir == sentinel
    finally:
        jax.config.update("jax_compilation_cache_dir", was)


def test_serve_int8_cache(capsys):
    serve_mod.main(["--arch", "starcoder2-3b", "--reduced", "--batch", "2",
                    "--prompt-len", "16", "--gen", "4", "--int8-cache"])
    out = capsys.readouterr().out
    assert "cache=int8" in out


@pytest.mark.slow
def test_hindsight_tracks_dynamic_quantization():
    """The paper's headline: static in-hindsight ranges achieve training
    behaviour on par with dynamic estimators (system-level, small LM)."""
    import jax.numpy as jnp
    from repro import configs, data
    from repro.core.policy import QuantPolicy
    from repro.optim import adamw
    from repro.optim.schedules import constant
    from repro.runtime import steps as steps_mod

    def final_loss(kind, seed=0):
        cfg = configs.get_reduced("starcoder2-3b")
        opt = adamw(weight_decay=0.0)
        state = steps_mod.init_train_state(jax.random.PRNGKey(seed), cfg, opt)
        stream = data.for_arch(cfg, seq_len=32, global_batch=8, seed=seed)
        pol = (QuantPolicy.disabled() if kind == "fp32"
               else QuantPolicy.w8a8g8(act_kind=kind, grad_kind=kind))
        ts = jax.jit(steps_mod.make_train_step(cfg, pol, opt,
                                               constant(3e-3)))
        losses = []
        for i in range(40):
            state, met = ts(state, stream.batch(i))
            losses.append(float(met["loss"]))
        return float(np.mean(losses[-5:]))

    l_hind = final_loss("hindsight")
    l_curr = final_loss("current")
    l_fp = final_loss("fp32")
    # hindsight within noise of dynamic current min-max and of fp32
    assert abs(l_hind - l_curr) < 0.35, (l_hind, l_curr)
    assert abs(l_hind - l_fp) < 0.5, (l_hind, l_fp)
