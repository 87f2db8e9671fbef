"""Smoke run of the main path on a TPU: train and serve starcoder2-3b.

    python chip_smoke.py              # one chip: train phase, serve phase
    python chip_smoke.py --chips 4    # four chips: the sharded train step

The model is ``starcoder2-3b-4l``: starcoder2-3b at its published widths
(d_model 3072, 24 q / 2 kv heads, head_dim 128, d_ff 12288, vocab 49152,
window 4096) cut to 4 of its 30 layers, with random weights from seed 0.

One chip:
  * train: ``repro.launch.train.main`` with ``--policy hindsight --backend
    fused``, seq 4096, batch 4 (4 microbatches of 1), 3 steps.  Every loss
    is finite, the compiled step contains the Pallas kernels
    (``tpu_custom_call``), and the step-0 loss matches the ``simulated``
    backend's on the same seed and batch within ``LOSS_TOL``.
  * serve: ``repro.launch.serve.main``, batch 8, prompt 1024, 8 generated
    tokens; serve fails on non-finite logits.

Four chips (``--chips 4``): the train step sharded over a 2x2
("data", "model") mesh with the ``runtime.sharding`` rules, on the
``simulated`` backend (the Mosaic kernels cannot be partitioned by the
compiler, and no shard_map wraps them yet).  Its step-0 loss matches the
same step on one of the chips within ``LOSS_TOL``, and ``memory_stats``
shows each chip holding its share of the train state.

The script runs in one process, which holds the chips, and fails without a
TPU.  Its last line is ``{"ok": true, "device": {...}}``.
"""
from __future__ import annotations

import argparse
import json
import math
import os
import sys
import tempfile
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")

import jax  # noqa: E402

ARCH = "starcoder2-3b-4l"
SEQ, BATCH, MICROBATCHES, STEPS = 4096, 4, 4, 3
# Step-0 losses of two programs that compute the same step: the fused and
# the simulated backend, or the sharded and the one-chip step.  Their int8
# contractions are exact in both, but the fp32/bf16 parts are not computed
# alike (XLA vs Mosaic exp and division; partial sums reduced across chips
# in another order), and a value that lands on the other side of a rounding
# boundary moves its int8 image by one step.  Averaged over 16384 tokens
# such flips move the ~10.8 nats loss by far less than 1e-2, the repo's own
# bound for the sharded-vs-one-device step (tests/test_sharding.py).
LOSS_TOL = 1e-2


def log(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


def require_tpu(chips: int) -> list:
    devices = jax.devices()
    if devices[0].platform != "tpu":
        sys.exit(f"[chip_smoke] no TPU found (JAX platform "
                 f"{devices[0].platform!r}); this smoke run needs the chip")
    if len(devices) < chips:
        sys.exit(f"[chip_smoke] {chips} chips asked for, {len(devices)} "
                 f"found")
    return devices[:chips]


def device_bytes(device, key: str) -> int:
    """One counter of ``device.memory_stats()``, e.g. peak_bytes_in_use."""
    return device.memory_stats()[key]


def train_argv(backend: str, steps: int, log_path: str) -> list:
    return ["--arch", ARCH, "--policy", "hindsight", "--backend", backend,
            "--seq", str(SEQ), "--batch", str(BATCH),
            "--grad-accum", str(MICROBATCHES), "--steps", str(steps),
            "--seed", "0", "--log", log_path, "--log-every", "1"]


def read_log(path: str) -> list:
    with open(path) as f:
        return [json.loads(line) for line in f]


def train_phase(tmp: str) -> None:
    from repro import configs, data
    from repro.launch import train
    from repro.runtime import steps

    fused_log = os.path.join(tmp, "fused.jsonl")
    argv = train_argv("fused", STEPS, fused_log)
    train.main(argv)  # its final state is dropped, and with it its memory
    rows = read_log(fused_log)
    losses = [r["loss"] for r in rows]
    log(f"train fused losses {losses}")
    if len(rows) != STEPS or not all(math.isfinite(x) for x in losses):
        raise RuntimeError(f"train: expected {STEPS} finite losses")
    first_s = rows[0]["phases_ms"]["compile"] / 1e3
    step_ms = [r["phases_ms"]["execute"] for r in rows[1:]]
    log(f"train first call (trace + compile + step 0) {first_s:.3f} s; "
        f"steady step ms {step_ms}")
    dev = jax.devices()[0]
    log(f"train peak_bytes_in_use {device_bytes(dev, 'peak_bytes_in_use')}")

    # The compiled step is the one main ran (same arguments, same jit).
    args = train.parse_args(argv)
    cfg = configs.get(ARCH)
    policy = train.build_policy(args.policy, args)
    opt, step = train.jit_train_step(cfg, policy, args)
    state = jax.eval_shape(lambda k: steps.init_train_state(
        k, cfg, opt, policy), jax.random.PRNGKey(0))
    batch = jax.eval_shape(
        data.for_arch(cfg, seq_len=SEQ, global_batch=BATCH).batch, 0)
    t0 = time.perf_counter()
    compiled = step.lower(state, batch).compile()
    log(f"train step lower + compile {time.perf_counter() - t0:.3f} s "
        f"(persistent cache: {jax.config.jax_compilation_cache_dir})")
    mem = compiled.memory_analysis()
    log(f"train step memory: arguments {mem.argument_size_in_bytes}, "
        f"temporaries {mem.temp_size_in_bytes}, aliased "
        f"{mem.alias_size_in_bytes} bytes")
    kernels = compiled.as_text().count("tpu_custom_call")
    log(f"train step Pallas kernel calls: {kernels}")
    if not kernels:
        raise RuntimeError("train: the fused step contains no tpu_custom_call")
    del compiled

    sim_log = os.path.join(tmp, "simulated.jsonl")
    train.main(train_argv("simulated", 1, sim_log))
    sim0 = read_log(sim_log)[0]["loss"]
    log(f"train step-0 loss fused {losses[0]!r} simulated {sim0!r} "
        f"|diff| {abs(losses[0] - sim0)!r} (tolerance {LOSS_TOL})")
    if not abs(losses[0] - sim0) <= LOSS_TOL:
        raise RuntimeError("train: fused and simulated step-0 losses differ")


def serve_phase() -> None:
    from repro.launch import serve

    t0 = time.perf_counter()
    gen = serve.main(["--arch", ARCH, "--batch", "8", "--prompt-len", "1024",
                      "--gen", "8"])
    log(f"serve {time.perf_counter() - t0:.3f} s, tokens {gen.shape}")
    if gen.shape != (8, 8):
        raise RuntimeError(f"serve: expected (8, 8) tokens, got {gen.shape}")


def sharded_phase(devices: list) -> None:
    """The train step on a 2x2 mesh vs the same step on one chip."""
    import numpy as np
    from jax.sharding import Mesh, SingleDeviceSharding

    from repro import configs, data
    from repro.core.policy import QuantPolicy
    from repro.optim import adamw
    from repro.optim.schedules import constant
    from repro.runtime import sharding, steps

    cfg = configs.get(ARCH)
    policy = QuantPolicy.w8a8g8()
    opt = adamw()
    fn = steps.make_train_step(cfg, policy, opt, constant(1e-3))
    batch = data.for_arch(cfg, seq_len=SEQ, global_batch=BATCH).batch(0)

    def init(key):
        return steps.init_train_state(key, cfg, opt, policy)

    key = jax.random.PRNGKey(0)
    shapes = jax.eval_shape(init, key)
    state_bytes = sum(x.size * x.dtype.itemsize
                      for x in jax.tree_util.tree_leaves(shapes))
    mesh = Mesh(np.array(devices).reshape(2, 2), ("data", "model"))
    st_sh = sharding.named(sharding.train_state_pspecs(shapes, mesh), mesh)
    b_sh = sharding.named(sharding.batch_pspecs(batch, mesh, ("data",)),
                          mesh)
    hints = {"batch": "data", "seq": None, "embed": None, "model": "model",
             "model_size": 2}
    state = jax.jit(init, out_shardings=st_sh)(key)
    jax.block_until_ready(state)
    held = [device_bytes(d, "bytes_in_use") for d in devices]
    log(f"sharded: train state {state_bytes} bytes; bytes_in_use per chip "
        f"{held}")
    if max(held) >= state_bytes / 2:
        raise RuntimeError("sharded: a chip holds more than half the state")
    t0 = time.perf_counter()
    with mesh, sharding.activation_hints(hints):
        step = jax.jit(fn, in_shardings=(st_sh, b_sh), donate_argnums=(0,))
        state, met = step(state, jax.device_put(batch, b_sh))
        loss_mesh = float(met["loss"])
    log(f"sharded step (compile + run) {time.perf_counter() - t0:.3f} s, "
        f"loss {loss_mesh!r}; peak_bytes_in_use per chip "
        f"{[device_bytes(d, 'peak_bytes_in_use') for d in devices]}")
    del state, met

    one = SingleDeviceSharding(devices[0])
    state = jax.jit(init, out_shardings=one)(key)
    t0 = time.perf_counter()
    state, met = jax.jit(fn, donate_argnums=(0,))(
        state, jax.device_put(batch, one))
    loss_one = float(met["loss"])
    log(f"one-chip step (compile + run) {time.perf_counter() - t0:.3f} s, "
        f"loss {loss_one!r}; |diff| {abs(loss_mesh - loss_one)!r} "
        f"(tolerance {LOSS_TOL})")
    del state, met
    if not (math.isfinite(loss_mesh)
            and abs(loss_mesh - loss_one) <= LOSS_TOL):
        raise RuntimeError("sharded and one-chip step-0 losses differ")


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=[1, 4],
                    help="1: train and serve phases; 4: only the sharded "
                         "train step on a 2x2 mesh")
    args = ap.parse_args(argv)
    devices = require_tpu(args.chips)
    sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(
        __file__)), "src"))
    from repro.launch import compile_cache
    log(f"compile cache: {compile_cache.enable()}")
    log(f"devices: {devices}")

    t0 = time.perf_counter()
    if args.chips == 4:
        sharded_phase(devices)
    else:
        with tempfile.TemporaryDirectory() as tmp:
            train_phase(tmp)
        serve_phase()
    log(f"total {time.perf_counter() - t0:.3f} s")
    d = jax.devices()[0]
    print(json.dumps({"ok": True, "device": {
        "platform": d.platform, "kind": d.device_kind,
        "count": len(jax.devices())}}))


if __name__ == "__main__":
    main()
