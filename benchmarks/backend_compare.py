"""Simulated-vs-fused execution-backend step-time comparison.

    PYTHONPATH=src python -m benchmarks.backend_compare
    PYTHONPATH=src python -m benchmarks.backend_compare --steps 10 --out x.json
    PYTHONPATH=src python -m benchmarks.backend_compare --family cnn \
        --out BENCH_conv.json --check

Runs the same training loop once per backend (identical batches) and
records per-step wall time plus the bit-exactness of the final quant
state.  ``--family lm`` (default) drives the reduced transformer config
-> ``BENCH_backend.json``; ``--family cnn`` drives a MobileNetV2 bench
config through the int8 conv path -> ``BENCH_conv.json``.

Interpretation caveat: on this CPU container the fused backend executes
the Pallas kernels in INTERPRET mode, which measures dispatch overhead,
not accelerator speed — the HBM-traffic model in
``benchmarks/kernel_bench.py`` (paper Fig. 4: ~5 B/elem static vs
~13 B/elem dynamic) is the performance claim; this benchmark is the
functional proof that the full hot path runs through the kernels and the
regression guard on its overhead.
"""
from __future__ import annotations

import argparse
import json
import time

import jax
import numpy as np

from repro import configs, data
from repro.core.policy import QuantPolicy
from repro.optim import adamw
from repro.optim.schedules import constant
from repro.runtime import steps as steps_mod

from .common import env_metadata, mean_std, report


def _time_loop(ts, state, batch_fn, steps: int, warmup: int):
    """Shared timing protocol: first call = compile, ``warmup`` discarded
    steps, then ``steps`` timed steps.  Returns (results dict, state)."""
    t0 = time.perf_counter()
    state, met = ts(state, batch_fn(0))
    jax.block_until_ready(met["loss"])
    compile_s = time.perf_counter() - t0

    times = []
    for i in range(1, warmup + steps + 1):
        t0 = time.perf_counter()
        state, met = ts(state, batch_fn(i))
        jax.block_until_ready(met["loss"])
        if i > warmup:
            times.append(time.perf_counter() - t0)
    m, s = mean_std(times)
    return {"compile_s": compile_s, "step_ms_mean": m * 1e3,
            "step_ms_std": s * 1e3, "loss": float(met["loss"])}, state


def time_backend(backend: str, arch: str, steps: int, warmup: int = 1):
    policy = QuantPolicy.w8a8g8(backend=backend)
    cfg = configs.get_reduced(arch)
    opt = adamw(weight_decay=0.0)
    state = steps_mod.init_train_state(jax.random.PRNGKey(0), cfg, opt,
                                       policy)
    stream = data.for_arch(cfg, seq_len=32, global_batch=4, seed=0)
    ts = jax.jit(steps_mod.make_train_step(cfg, policy, opt, constant(3e-3)))
    return _time_loop(ts, state, stream.batch, steps, warmup)


def time_backend_cnn(backend: str, steps: int, warmup: int = 1):
    """MobileNetV2 bench config through the int8 conv backend site."""
    import jax.numpy as jnp

    from repro.cnn import models, train as cnn_train
    from repro.data import ImageStream
    from repro.optim import sgdm

    policy = QuantPolicy.w8a8g8(backend=backend)
    cfg = models.bench_config("mobilenetv2", num_classes=4, width=0.25,
                              image_size=8)
    params, bn = models.init(jax.random.PRNGKey(0), cfg)
    quant = models.init_sites(cfg, policy)
    opt = sgdm(momentum=0.9)
    stream = ImageStream(cfg.num_classes, cfg.image_size, cfg.channels, 4,
                         seed=0)
    ts = jax.jit(cnn_train.make_cnn_train_step(cfg, policy, opt,
                                               constant(0.05)))
    state = {"params": params, "bn": bn, "opt": opt.init(params),
             "quant": quant, "step": jnp.zeros((), jnp.int32)}
    return _time_loop(ts, state, stream.batch, steps, warmup)


def time_backend_attn(backend: str, steps: int, warmup: int = 1):
    """One GQA attention layer as a toy train loop: every step runs the
    backend-dispatched int8 attention core (``backend.attention``) plus
    the q/k/v/o projection sites, with estimator updates between steps."""
    import jax.numpy as jnp

    from repro.core import qlinear
    from repro.models import attention as attn_mod

    policy = QuantPolicy.w8a8g8(backend=backend)
    n_heads, n_kv, head_dim, d_model, seq, batch = 8, 2, 16, 64, 32, 4
    params = attn_mod.init_attention(jax.random.PRNGKey(0), d_model,
                                     n_heads, n_kv, head_dim, use_bias=False)

    def train_step(state, batch):
        def loss_fn(p):
            y, ns, _ = attn_mod.attention_layer(
                p, state["quant"], batch, n_heads=n_heads, n_kv=n_kv,
                head_dim=head_dim, mode="causal", policy=policy,
                seed=jnp.int32(0), step=state["step"])
            return jnp.mean(y ** 2), ns
        (loss, ns), grads = jax.value_and_grad(loss_fn, has_aux=True)(
            state["params"])
        new_params = jax.tree_util.tree_map(lambda p, g: p - 3e-3 * g,
                                            state["params"], grads)
        return {"params": new_params,
                "quant": qlinear.update_quant_state(policy, state["quant"],
                                                    ns),
                "step": state["step"] + 1}, {"loss": loss}

    state = {"params": params, "quant": attn_mod.init_attention_sites(),
             "step": jnp.zeros((), jnp.int32)}

    def batch_fn(i):
        return jax.random.normal(jax.random.PRNGKey(i),
                                 (batch, seq, d_model), jnp.float32)

    return _time_loop(jax.jit(train_step), state, batch_fn, steps, warmup)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="starcoder2-3b")
    ap.add_argument("--family", default="lm", choices=["lm", "cnn", "attn"],
                    help="lm = reduced transformer (matmul sites), cnn = "
                         "MobileNetV2 bench config (int8 conv sites), attn "
                         "= one GQA attention layer (int8 flash core)")
    ap.add_argument("--steps", type=int, default=5)
    ap.add_argument("--out", default="",
                    help="output JSON (default BENCH_backend.json for lm, "
                         "BENCH_conv.json for cnn)")
    ap.add_argument("--check", action="store_true",
                    help="exit non-zero unless the two backends end the "
                         "run with bit-identical quant states and losses "
                         "(the CI gate)")
    args = ap.parse_args(argv)
    args.out = args.out or {"cnn": "BENCH_conv.json",
                            "attn": "BENCH_attention.json"}.get(
                                args.family, "BENCH_backend.json")

    results = {"family": args.family, "meta": env_metadata()}
    states = {}
    for bk in ("simulated", "fused"):
        if args.family == "cnn":
            results[bk], states[bk] = time_backend_cnn(bk, args.steps)
        elif args.family == "attn":
            results[bk], states[bk] = time_backend_attn(bk, args.steps)
        else:
            results[bk], states[bk] = time_backend(bk, args.arch, args.steps)

    eq = jax.tree_util.tree_map(
        lambda a, b: bool((np.asarray(a) == np.asarray(b)).all()),
        states["simulated"]["quant"], states["fused"]["quant"])
    leaves = jax.tree_util.tree_leaves(eq)
    results["quant_state_bit_exact"] = bool(all(leaves))
    results["loss_bit_exact"] = (results["simulated"]["loss"]
                                 == results["fused"]["loss"])
    results["note"] = ("fused runs Pallas kernels in interpret mode on CPU "
                       "(functional proxy); see kernel_bench for the "
                       "HBM-traffic model")

    rows = [[bk, f"{results[bk]['compile_s']:.1f}",
             f"{results[bk]['step_ms_mean']:.1f}",
             f"{results[bk]['step_ms_std']:.1f}",
             f"{results[bk]['loss']:.6f}"] for bk in ("simulated", "fused")]
    report(rows, ["backend", "compile_s", "step_ms", "step_ms_std", "loss"])
    print(f"quant_state_bit_exact={results['quant_state_bit_exact']} "
          f"loss_bit_exact={results['loss_bit_exact']}")

    with open(args.out, "w") as f:
        json.dump(results, f, indent=2)
    print(f"wrote {args.out}")
    if args.check and not (results["quant_state_bit_exact"]
                           and results["loss_bit_exact"]):
        raise SystemExit("backend parity violated: simulated and fused "
                         "runs diverged (see " + args.out + ")")
    return results


if __name__ == "__main__":
    main()
