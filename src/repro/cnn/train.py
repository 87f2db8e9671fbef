"""CNN training loop used by the paper-table benchmarks and tests.

Implements the paper's exact experimental setting: SGD + momentum 0.9,
step-decay or cosine schedule, per-estimator QuantPolicy, activation-range
calibration before training (paper sec. 5.2), and the one-update-per-step
range semantics shared with the LM path.

Also runnable as a driver (parity with ``repro.launch.train``):

  PYTHONPATH=src python -m repro.cnn.train --arch mobilenetv2 \
      --steps 50 --batch 16 --policy hindsight --backend fused
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.core import qlinear
from repro.core.policy import QuantPolicy
from repro.data import ImageStream
from repro.optim import apply_updates, clip_by_global_norm, sgdm

from . import models


def make_cnn_train_step(cfg: models.CNNConfig, policy: QuantPolicy,
                        optimizer, lr_schedule, clip_norm: float = 5.0):
    def step_fn(state, batch):
        params, bn, quant, step = (state["params"], state["bn"],
                                   state["quant"], state["step"])

        def lf(p, q):
            return models.loss_fn(cfg, p, bn, q, batch, policy,
                                  step * 131072, step)

        (loss, (new_bn, fwd_stats, met)), (pg, qg) = jax.value_and_grad(
            lf, argnums=(0, 1), has_aux=True)(params, quant)
        stats = qlinear.merge_stats(fwd_stats, qg)
        pg, gnorm = clip_by_global_norm(pg, clip_norm)
        updates, new_opt = optimizer.update(pg, state["opt"], params,
                                            lr_schedule(step))
        return {
            "params": apply_updates(params, updates),
            "bn": new_bn,
            "opt": new_opt,
            "quant": qlinear.update_quant_state(policy, quant, stats),
            "step": step + 1,
        }, {"loss": loss, "grad_norm": gnorm, **met}

    return step_fn


def calibrate_cnn(cfg, params, bn, quant, policy, stream: ImageStream,
                  batches: int = 4):
    """Paper sec. 5.2: feed a few batches to warm activation ranges before
    training (observation at 16-bit so the applied error is negligible)."""
    from repro.core.calibration import observation_policy
    obs = observation_policy(policy)

    @jax.jit
    def fwd(q, batch):
        _, (_, stats, _) = models.loss_fn(cfg, params, bn, q, batch, obs,
                                          0, 0, train=False)
        return stats

    for i in range(batches):
        stats = fwd(quant, stream.batch(10_000 + i))
        quant = qlinear.update_quant_state(obs, quant, stats)
    return quant


def train_cnn(cfg: models.CNNConfig, policy: QuantPolicy, *, steps: int,
              batch: int, lr: float = 0.05, seed: int = 0,
              calibration_batches: int = 2, eval_batches: int = 4,
              lr_schedule=None, telemetry_sink=None,
              trace_path: Optional[str] = None):
    """Train + eval; returns (final_eval_acc, history).

    ``telemetry_sink``: any object with ``write(step, records)`` (e.g.
    ``repro.telemetry.JsonlSink`` / ``MemorySink``); fed the per-site
    health records collected from the quant state after every step when
    the policy has telemetry enabled.  When a sink is armed, each line
    also carries the step's ``"perf"`` phase breakdown.

    ``trace_path``: export a Chrome-trace JSON of the step phases
    (data / compile / execute / telemetry) to this path — host-side
    timing only, the computation is unchanged."""
    from repro.optim.schedules import cosine
    key = jax.random.PRNGKey(seed)
    params, bn = models.init(key, cfg)
    quant = models.init_sites(cfg, policy)
    opt = sgdm(momentum=0.9, weight_decay=1e-4)
    sched = lr_schedule or cosine(lr, steps, warmup=max(1, steps // 20))
    stream = ImageStream(cfg.num_classes, cfg.image_size, cfg.channels,
                         batch, seed=seed)

    if policy.enabled and policy.quantize_acts and calibration_batches:
        quant = calibrate_cnn(cfg, params, bn, quant, policy, stream,
                              calibration_batches)

    state = {"params": params, "bn": bn, "opt": opt.init(params),
             "quant": quant, "step": jnp.zeros((), jnp.int32)}
    step_fn = jax.jit(make_cnn_train_step(cfg, policy, opt, sched))

    collect = None
    if telemetry_sink is not None and policy.telemetry.enabled:
        from repro.telemetry import collect

    from repro.telemetry import trace as trace_mod
    tracer = trace_mod.Tracer(enabled=bool(trace_path))
    timer = trace_mod.StepTimer(tracer)

    history = []
    for s in range(steps):
        records = None
        with timer.step(s) as st:
            with st.phase("data"):
                b = stream.batch(s)
            with st.execute():  # "compile" phase on the jit's first call
                state, met = step_fn(state, b)
                history.append({k: float(v) for k, v in met.items()})
            if collect is not None:
                with st.phase("telemetry"):
                    records = collect(state["quant"])
        if records is not None:
            telemetry_sink.write(
                s, records, perf=timer.perf_record(items=batch,
                                                   unit="images"))
    if trace_path:
        tracer.export(trace_path)

    @jax.jit
    def eval_fn(state, batch):
        logits, _, _ = models.apply_cfg(
            cfg, state["params"], state["bn"], state["quant"],
            batch["images"], policy, 0, state["step"], train=False)
        return jnp.mean((jnp.argmax(logits, -1) == batch["labels"])
                        .astype(jnp.float32))

    accs = [float(eval_fn(state, stream.batch(50_000 + i)))
            for i in range(eval_batches)]
    return sum(accs) / len(accs), history


def main(argv=None):
    """CLI driver for the CNN path (parity with ``repro.launch.train``)."""
    import argparse

    from repro import telemetry
    from repro.launch import compile_cache
    from repro.core.estimators import ALL_ESTIMATORS

    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="resnet18",
                    choices=["resnet18", "vgg16", "mobilenetv2"])
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=16)
    ap.add_argument("--lr", type=float, default=0.05)
    ap.add_argument("--num-classes", type=int, default=10)
    ap.add_argument("--width", type=float, default=0.25)
    ap.add_argument("--image-size", type=int, default=32)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--calibration-batches", type=int, default=2)
    ap.add_argument("--policy", default="hindsight",
                    choices=list(ALL_ESTIMATORS) + ["fp32"])
    ap.add_argument("--backend", default="simulated",
                    choices=["simulated", "fused"],
                    help="execution backend for the quantization sites "
                         "(incl. the int8 conv contraction): 'simulated' = "
                         "jnp fake-quant + int32 XLA conv, 'fused' = the "
                         "Pallas single-pass kernels via im2col (compiled "
                         "on a TPU, interpreted elsewhere; requires a "
                         "fully-static --policy, i.e. hindsight or fixed)")
    ap.add_argument("--telemetry", action="store_true",
                    help="per-site quantization health telemetry")
    ap.add_argument("--telemetry-out", default="",
                    help="telemetry JSONL path (default: telemetry.jsonl "
                         "in the cwd)")
    ap.add_argument("--guard", action="store_true",
                    help="arm the overflow guard (implies --telemetry)")
    ap.add_argument("--trace", default="", metavar="PATH",
                    help="export a Chrome-trace JSON of the step phases "
                         "to PATH (view at https://ui.perfetto.dev)")
    args = ap.parse_args(argv)
    compile_cache.enable()
    if args.guard:
        args.telemetry = True

    if args.policy == "fp32":
        policy = QuantPolicy.disabled()
    else:
        policy = QuantPolicy.w8a8g8(act_kind=args.policy,
                                    grad_kind=args.policy)
    if args.telemetry:
        policy = policy.with_telemetry(guard=args.guard)
    if args.backend != policy.backend:
        # Validated at policy construction: raises the backend module's
        # clear error for illegal combinations (dynamic estimator or
        # dynamic-mode guard with backend='fused').
        policy = policy.with_backend(args.backend)

    cfg = models.bench_config(args.arch, num_classes=args.num_classes,
                              width=args.width, image_size=args.image_size)
    sink = None
    if args.telemetry:
        sink = telemetry.JsonlSink(args.telemetry_out or "telemetry.jsonl")
        print(f"[cnn.train] telemetry -> {sink.path}")
    acc, history = train_cnn(
        cfg, policy, steps=args.steps, batch=args.batch, lr=args.lr,
        seed=args.seed, calibration_batches=args.calibration_batches,
        telemetry_sink=sink, trace_path=args.trace or None)
    if args.trace:
        print(f"[cnn.train] trace: {args.trace} — load at "
              f"https://ui.perfetto.dev")
    for i, met in enumerate(history):
        if i % 10 == 0 or i == len(history) - 1:
            print(f"[cnn.train] step {i:4d} "
                  + " ".join(f"{k} {v:.4f}" for k, v in met.items()))
    print(f"[cnn.train] arch={cfg.name} policy={args.policy} "
          f"backend={args.backend} final_eval_acc={acc:.4f}")
    if sink is not None:
        sink.close()
    return acc


if __name__ == "__main__":
    main()
