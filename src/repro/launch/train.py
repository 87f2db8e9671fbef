"""End-to-end training driver with fault tolerance.

Features exercised here (single-host; the mechanisms are what a multi-host
deployment needs):

  * auto-resume: restores the latest checkpoint in --ckpt-dir (params,
    optimizer, QUANT RANGES, step) and continues bit-exactly,
  * periodic atomic checkpoints (--ckpt-every, keep-last-k),
  * preemption handling: SIGTERM/SIGINT trigger a final checkpoint before
    exit (the TPU-pod preemption pattern),
  * straggler watchdog: a heartbeat thread logs step-latency outliers
    (> --straggler-factor x trailing median) — on a real cluster this is
    the signal that triggers hot-spare swap / elastic down-scale,
  * metrics JSONL log for the benchmark harness,
  * performance observability (--trace): every step is split into
    data / compile / execute / telemetry / checkpoint phases by a
    repro.telemetry.trace.StepTimer; the span timeline exports as
    Chrome-trace JSON (load it at https://ui.perfetto.dev) and each
    step's phase breakdown rides the telemetry JSONL stream as a
    "perf" record (render with `repro.telemetry.report --perf`).

Examples:
  PYTHONPATH=src python -m repro.launch.train --arch starcoder2-3b --reduced \
      --steps 200 --batch 8 --seq 64 --policy hindsight
  PYTHONPATH=src python -m repro.launch.train ... --resume --ckpt-dir /tmp/ck
"""
from __future__ import annotations

import argparse
import json
import os
import signal
import statistics
import threading

import jax
import jax.numpy as jnp

from repro import checkpoint, configs, data, telemetry
from repro.core.estimators import ALL_ESTIMATORS
from repro.core.policy import QuantPolicy
from repro.launch import compile_cache
from repro.optim import adamw, sgdm
from repro.optim.schedules import cosine
from repro.runtime import steps as steps_mod


def build_policy(kind: str, args=None) -> QuantPolicy:
    if kind == "fp32":
        policy = QuantPolicy.disabled()
    else:
        assert kind in ALL_ESTIMATORS, kind
        policy = QuantPolicy.w8a8g8(act_kind=kind, grad_kind=kind)
    if args is not None and args.telemetry:
        policy = policy.with_telemetry(
            guard=args.guard, clip_threshold=args.guard_threshold,
            patience=args.guard_patience, widen_factor=args.guard_widen,
            mode=args.guard_mode)
    if args is not None and args.backend != policy.backend:
        # Raises with a clear message for illegal combinations (dynamic
        # estimator or dynamic-mode guard with backend='fused').
        policy = policy.with_backend(args.backend)
    return policy


class Watchdog:
    """Step-latency heartbeat: flags stragglers for the cluster scheduler."""

    def __init__(self, factor: float = 3.0, window: int = 32):
        self.durations: list = []
        self.factor = factor
        self.window = window
        self.flagged = 0

    def step(self, dt: float, step: int):
        hist = self.durations[-self.window:]
        if len(hist) >= 8:
            med = statistics.median(hist)
            if dt > self.factor * med:
                self.flagged += 1
                print(f"[watchdog] step {step}: {dt*1e3:.0f}ms "
                      f"(median {med*1e3:.0f}ms) — straggler suspected; "
                      f"a production deployment would alert the scheduler")
        self.durations.append(dt)


def jit_train_step(cfg, policy: QuantPolicy, args):
    """``(optimizer, jitted step)`` as ``main`` runs them.

    The step donates the train state: without donation the old and the new
    state (params, AdamW moments, quant ranges) are live at once, which
    alone takes 15.3 GiB of a 16 GiB TPU v5e at starcoder2-3b widths and
    4 layers (compiled for a v5e at batch 1 x 4096).
    """
    opt = adamw() if args.optimizer == "adamw" else sgdm(momentum=0.9)
    sched = cosine(args.lr, args.steps, warmup=min(20, args.steps // 10))
    step = jax.jit(steps_mod.make_train_step(
        cfg, policy, opt, sched, grad_accum=args.grad_accum),
        donate_argnums=(0,))
    return opt, step


def parse_args(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="starcoder2-3b")
    ap.add_argument("--reduced", action="store_true",
                    help="use the smoke-scale config (CPU-friendly)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--optimizer", default="adamw", choices=["adamw", "sgdm"])
    ap.add_argument("--policy", default="hindsight",
                    choices=["hindsight", "current", "running", "dsgc",
                             "fixed", "fp32"])
    ap.add_argument("--backend", default="simulated",
                    choices=["simulated", "fused"],
                    help="execution backend for the quantization sites: "
                         "'simulated' = jnp fake-quant, 'fused' = the "
                         "Pallas single-pass kernels (compiled on a TPU, "
                         "interpreted elsewhere; requires a fully-static "
                         "--policy, i.e. hindsight or fixed)")
    ap.add_argument("--grad-accum", type=int, default=1)
    ap.add_argument("--ckpt-dir", default="")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--keep-last", type=int, default=3)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--log", default="")
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--straggler-factor", type=float, default=3.0)
    ap.add_argument("--telemetry", action="store_true",
                    help="per-site quantization health telemetry "
                         "(clip rate / SQNR / drift; repro.telemetry)")
    ap.add_argument("--telemetry-dir", default="",
                    help="directory for the telemetry JSONL ring log "
                         "(default: --ckpt-dir or cwd)")
    ap.add_argument("--telemetry-every", type=int, default=1,
                    help="collect/log telemetry every N steps")
    ap.add_argument("--telemetry-keep", type=int, default=1024,
                    help="JSONL ring size in steps")
    ap.add_argument("--guard", action="store_true",
                    help="arm the overflow guard (implies --telemetry state)")
    ap.add_argument("--guard-threshold", type=float, default=0.01,
                    help="clip-rate threshold that counts as unhealthy")
    ap.add_argument("--guard-patience", type=int, default=3,
                    help="consecutive unhealthy steps before the guard acts")
    ap.add_argument("--guard-widen", type=float, default=1.5,
                    help="range expansion factor in widen mode")
    ap.add_argument("--guard-mode", default="widen",
                    choices=list(telemetry.GUARD_MODES))
    ap.add_argument("--trace", default="", metavar="PATH",
                    help="export a Chrome-trace JSON of the step phases "
                         "(data/compile/execute/telemetry/checkpoint) to "
                         "PATH — viewable at https://ui.perfetto.dev; "
                         "tracing is host-side only and never changes the "
                         "computation")
    args = ap.parse_args(argv)
    if args.guard:
        args.telemetry = True
    return args


def main(argv=None):
    args = parse_args(argv)
    compile_cache.enable()
    cfg = configs.get_reduced(args.arch) if args.reduced \
        else configs.get(args.arch)
    policy = build_policy(args.policy, args)
    opt, train_step = jit_train_step(cfg, policy, args)

    state = steps_mod.init_train_state(jax.random.PRNGKey(args.seed), cfg,
                                       opt, policy)
    start = 0
    if args.resume and args.ckpt_dir:
        latest = checkpoint.latest_step(args.ckpt_dir)
        if latest is not None:
            try:
                state = checkpoint.restore(args.ckpt_dir, latest, state)
            except ValueError:
                if not policy.telemetry.enabled:
                    raise
                # Pre-telemetry checkpoint (width-3 quant leaves): restore
                # against the classic template, then widen in place — the
                # ranges carry over, the counters start at zero.
                legacy = dict(state)
                legacy["quant"] = steps_mod.model.init_quant_state(cfg)
                legacy = checkpoint.restore(args.ckpt_dir, latest, legacy)
                legacy["quant"] = telemetry.widen_state(
                    legacy["quant"], policy.stat_width)
                state = legacy
                print("[train] migrated width-3 quant state to telemetry "
                      "layout")
            start = int(latest)
            print(f"[train] resumed from step {start}")

    stream = data.for_arch(cfg, seq_len=args.seq, global_batch=args.batch,
                           seed=args.seed)

    stop = {"now": False}

    def _sig(_signum, _frame):
        stop["now"] = True
    signal.signal(signal.SIGTERM, _sig)
    signal.signal(signal.SIGINT, _sig)

    wd = Watchdog(args.straggler_factor)
    logf = open(args.log, "a") if args.log else None

    tele_sink = None
    tele_events = None
    if args.telemetry and policy.telemetry.enabled:
        tdir = args.telemetry_dir or args.ckpt_dir or "."
        tpath = os.path.join(tdir, "telemetry.jsonl")
        tele_sink = telemetry.JsonlSink(tpath, max_steps=args.telemetry_keep)
        tele_events = telemetry.GuardEventDetector(policy.telemetry, policy)
        print(f"[train] telemetry -> {tpath} "
              f"(guard={'on' if policy.telemetry.guard else 'off'}, "
              f"mode={policy.telemetry.mode})")

    tracer = telemetry.Tracer(enabled=bool(args.trace))
    timer = telemetry.StepTimer(tracer)
    tokens_per_step = args.batch * args.seq

    for step in range(start, args.steps):
        records = events = None
        with timer.step(step) as st:
            with st.phase("data"):
                batch = stream.batch(step)
            with st.execute():  # "compile" phase on the jit's first call
                state, met = train_step(state, batch)
                met = {k: float(v) for k, v in met.items()}  # fences
            if tele_sink is not None and (step % args.telemetry_every == 0
                                          or step == args.steps - 1):
                with st.phase("telemetry"):
                    records = telemetry.collect(state["quant"])
                    events = tele_events.update(step, records)
                for ev in events:
                    tracer.instant(f"guard:{ev['action']}", site=ev["site"])
                    print(f"[guard] step {step}: {ev['action']} @ "
                          f"{ev['site']} {ev['old']} -> {ev['new']} "
                          f"(clip {100 * ev['clip_rate']:.2f}%)")
            should_ckpt = args.ckpt_dir and (
                (step + 1) % args.ckpt_every == 0 or stop["now"]
                or step == args.steps - 1)
            if should_ckpt:
                with st.phase("checkpoint"):
                    path = checkpoint.save(args.ckpt_dir, step + 1, state,
                                           keep_last=args.keep_last)
                print(f"[train] checkpoint @ {step + 1}: {path}")

        # The watchdog watches the hot path (data + device step), not the
        # telemetry/checkpoint epilogue — same semantics as before tracing.
        phases = timer.last["phases"]
        dt = (phases.get("data", 0.0) + phases.get("compile", 0.0)
              + phases.get("execute", 0.0)) / 1e3
        wd.step(dt, step)

        if step % args.log_every == 0 or step == args.steps - 1:
            print(f"[train] step {step:5d} loss {met['loss']:.4f} "
                  f"nll {met.get('nll', 0):.4f} lr {met['lr']:.2e} "
                  f"{dt*1e3:.0f}ms")
        if logf:
            logf.write(json.dumps({"step": step, "dt": dt, **met,
                                   "phases_ms": phases}) + "\n")
            logf.flush()
        if records is not None:
            tele_sink.write(step, records, events,
                            perf=timer.perf_record(items=tokens_per_step,
                                                   unit="tokens"))
        if stop["now"]:
            print("[train] preemption signal received — exiting cleanly")
            break

    if logf:
        logf.close()
    if tele_sink is not None:
        tele_sink.close()
        print(f"[train] telemetry log: {tele_sink.path} — render with "
              f"`python -m repro.telemetry.report {tele_sink.path}` "
              f"(--perf for the step-phase breakdown)")
    if args.trace:
        tracer.export(args.trace)
        print(f"[train] trace: {args.trace} — load at "
              f"https://ui.perfetto.dev (or chrome://tracing)")
    return state


if __name__ == "__main__":
    main()
