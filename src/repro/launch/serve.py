"""Batched serving driver: quantized prefill + decode with static ranges.

In-hindsight ranges double as INFERENCE static quantization ranges: after
training (or a calibration pass) the per-site (qmin, qmax) state is frozen
and every activation quantizer runs single-pass static — the deployment
story of the paper carried to serving.  The KV cache is stored in
``cfg.cache_dtype`` (bf16 default; --int8-cache switches to the int8
hindsight-range cache, the beyond-paper option).

Example:
  PYTHONPATH=src python -m repro.launch.serve --arch starcoder2-3b --reduced \
      --batch 4 --prompt-len 32 --gen 16
"""
from __future__ import annotations

import argparse
import dataclasses
import time

import jax
import jax.numpy as jnp

from repro import checkpoint, configs, data, telemetry
from repro.core.policy import QuantPolicy
from repro.launch import compile_cache
from repro.models import model


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="starcoder2-3b")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--policy", default="hindsight",
                    choices=["hindsight", "fp32"])
    ap.add_argument("--int8-cache", action="store_true")
    ap.add_argument("--ckpt-dir", default="",
                    help="restore trained params + calibrated ranges")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--telemetry", default="",
                    help="write per-site prefill quantization health "
                         "(clip/SQNR/util) as JSONL to this path")
    ap.add_argument("--trace", default="", metavar="PATH",
                    help="export a Chrome-trace JSON of the serving "
                         "phases (prefill / per-step decode / telemetry) "
                         "to PATH — view at https://ui.perfetto.dev")
    args = ap.parse_args(argv)
    compile_cache.enable()
    tracer = telemetry.Tracer(enabled=bool(args.trace))

    cfg = configs.get_reduced(args.arch) if args.reduced \
        else configs.get(args.arch)
    if args.int8_cache:
        cfg = dataclasses.replace(cfg, cache_dtype="int8")
    policy = QuantPolicy.disabled() if args.policy == "fp32" \
        else QuantPolicy.w8a8g8()
    if args.telemetry:
        policy = policy.with_telemetry()

    params = model.init_params(jax.random.PRNGKey(args.seed), cfg)
    quant = model.init_quant_state(cfg, policy)
    if args.ckpt_dir:
        # A failed restore ends the run: serving random weights in place of
        # the asked-for checkpoint would look like success.
        latest = checkpoint.latest_step(args.ckpt_dir)
        if latest is None:
            raise SystemExit(f"[serve] no checkpoint in {args.ckpt_dir}")
        try:
            st = checkpoint.restore(args.ckpt_dir, latest,
                                    {"params": params, "quant": quant})
        except ValueError:
            if not policy.telemetry.enabled:
                raise
            # Pre-telemetry checkpoint (width-3 quant leaves): restore
            # the classic layout, then widen — ranges carry over.
            st = checkpoint.restore(
                args.ckpt_dir, latest,
                {"params": params, "quant": model.init_quant_state(cfg)})
            st["quant"] = telemetry.widen_state(st["quant"],
                                                policy.stat_width)
            print("[serve] migrated width-3 quant state to telemetry "
                  "layout")
        params, quant = st["params"], st["quant"]
        print(f"[serve] restored step {latest}")

    stream = data.for_arch(cfg, seq_len=args.prompt_len + args.gen,
                           global_batch=args.batch, seed=args.seed)
    batch = stream.batch(0)
    prompt = {k: (v[:, :args.prompt_len] if k in ("tokens",) else v)
              for k, v in batch.items() if k in ("tokens", "frames",
                                                 "patches")}
    cache_len = args.prompt_len + args.gen + (
        cfg.n_patches if cfg.family == "vlm" else 0)

    want_stats = bool(args.telemetry) and policy.telemetry.enabled
    prefill = jax.jit(lambda p, q, b: model.prefill(
        p, q, b, cfg, policy, cache_len=cache_len, return_stats=want_stats))
    # The caches are donated: each decode step writes one token into them,
    # and without donation the old and the new caches are live at once.
    decode = jax.jit(lambda p, q, t, pos, c: model.decode_step(
        p, q, t, pos, c, cfg, policy), donate_argnums=(4,))

    t0 = time.perf_counter()
    # The first prefill/decode call compiles — the trace shows it as one
    # long "prefill (compile+execute)" span, the decode steps as a span
    # per generated token.
    with tracer.span("prefill (compile+execute)", batch=args.batch,
                     prompt_len=args.prompt_len):
        if want_stats:
            logits, caches, prefill_stats = prefill(params, quant, prompt)
        else:
            logits, caches = prefill(params, quant, prompt)
            prefill_stats = None
        logits.block_until_ready()
    t_prefill = time.perf_counter() - t0
    finite = jnp.all(jnp.isfinite(logits))

    if prefill_stats is not None:
        with tracer.span("telemetry flush"):
            sink = telemetry.JsonlSink(args.telemetry, max_steps=1024)
            sink.write(0, telemetry.collect(prefill_stats))
            sink.close()
        print(f"[serve] prefill telemetry -> {args.telemetry} — render with "
              f"`python -m repro.telemetry.report {args.telemetry}`")

    pos0 = args.prompt_len + (cfg.n_patches if cfg.family == "vlm" else 0)
    tok = jnp.argmax(logits, -1)[:, None].astype(jnp.int32)
    out_tokens = [tok]
    t0 = time.perf_counter()
    with tracer.span("decode", steps=args.gen - 1):
        for i in range(args.gen - 1):
            with tracer.span("decode step" if i else
                             "decode step (compile)", pos=pos0 + i):
                pos = jnp.full((args.batch,), pos0 + i, jnp.int32)
                logits, caches = decode(params, quant, tok, pos, caches)
                finite = finite & jnp.all(jnp.isfinite(logits))
                tok = jnp.argmax(logits, -1)[:, None].astype(jnp.int32)
                if tracer.enabled:  # fence per-span only when tracing
                    tok.block_until_ready()
            out_tokens.append(tok)
    tok.block_until_ready()
    t_decode = time.perf_counter() - t0
    if not bool(finite):
        raise RuntimeError("[serve] non-finite logits in prefill or decode")

    gen = jnp.concatenate(out_tokens, axis=1)
    print(f"[serve] arch={cfg.name} policy={args.policy} "
          f"cache={cfg.cache_dtype}")
    print(f"[serve] prefill {args.batch}x{args.prompt_len}: "
          f"{t_prefill*1e3:.1f} ms")
    print(f"[serve] decode  {args.gen - 1} steps: {t_decode*1e3:.1f} ms "
          f"({(args.gen - 1) * args.batch / max(t_decode, 1e-9):.1f} tok/s)")
    print(f"[serve] sample tokens[0]: {gen[0][:12].tolist()}")
    print(f"[serve] logits finite in prefill and {args.gen - 1} decode steps")
    if args.trace:
        tracer.export(args.trace)
        print(f"[serve] trace: {args.trace} — load at "
              f"https://ui.perfetto.dev")
    return gen


if __name__ == "__main__":
    main()
