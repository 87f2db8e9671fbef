"""Driver of closed-loop serving mixes.

One client sends request after request: each is ``batch`` prompts of
``prompt`` tokens, prefilled at once, then ``gen`` greedy tokens decoded
through the cache.  A streaming server hands every token to its client,
so each step's tokens are fetched to the host; the gaps between those
fetches are the inter-token latencies.  Set-up serves one whole request
of its own, which compiles every shape the window uses.  The window runs
whole requests until ``--seconds`` have passed.

Once the window has closed and the program's state is freed, a sample of
the finished requests, drawn from the seed, is run through the plain
reference: the check is the widest gap by which a served token's logit
lies below the reference's best at that position.
"""
from __future__ import annotations

import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

import flops
import harness
import traffic as traffic_mod

WARMUP_REQUEST = 2 ** 30          # request index of set-up's own request


@jax.jit
def pick(logits):
    """Greedy choice: the highest logit of each row."""
    return jnp.argmax(logits, -1)[:, None].astype(jnp.int32)


def serve_request(sv, prompts, positions, times=None):
    """Serve one request; returns its tokens [B, gen] on the host."""
    with harness.span("prefill"):
        logits, caches = sv.prefill(sv.params, sv.quant, {"tokens": prompts})
        tok = pick(logits)
    with harness.span("fetch"):
        out = [np.asarray(tok)]
    if times is not None:
        times.append(time.perf_counter())
    for pos in positions:
        with harness.span("decode"):
            logits, caches = sv.decode(sv.params, sv.quant, tok, pos, caches)
            tok = pick(logits)
        with harness.span("fetch"):
            out.append(np.asarray(tok))
        if times is not None:
            times.append(time.perf_counter())
    return np.concatenate(out, axis=1)


def work(c: dict, t: dict, requests: int) -> dict:
    b, p, g = t["batch"], t["prompt"], t["gen"]
    cache_bytes = np.dtype(jnp.dtype(c["cache_dtype"])).itemsize
    ctxs = [p + 1 + i for i in range(g - 1)]
    return {
        "requests": requests, "decode_steps": requests * (g - 1),
        "serve_flops": requests * (flops.lm_prefill_flops(c, p, b) + sum(
            flops.lm_decode_flops(c, x, b) for x in ctxs)),
        "decode_contractions": requests * [
            (flops.lm_decode_flops(c, x, b),
             flops.lm_decode_bytes(c, x, b, cache_bytes)) for x in ctxs]}


def reference_gap(cell, prompts, served, chosen=None) -> float:
    """Widest gap, over the positions of the served tokens, between the
    reference's best logit and its logit of the token chosen there: the
    served token itself, or ``chosen`` (the control's choice at the same
    position of the same prompts and served tokens)."""
    ref, c = cell.ref, cell.cfg
    params = jax.jit(lambda k: ref.init_params(k, c))(cell.key)
    p = prompts.shape[1]
    seq = jnp.concatenate([prompts, jnp.asarray(served[:, :-1])], axis=1)
    tokens = jnp.asarray(served if chosen is None else chosen)

    @jax.jit
    def gap(w, s, tok):
        z = ref.logits_at(w, s, p - 1, c)
        gold = jnp.take_along_axis(z, tok[..., None], -1)[..., 0]
        return jnp.max(jnp.max(z, -1) - gold)

    return float(gap(params, seq, tokens))


def run(cell) -> dict:
    c, t = cell.cfg, cell.traffic
    sv = cell.prog.Serve(c, t, cell.key, cell.ref, bits=cell.bits)
    stream = traffic_mod.prompt_stream(c, t, cell.seed)
    positions = [jnp.full((t["batch"],), t["prompt"] + i, jnp.int32)
                 for i in range(t["gen"] - 1)]
    serve_request(sv, stream.batch(WARMUP_REQUEST)["tokens"], positions)
    cell.setup_done()

    served, gaps = [], []
    seconds = cell.window_seconds()
    with cell.tracer():
        t0 = time.perf_counter()
        while time.perf_counter() - t0 < seconds:
            times = []
            with harness.span("data"):
                prompts = stream.batch(len(served))["tokens"]
            served.append(serve_request(sv, prompts, positions, times))
            gaps += list(np.diff(times))
        window = time.perf_counter() - t0
    tokens = sum(s.size for s in served)
    out = {"attempted": len(served), "failed": 0,
           "e2e": {"serve_tokens_per_s": tokens / window,
                   "itl_ms_p95": float(np.percentile(gaps, 95)) * 1e3},
           "work": work(c, t, len(served))}
    if cell.trace:
        pr = stream.batch(0)["tokens"]
        out["hlo"] = [harness.hlo_text(sv.prefill, sv.params, sv.quant,
                                       {"tokens": pr})]
        logits, caches = sv.prefill(sv.params, sv.quant, {"tokens": pr})
        out["hlo"].append(harness.hlo_text(
            sv.decode, sv.params, sv.quant, pick(logits), positions[0],
            caches))
        del logits, caches
    out["memory_peak_bytes"] = harness.peak_bytes(cell.devices)
    del sv

    rng = np.random.default_rng(cell.seed)
    sample = rng.choice(len(served), size=min(t["check_requests"],
                                              len(served)), replace=False)
    t0 = time.perf_counter()
    worst = max(reference_gap(cell, stream.batch(int(r))["tokens"],
                              served[r]) for r in sample)
    print(f"reference: {len(sample)} requests in "
          f"{time.perf_counter() - t0:.1f} s", file=sys.stderr)
    out["checks"] = [(k, worst, cell.limits[k]) for k in cell.limits]
    return out
