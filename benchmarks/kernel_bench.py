"""Kernel microbenchmark: the paper's single-pass-vs-two-pass dataflow.

Per tensor size, reports:
  * the HBM-traffic model of the fused static kernel vs the dynamic
    two-pass flow (the paper's Fig. 4 in bytes — static reads fp + writes
    int8 once; dynamic additionally writes + re-reads the fp accumulator),
  * measured XLA `bytes accessed` for the two compiled graphs — the
    STRUCTURAL proof that a dynamic estimator forces the extra
    materialization even under XLA fusion,
  * interpret-mode bit-exactness of the Pallas kernel vs its oracle.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import quant
from repro.core.quant import QuantSpec
from repro.kernels import ops, ref

from .common import env_metadata, report

SPEC = QuantSpec(bits=8, symmetric=False)

HEADER = ["kernel", "size", "model_static_B", "model_dynamic_B",
          "model_ratio", "xla_static_B", "xla_dynamic_B",
          "xla_ratio", "correctness"]


def traffic_model(n_elems: int):
    static = n_elems * (4 + 1)                 # read fp32, write int8
    dynamic = n_elems * (4 + 4 + 4 + 1)        # +write fp32, +read fp32
    return static, dynamic


def xla_bytes(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    from repro.launch import hlo_cost
    return hlo_cost.analyze(compiled.as_text())["bytes_accessed"]


def static_quant_graph(x, qmin, qmax):
    return quant.quantize(x, qmin, qmax, SPEC).astype(jnp.int8)


def dynamic_quant_graph(x):
    mn, mx = quant.tensor_minmax(x)
    return quant.quantize(x, mn, mx, SPEC).astype(jnp.int8)


def main(argv=None):
    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="CI-scale pass: one small size per kernel "
                         "(exercises the interpret-mode bit-exactness "
                         "checks without the large-tensor timings)")
    ap.add_argument("--out", default="",
                    help="also write the rows + env metadata as JSON "
                         "(e.g. BENCH_kernels.json — the committed "
                         "baseline for benchmarks/check_regression.py)")
    args = ap.parse_args(argv)

    sizes = (1 << 16,) if args.smoke else (1 << 16, 1 << 20, 1 << 22)
    mm_shapes = ((129, 300, 77),) if args.smoke else (
        (256, 256, 256), (384, 512, 640), (129, 300, 77))
    rows = []
    for n in sizes:
        shape = (n // 256, 256)
        x = jax.random.normal(jax.random.PRNGKey(0), shape)
        st_model, dy_model = traffic_model(n)
        st_meas = xla_bytes(static_quant_graph, x, jnp.float32(-3),
                            jnp.float32(3))
        dy_meas = xla_bytes(dynamic_quant_graph, x)
        q, mn, mx = ops.fused_quantize(x, -3.0, 3.0, spec=SPEC)
        qr, mnr, mxr = ref.ref_fused_quantize(x, jnp.float32(-3),
                                              jnp.float32(3), SPEC)
        d = np.abs(np.asarray(q).astype(int) - np.asarray(qr).astype(int))
        if d.max() == 0:
            verdict = "bit-exact"
        elif d.max() <= 1 and (d != 0).mean() < 1e-3:
            # round-half-even ties land one ulp apart between two
            # SEPARATELY compiled graphs (x/scale constant-folds
            # differently); the requant grid itself agrees.  The
            # order-pinned int8_matmul epilogue below stays bit-exact.
            verdict = f"ok(<=1-level ties: {(d != 0).sum()}/{d.size})"
        else:
            verdict = "MISMATCH"
        rows.append(["fused_quantize", n, st_model, dy_model,
                     f"{dy_model / st_model:.2f}x",
                     int(st_meas), int(dy_meas),
                     f"{dy_meas / max(st_meas, 1):.2f}x", verdict])

    # int8 conv via im2col onto the batched MXU matmul: bit-exactness vs
    # the int32 XLA conv oracle at MobileNetV2 block geometries
    # (pointwise expand / strided depthwise / pointwise project).
    conv_shapes = [
        # (N, H, W, Cin, KH, Cout, stride, groups) — MobileNetV2 blocks
        ("mbv2-expand-1x1", (2, 16, 16, 24, 1, 144, 1, 1)),
        ("mbv2-dw-3x3-s2", (2, 16, 16, 144, 3, 144, 2, 144)),
        ("mbv2-project-1x1", (2, 8, 8, 144, 1, 32, 1, 1)),
    ]
    if args.smoke:
        conv_shapes = [                      # CI scale: same geometry zoo
            ("mbv2-dw-3x3-s2", (2, 8, 8, 16, 3, 16, 2, 16)),
            ("mbv2-project-1x1", (2, 6, 6, 16, 1, 8, 1, 1))]
    for name, (n_, h, w_, cin, kh, cout, stride, g) in conv_shapes:
        xq = jax.random.randint(jax.random.PRNGKey(3), (n_, h, w_, cin), 0,
                                256).astype(jnp.uint8)
        wq = jax.random.randint(jax.random.PRNGKey(4),
                                (kh, kh, cin // g, cout), -127,
                                128).astype(jnp.int8)
        plan = ops.plan_conv(xq.shape, wq.shape, stride, "SAME", 1, g)
        y, mn, mx = ops.int8_conv_fp(xq, wq, jnp.float32(120.0),
                                     jnp.float32(2e-4), plan=plan)
        yr, mnr, mxr = ref.ref_int8_conv_fp(
            xq, wq, jnp.float32(120.0), jnp.float32(2e-4),
            stride=(stride, stride), padding="SAME", groups=g)
        exact = bool((np.asarray(y) == np.asarray(yr)).all()
                     and float(mn) == float(mnr) and float(mx) == float(mxr))
        elems = n_ * h * w_ * cin
        st = elems * (4 + 1)                   # fp read + int8 write (Fig. 4)
        dy = elems * (4 + 4 + 4 + 1)
        rows.append([f"int8_conv_fp[{name}]",
                     f"{n_}x{h}x{w_}x{cin}->k{kh}s{stride}g{g}x{cout}",
                     st, dy, f"{dy / st:.2f}x", "-", "-", "-",
                     "bit-exact" if exact else "MISMATCH"])

    # int8 flash attention: kernel-vs-oracle bit-exactness, one geometry
    # per mask mode (incl. a GQA broadcast + runtime kv_len bound).  The
    # traffic model is the flash claim: the fused kernel streams the
    # [S, Skv] score tile through VMEM (int8 q/k/v in, fp32 out), while
    # the dynamic two-pass fp path writes + re-reads it in HBM.
    from repro.kernels.int8_attention import make_schedule
    attn_shapes = [
        # (label, mode, sq, skv, hd, bq, bkv, groups, window, prefix, kvlen)
        ("causal", "causal", 32, 32, 16, 8, 8, 1, 0, 0, None),
        ("sliding-w16", "sliding", 64, 64, 16, 8, 8, 1, 16, 0, None),
        ("prefix-10", "prefix", 24, 24, 8, 8, 8, 1, 0, 10, None),
        ("cross-gqa", "cross", 16, 40, 8, 8, 16, 4, 0, 0, 33),
    ]
    if not args.smoke:
        attn_shapes.append(
            ("causal-large", "causal", 256, 256, 64, 64, 64, 1, 0, 0, None))
    for (name, mode, sq, skv, hd, bq, bkv, g, win, pfx, kvlen) in attn_shapes:
        sched = make_schedule(sq=sq, skv=skv, hd=hd, bq=bq, bkv=bkv,
                              groups=g, mode=mode, window=win,
                              prefix_len=pfx, sm_scale=hd ** -0.5)
        zb = 2
        bh = zb * g
        qk = jax.random.randint(jax.random.PRNGKey(5), (bh, sq, hd), 0,
                                256).astype(jnp.uint8)
        kk = jax.random.randint(jax.random.PRNGKey(6), (zb, skv, hd), -127,
                                128).astype(jnp.int8)
        vk = jax.random.randint(jax.random.PRNGKey(7), (zb, skv, hd), -127,
                                128).astype(jnp.int8)
        regs = jnp.asarray([[128.0, 1e-3 * sched.sm_scale, 1.0 / 255.0,
                             0.0, 2e-2 / 255.0, 0.0, 1.0, 0.0]], jnp.float32)
        kvl = jnp.asarray([[skv if kvlen is None else kvlen]], jnp.int32)
        out, ml, ps = ops.int8_attention_fp(qk, kk, vk, regs, kvl,
                                            sched=sched)
        ro, rml, rps = ref.ref_int8_attention(qk, kk, vk, regs, kvl,
                                              sched=sched)
        exact = all(bool((np.asarray(a) == np.asarray(b)).all())
                    for a, b in ((out, ro), (ml, rml), (ps, rps)))
        st = bh * sq * hd + 2 * zb * skv * hd + 4 * bh * sq * hd
        dy = 4 * (2 * bh * sq * hd + 2 * zb * skv * hd) \
            + 2 * 4 * bh * sq * skv
        rows.append([f"int8_attention[{name}]",
                     f"{bh}x{sq}x{skv}xh{hd}g{g}", st, dy,
                     f"{dy / st:.2f}x", "-", "-", "-",
                     "bit-exact" if exact else "MISMATCH"])

    # int8 matmul epilogue: correctness at MXU-aligned and ragged shapes
    for (m, k, n) in mm_shapes:
        xq = jax.random.randint(jax.random.PRNGKey(1), (m, k), 0,
                                256).astype(jnp.uint8)
        wq = jax.random.randint(jax.random.PRNGKey(2), (k, n), -127,
                                128).astype(jnp.int8)
        out = ops.int8_matmul_fused(xq, wq, 0.01, 120.0, 0.02, None,
                                    -2.0, 2.0, block=(128, 128, 128))
        r = ref.ref_int8_matmul_fused(
            xq, wq, jnp.float32(0.01), jnp.float32(120.0),
            jnp.float32(0.02), None, jnp.float32(-2.0), jnp.float32(2.0),
            SPEC)
        exact = bool((np.asarray(out[0]) == np.asarray(r[0])).all())
        st = m * k + k * n + m * n                       # int8 in/out
        dy = m * k + k * n + m * n * (4 + 4 + 1)
        rows.append(["int8_matmul_fused", f"{m}x{k}x{n}", st, dy,
                     f"{dy / st:.2f}x", "-", "-", "-",
                     "bit-exact" if exact else "MISMATCH"])
    report(rows, HEADER)
    if args.out:
        import json
        payload = {"meta": env_metadata(), "smoke": args.smoke,
                   "rows": [dict(zip(HEADER, [str(v) for v in r]))
                            for r in rows]}
        with open(args.out, "w") as f:
            json.dump(payload, f, indent=2)
        print(f"wrote {args.out}")
    return rows


if __name__ == "__main__":
    main()
