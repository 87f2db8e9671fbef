"""Paper Tables 1-3 (+ Table 4 analogue): range-estimator comparisons.

Structure mirrors the paper exactly:

  Table 1  gradient-only quantization   (forward FP, Q_G under study)
  Table 2  activation-only quantization (backward FP, Q_Y under study)
  Table 3  fully quantized W8/A8/G8     (both quantizers = same estimator)
  Table 4  the same fully-quantized study on the assigned LM workload
           (the paper's ImageNet table carried to this framework's domain)

Estimators: current min-max, running min-max, DSGC (gradient tables),
in-hindsight min-max; FP32 reference row.  Multiple seeds, mean +/- std.

Scale: synthetic data + reduced widths by default (CPU container — see
DESIGN.md §6); the COMPARISON between estimators is the paper's claim
under test, and that is scale-transportable.
"""
from __future__ import annotations

import argparse

from repro.core.policy import QuantPolicy
from repro.cnn import bench_config, train_cnn

from .common import mean_std, report


def _policy(table: str, kind: str) -> QuantPolicy:
    if kind == "fp32":
        return QuantPolicy.disabled()
    if table == "grad":       # Table 1: only gradients quantized
        return QuantPolicy.grad_only(kind)
    if table == "act":        # Table 2: only activations quantized
        return QuantPolicy.act_only(kind)
    return QuantPolicy.w8a8g8(act_kind="current" if kind == "dsgc" else kind,
                              grad_kind=kind)


def cnn_study(table: str, arch: str, estimators, *, steps, batch, width,
              image_size, classes, seeds):
    rows = []
    for kind in estimators:
        accs = []
        for seed in range(seeds):
            cfg = bench_config(arch, num_classes=classes, width=width,
                               image_size=image_size)
            acc, _ = train_cnn(cfg, _policy(table, kind), steps=steps,
                               batch=batch, lr=0.05, seed=seed)
            accs.append(acc * 100)
        m, s = mean_std(accs)
        static = "yes" if kind in ("hindsight", "fixed") else (
            "n.a." if kind == "fp32" else "no")
        rows.append([f"table_{table}", arch, kind, static,
                     f"{m:.2f}", f"{s:.2f}"])
    return rows


def lm_study(estimators, *, steps, seeds, arch="starcoder2-3b"):
    import jax
    import numpy as np
    from repro import configs, data
    from repro.optim import adamw
    from repro.optim.schedules import constant
    from repro.runtime import steps as steps_mod

    rows = []
    for kind in estimators:
        finals = []
        for seed in range(seeds):
            cfg = configs.get_reduced(arch)
            opt = adamw(weight_decay=0.0)
            state = steps_mod.init_train_state(jax.random.PRNGKey(seed),
                                               cfg, opt)
            stream = data.for_arch(cfg, seq_len=32, global_batch=8,
                                   seed=seed)
            ts = jax.jit(steps_mod.make_train_step(
                cfg, _policy("full", kind), opt, constant(3e-3)))
            losses = []
            for i in range(steps):
                state, met = ts(state, stream.batch(i))
                losses.append(float(met["loss"]))
            finals.append(float(np.mean(losses[-5:])))
        m, s = mean_std(finals)
        static = "yes" if kind == "hindsight" else (
            "n.a." if kind == "fp32" else "no")
        rows.append(["table4_lm", arch, kind, static, f"{m:.4f}",
                     f"{s:.4f}"])
    return rows


def attn_site_study(estimators, *, steps, seeds):
    """Per-site estimator sweep over the attention core's quant sites.

    One GQA attention layer trained for ``steps`` toy steps per estimator;
    reports the final loss per estimator plus, for the static hindsight
    run, one row per core site (q/k/v logits, softmax probabilities) with
    its learned EMA range — the sites the int8 flash kernel consumes
    (``backend.attention``).  For the per-site rows the metric columns
    carry [range_lo, range_hi] instead of [mean, std]."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core import qlinear
    from repro.models import attention as attn_mod

    n_heads, n_kv, head_dim, d_model, seq, batch = 8, 2, 16, 64, 32, 4
    rows, site_rows = [], []
    for kind in estimators:
        policy = _policy("full", kind)
        finals = []
        sites = None
        for seed in range(seeds):
            params = attn_mod.init_attention(
                jax.random.PRNGKey(seed), d_model, n_heads, n_kv, head_dim,
                use_bias=False)
            sites = attn_mod.init_attention_sites()

            @jax.jit
            def one(params, sites, x, step):
                def loss_fn(p):
                    y, ns, _ = attn_mod.attention_layer(
                        p, sites, x, n_heads=n_heads, n_kv=n_kv,
                        head_dim=head_dim, mode="causal", policy=policy,
                        seed=jnp.int32(0), step=step)
                    return jnp.mean(y ** 2), ns
                (loss, ns), grads = jax.value_and_grad(
                    loss_fn, has_aux=True)(params)
                new_params = jax.tree_util.tree_map(
                    lambda p, g: p - 3e-3 * g, params, grads)
                return loss, new_params, qlinear.update_quant_state(
                    policy, sites, ns)

            losses = []
            for i in range(steps):
                x = jax.random.normal(jax.random.PRNGKey(1000 + i),
                                      (batch, seq, d_model), jnp.float32)
                loss, params, sites = one(params, sites, x, jnp.int32(i))
                losses.append(float(loss))
            finals.append(float(np.mean(losses[-5:])))
        m, s = mean_std(finals)
        static = "yes" if kind == "hindsight" else (
            "n.a." if kind == "fp32" else "no")
        rows.append(["table_attn_core", "attn-layer", kind, static,
                     f"{m:.6f}", f"{s:.6f}"])
        if kind == "hindsight" and sites is not None:
            for name in ("q", "k", "v", "p"):
                leaf = np.asarray(sites["core"][name]["act"])
                site_rows.append(["attn_site_range", f"core.{name}", kind,
                                  "yes", f"{leaf[0]:.4f}", f"{leaf[1]:.4f}"])
    return rows + site_rows


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--table", default="all",
                    choices=["all", "1", "2", "3", "4", "attn"])
    ap.add_argument("--full", action="store_true",
                    help="larger widths/steps/seeds (slow)")
    args = ap.parse_args(argv)

    if args.full:
        kw = dict(steps=120, batch=32, width=0.5, image_size=32, classes=10,
                  seeds=3)
        lm_kw = dict(steps=80, seeds=3)
    else:
        kw = dict(steps=20, batch=16, width=0.25, image_size=16, classes=4,
                  seeds=2)
        lm_kw = dict(steps=30, seeds=2)

    grad_est = ["fp32", "current", "running", "dsgc", "hindsight"]
    act_est = ["fp32", "current", "running", "hindsight"]
    rows = []
    if args.table in ("all", "1"):
        rows += cnn_study("grad", "resnet18", grad_est, **kw)
    if args.table in ("all", "2"):
        rows += cnn_study("act", "resnet18", act_est, **kw)
    if args.table in ("all", "3"):
        for arch in ["resnet18", "vgg16", "mobilenetv2"]:
            rows += cnn_study("full", arch,
                              ["fp32", "current", "running", "hindsight"],
                              **kw)
    if args.table in ("all", "4"):
        rows += lm_study(["fp32", "current", "running", "hindsight"],
                         **lm_kw)
    if args.table in ("all", "attn"):
        rows += attn_site_study(["fp32", "current", "running", "hindsight"],
                                **lm_kw)
    report(rows, ["table", "arch", "estimator", "static", "metric_mean",
                  "metric_std"])
    return rows


if __name__ == "__main__":
    main()
