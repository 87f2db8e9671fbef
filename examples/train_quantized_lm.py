"""End-to-end driver: train an LM with fully-quantized W8/A8/G8 training
and compare the loss curve against FP32 — the paper's Tables 3-4 protocol
on this framework's assigned workload.

CI preset (default) trains a reduced starcoder2 on CPU in ~2 minutes;
--preset full trains a ~110M-parameter model for a few hundred steps
(hours on CPU; the config is the point — on a v5e slice it is minutes).

    PYTHONPATH=src python examples/train_quantized_lm.py
    PYTHONPATH=src python examples/train_quantized_lm.py --preset full
"""
import argparse
import dataclasses

import jax
import numpy as np

from repro import configs, data
from repro.core.policy import QuantPolicy
from repro.optim import adamw
from repro.optim.schedules import cosine
from repro.runtime import steps as steps_mod


def run(policy_name: str, cfg, seq, batch, steps, seed=0):
    policy = (QuantPolicy.disabled() if policy_name == "fp32"
              else QuantPolicy.w8a8g8(act_kind=policy_name,
                                      grad_kind=policy_name))
    opt = adamw(weight_decay=0.01)
    state = steps_mod.init_train_state(jax.random.PRNGKey(seed), cfg, opt)
    n_params = sum(int(np.prod(l.shape)) for l in
                   jax.tree_util.tree_leaves(state["params"]))
    stream = data.for_arch(cfg, seq_len=seq, global_batch=batch, seed=seed)
    ts = jax.jit(steps_mod.make_train_step(
        cfg, policy, opt, cosine(3e-3, steps, warmup=steps // 10)))
    losses = []
    for i in range(steps):
        state, met = ts(state, stream.batch(i))
        losses.append(float(met["loss"]))
        if i % max(1, steps // 10) == 0:
            print(f"  [{policy_name:9s}] step {i:4d} loss {losses[-1]:.4f}")
    return losses, n_params


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--preset", default="ci", choices=["ci", "full"])
    args = ap.parse_args()

    if args.preset == "full":
        # ~110M params: 12L x 768 with a 32k vocab.
        cfg = dataclasses.replace(
            configs.get_reduced("starcoder2-3b"), n_layers=12, d_model=768,
            n_heads=12, n_kv=4, head_dim=64, d_ff=3072, vocab=32768,
            sliding_window=256, loss_chunk=64)
        seq, batch, steps = 256, 16, 300
    else:
        cfg = configs.get_reduced("starcoder2-3b")
        seq, batch, steps = 64, 8, 60

    print(f"== arch {cfg.name} (modified) seq={seq} batch={batch} "
          f"steps={steps}")
    curves = {}
    for pol in ("fp32", "hindsight"):
        curves[pol], n = run(pol, cfg, seq, batch, steps)
        print(f"{pol}: {n/1e6:.1f}M params, final loss "
              f"{np.mean(curves[pol][-5:]):.4f}")

    gap = abs(np.mean(curves["fp32"][-5:])
              - np.mean(curves["hindsight"][-5:]))
    print(f"\nFP32 vs W8A8G8-hindsight final-loss gap: {gap:.4f} "
          f"(paper: within ~0.5% accuracy on ImageNet-class tasks)")


if __name__ == "__main__":
    main()
