"""The system under test for the paper's CNN configurations.

``Train`` builds ``jax.jit(repro.cnn.train.make_cnn_train_step(...))`` as
``repro.cnn.train.train_cnn`` does (SGD with momentum and weight decay, a
cosine schedule with warm-up, activation ranges calibrated on a few
batches first), with the benchmark's weights in its state.  ``bits=4``
builds the same step with 4-bit quantizers: the control that ``correct``
has to reject.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

import harness
from lm import _low_bits

SIZE_KEYS = ("arch", "width", "image_size", "channels", "num_classes")


def cnn_config(c: dict):
    from repro.cnn import models
    cfg = {m.name: m for m in (models.MOBILENETV2_TINY,)}[c["registered_as"]]
    differ = {k: (getattr(cfg, k), c[k]) for k in SIZE_KEYS
              if getattr(cfg, k) != c[k]}
    if differ or [list(p) for p in models._MBV2_PLAN] != c["plan"]:
        raise ValueError(f"{c['name']}: the program's model differs from "
                         f"the benchmark's file: {differ}")
    return cfg


class Train:
    def __init__(self, c: dict, traffic: dict, key, ref, bits: int = 8,
                 stream=None):
        from repro.cnn import models
        from repro.cnn import train as cnn_train
        from repro.core.policy import QuantPolicy
        from repro.optim import sgdm
        from repro.optim.schedules import cosine

        cfg = cnn_config(c)
        ours = jax.eval_shape(lambda k: ref.init_params(k, c), key)
        theirs = jax.eval_shape(lambda k: models.init(k, cfg), key)
        if jax.tree_util.tree_structure(ours) != \
                jax.tree_util.tree_structure(theirs):
            raise ValueError("the benchmark's weights do not have the "
                             "layout of the program's parameter tree")
        o = traffic["optimizer"]
        policy = QuantPolicy.w8a8g8(act_kind=traffic["policy"],
                                    grad_kind=traffic["policy"])
        policy = _low_bits(policy.with_backend(traffic["backend"]), bits)
        opt = sgdm(momentum=o["momentum"], weight_decay=o["weight_decay"])
        sched = cosine(o["lr"], o["total_steps"], warmup=o["warmup"])
        self.step = jax.jit(cnn_train.make_cnn_train_step(
            cfg, policy, opt, sched, clip_norm=o["clip_norm"]))
        self.wd = o["weight_decay"]
        self.init_leaf = lambda k, path: ref.init_leaf(k, c, path)
        self.key = key
        params, bn = jax.jit(lambda k: ref.init_params(k, c))(key)
        quant = models.init_sites(cfg, policy)
        if traffic.get("calibration_batches"):
            quant = cnn_train.calibrate_cnn(cfg, params, bn, quant, policy,
                                            stream,
                                            traffic["calibration_batches"])
        self.state = {"params": params, "bn": bn, "opt": opt.init(params),
                      "quant": quant, "step": jnp.zeros((), jnp.int32)}

    @staticmethod
    def feed(batch: dict) -> dict:
        return batch

    def first_grad_norms(self, state) -> np.ndarray:
        """After one step m = g + wd p0: the clipped first gradient."""
        return harness.norms_against_init(state["opt"]["m"], self.key,
                                          self.init_leaf,
                                          lambda m, p: m - self.wd * p)

    def change_norms(self, state) -> np.ndarray:
        return harness.norms_against_init(state["params"], self.key,
                                          self.init_leaf, jnp.subtract)
