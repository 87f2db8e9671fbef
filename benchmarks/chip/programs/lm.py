"""The system under test for decoder-only LM configurations.

Builds the program's own jitted entry points from ``configs/<name>.json``
and a traffic mix, with the benchmark's weights (``ref.init_params``) in
the program's state:

* ``train``: ``repro.launch.train.jit_train_step`` (the step the training
  driver runs, train state donated) and its state;
* ``serve``: ``repro.models.model.prefill`` and ``decode_step``, jitted as
  ``repro.launch.serve`` jits them (caches donated), with its policy.

``Serve(bits=4)`` builds the serving entry points with 4-bit weight,
activation and gradient quantizers: the control that ``correct`` has to
reject.  Training has no such knob: the program's own 4-bit step does not
fit one chip at the cell's size, so its control is the reference at 4 bits
(``drivers/train.py`` ``reference_readings``).
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

import harness
from ref_train import leaf_norms

# Keys of configs/<name>.json that must equal the registered config's.
SIZE_KEYS = ("n_layers", "d_model", "n_heads", "n_kv", "head_dim", "d_ff",
             "vocab", "sliding_window", "rope_theta", "norm_kind",
             "use_bias", "mlp_kind", "tie_embeddings", "param_dtype",
             "compute_dtype", "cache_dtype")


def arch_config(c: dict):
    """The program's registered config, checked against the file."""
    from repro import configs
    cfg = configs.get(c["registered_as"])
    differ = {k: (getattr(cfg, k), c[k]) for k in SIZE_KEYS
              if getattr(cfg, k) != c[k]}
    if differ:
        raise ValueError(f"{c['name']}: registered config differs from the "
                         f"benchmark's file (program, file): {differ}")
    return cfg


def _low_bits(policy, bits: int):
    if bits == 8:
        return policy
    from repro.core.quant import QuantSpec
    return dataclasses.replace(
        policy, weight_spec=QuantSpec(bits, symmetric=True),
        act_spec=QuantSpec(bits), grad_spec=QuantSpec(bits, stochastic=True))


def _check_layout(cfg, c, ref, key):
    from repro.models import model
    ours = jax.eval_shape(lambda k: ref.init_params(k, c), key)
    theirs = jax.eval_shape(lambda k: model.init_params(k, cfg), key)
    if jax.tree_util.tree_structure(ours) != \
            jax.tree_util.tree_structure(theirs) or any(
            a.shape != b.shape or a.dtype != b.dtype for a, b in zip(
                jax.tree_util.tree_leaves(ours),
                jax.tree_util.tree_leaves(theirs))):
        raise ValueError("the benchmark's weights do not have the layout of "
                         "the program's parameter tree")


class Train:
    """``state``, ``step(state, batch)`` and the readings of its state."""

    def __init__(self, c: dict, traffic: dict, key, ref):
        from repro.launch import train as launch_train
        from repro.models import model

        cfg = arch_config(c)
        _check_layout(cfg, c, ref, key)
        o = traffic["optimizer"]
        args = launch_train.parse_args([
            "--policy", traffic["policy"], "--backend", traffic["backend"],
            "--optimizer", o["kind"], "--lr", str(o["lr"]),
            "--steps", str(o["total_steps"]),
            "--grad-accum", str(traffic["microbatches"]),
            "--seq", str(traffic["seq"]), "--batch", str(traffic["batch"])])
        policy = launch_train.build_policy(args.policy, args)
        opt, self.step = launch_train.jit_train_step(cfg, policy, args)
        self.b1 = o["b1"]

        @jax.jit
        def init(k):
            params = ref.init_params(k, c)
            return {"params": params, "opt": opt.init(params),
                    "quant": model.init_quant_state(cfg, policy),
                    "step": jnp.zeros((), jnp.int32)}

        self.state = init(key)
        self.init_leaf = lambda k, path: ref.init_leaf(k, c, path)
        self.key = key

    @staticmethod
    def feed(batch: dict) -> dict:
        return dict(batch, mask=jnp.ones(batch["labels"].shape, jnp.float32))

    def first_grad_norms(self, state) -> np.ndarray:
        """After one AdamW step m = (1 - b1) g: the clipped first gradient.
        The norms are taken of m in one program and then scaled, so that no
        scaled copy of m is held beside the state."""
        return np.asarray(leaf_norms(state["opt"]["m"])) / (1 - self.b1)

    def change_norms(self, state) -> np.ndarray:
        return harness.norms_against_init(state["params"], self.key,
                                          self.init_leaf, jnp.subtract)


class Serve:
    """``prefill(params, quant, batch)`` and ``decode(params, quant, token,
    pos, caches)`` with the served policy, and their state."""

    def __init__(self, c: dict, traffic: dict, key, ref, bits: int = 8):
        from repro.core.policy import QuantPolicy
        from repro.models import model

        cfg = arch_config(c)
        _check_layout(cfg, c, ref, key)
        policy = _low_bits(QuantPolicy.w8a8g8(), bits)
        cache_len = traffic["prompt"] + traffic["gen"]

        def prefill_step(p, q, b):
            return model.prefill(p, q, b, cfg, policy, cache_len=cache_len)

        def decode_step(p, q, t, pos, caches):
            return model.decode_step(p, q, t, pos, caches, cfg, policy)

        self.prefill = jax.jit(prefill_step)
        self.decode = jax.jit(decode_step, donate_argnums=(4,))
        self.params = jax.jit(lambda k: ref.init_params(k, c))(key)
        self.quant = model.init_quant_state(cfg, policy)
