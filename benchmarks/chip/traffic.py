"""The one generator of inputs; every traffic mix is a data file it reads.

``traffic/<mix>.json`` names the driver that runs it (``"driver"``) and the
parameters of its inputs.  Everything is drawn from the run's ``--seed``
on the device, so two runs of one seed see the same inputs, and every seed
sees the same sizes.

* Token streams: a fixed random Markov chain over the vocabulary (each
  token has ``branch`` successors), one walk per row from a random start.
  A model can learn it, so the loss moves as it would on text.  The same
  construction as the program's ``data.LMStream``, kept here so that the
  benchmark's inputs cannot change with the program.
* Image streams: a class-dependent fixed pattern plus Gaussian noise, as
  the program's ``data.ImageStream`` draws them.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


def seed_key(seed: int, salt: int):
    """A PRNG key from ``--seed`` (any whole number) and a stream salt."""
    return jax.random.fold_in(jax.random.PRNGKey(seed % 2 ** 32), salt)


class TokenStream:
    """``batch(i)`` -> {"tokens", "labels"}: ``rows`` walks of ``length``
    tokens and the token that follows each."""

    def __init__(self, seed: int, vocab: int, rows: int, length: int,
                 branch: int = 4):
        self.table = jax.jit(lambda k: jax.random.randint(
            k, (vocab, branch), 0, vocab, jnp.int32))(seed_key(seed, 1))
        self.key = seed_key(seed, 2)
        self.shape = (rows, length, vocab, branch)

    @functools.partial(jax.jit, static_argnums=(0,))
    def _gen(self, table, key, i):
        rows, length, vocab, branch = self.shape
        k0, k1 = jax.random.split(jax.random.fold_in(key, i))
        start = jax.random.randint(k0, (rows,), 0, vocab)
        choice = jax.random.randint(k1, (length, rows), 0, branch)

        def walk(tok, ch):
            nxt = table[tok, ch]
            return nxt, nxt

        _, seq = jax.lax.scan(walk, start, choice)
        seq = jnp.concatenate([start[None], seq], axis=0).T   # [rows, L+1]
        return {"tokens": seq[:, :length], "labels": seq[:, 1:]}

    def batch(self, i: int) -> dict:
        return self._gen(self.table, self.key, jnp.int32(i))


class ImageStream:
    """``batch(i)`` -> {"images" f32[B,H,W,C], "labels" i32[B]}."""

    def __init__(self, seed: int, num_classes: int, size: int,
                 channels: int, rows: int):
        self.basis = jax.jit(lambda k: jax.random.normal(
            k, (num_classes, size, size, channels)))(seed_key(seed, 3))
        self.key = seed_key(seed, 4)
        self.shape = (rows, num_classes, size, channels)

    @functools.partial(jax.jit, static_argnums=(0,))
    def _gen(self, basis, key, i):
        rows, num_classes, size, channels = self.shape
        kl, kn = jax.random.split(jax.random.fold_in(key, i))
        labels = jax.random.randint(kl, (rows,), 0, num_classes)
        noise = jax.random.normal(kn, (rows, size, size, channels))
        return {"images": (0.6 * basis[labels] + noise).astype(jnp.float32),
                "labels": labels}

    def batch(self, i: int) -> dict:
        return self._gen(self.basis, self.key, jnp.int32(i))


def train_stream(cfg: dict, traffic: dict, seed: int):
    """The stream of training batches a mix asks for."""
    if traffic["inputs"] == "tokens":
        return TokenStream(seed, cfg["vocab"], traffic["batch"],
                           traffic["seq"], traffic.get("branch", 4))
    if traffic["inputs"] == "images":
        return ImageStream(seed, cfg["num_classes"], cfg["image_size"],
                           cfg["channels"], traffic["batch"])
    raise ValueError(f"unknown inputs {traffic['inputs']!r}")


def prompt_stream(cfg: dict, traffic: dict, seed: int) -> TokenStream:
    """Request ``i`` of a serving mix: ``batch`` prompts of ``prompt``
    tokens (the ``tokens`` of batch ``i``)."""
    return TokenStream(seed, cfg["vocab"], traffic["batch"],
                       traffic["prompt"], traffic.get("branch", 4))
