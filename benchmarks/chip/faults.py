"""Faults planted under a cell's timed path, and the 4-bit control.

Each takes the loaded program adapter (``programs/<program>.py``) and
breaks it in place.  The benchmark's tests plant them at a tiny size on
the CPU and see ``correct`` come out false; ``calibrate.py --fault`` reads
them on the chip at the cell's own size, for the limits' upper readings.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

tmap = jax.tree_util.tree_map


def state_unchanged(mod):
    """The step computes, but hands back the state it was given."""
    class Train(mod.Train):
        def __init__(self, *a, **k):
            super().__init__(*a, **k)
            real = self.step
            self.step = lambda st, b: (st, real(tmap(jnp.copy, st), b)[1])
    mod.Train = Train


def half_batch(mod):
    """The step sees the first half of the rows twice: the mean is taken
    over that half and the rest is left out."""
    class Train(mod.Train):
        def feed(self, batch):
            b = super().feed(batch)
            return tmap(lambda x: jnp.concatenate(
                [x[:x.shape[0] // 2]] * 2), b)
    mod.Train = Train


def token_altered(mod):
    """Each decode step's logits are shifted along the vocabulary, so the
    token it produces is another."""
    class Serve(mod.Serve):
        def __init__(self, *a, **k):
            super().__init__(*a, **k)
            real = self.decode
            self.decode = lambda *a: _roll(real(*a))
    mod.Serve = Serve


def _roll(out):
    logits, caches = out
    return jnp.roll(logits, 1, axis=-1), caches


def control(mod):
    """The program at 4 bits: the lower precision ``correct`` rejects."""
    low = mod._low_bits
    mod._low_bits = lambda policy, bits: low(policy, 4)
