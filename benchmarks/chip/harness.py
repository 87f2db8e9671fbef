"""What every driver shares: the run's cell, host spans, the profiler
window and the device readings."""
from __future__ import annotations

import contextlib
import dataclasses
import glob
import os
import shutil
import sys
import tempfile
import time
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np

import trace_reduce


def span(name: str):
    """A host span on the profiler's clock (``bench:<name>``)."""
    return jax.profiler.TraceAnnotation(trace_reduce.HOST_PREFIX + name)


@dataclasses.dataclass
class Cell:
    """One run of one workload."""
    name: str
    cfg: dict
    traffic: dict
    limits: dict
    seed: int
    seconds: float
    trace: bool
    prog: Any                    # programs/<program>.py
    ref: Any                     # configs/<reference>.py
    devices: list
    started: float               # perf_counter at process start
    bits: int = 8                # 4: the program's lower-precision control
    setup_s: Optional[float] = None
    trace_dir: Optional[str] = None

    @property
    def key(self):
        return jax.random.PRNGKey(self.seed % 2 ** 32)

    def setup_done(self) -> None:
        self.setup_s = time.perf_counter() - self.started

    def window_seconds(self) -> float:
        """A traced run traces a stretch of the window and ends with it."""
        if self.trace:
            return min(self.seconds, self.traffic["trace_seconds"])
        return self.seconds

    @contextlib.contextmanager
    def tracer(self):
        """The window, under the profiler when the run is traced."""
        if not self.trace:
            yield
            return
        self.trace_dir = tempfile.mkdtemp(prefix="bench-trace-")
        # Device and host events only: Python's own calls are not traced,
        # and no HLO is written into the trace (the drivers pass it).
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.enable_hlo_proto = False
        jax.profiler.start_trace(self.trace_dir, profiler_options=opts)
        try:
            with span("window"):
                yield
        finally:
            jax.profiler.stop_trace()

    def trace_events(self, programs: dict) -> trace_reduce.Events:
        """The traced window's events; the trace is deleted once read."""
        try:
            path, = glob.glob(os.path.join(
                self.trace_dir, "**", "*.xplane.pb"), recursive=True)
            scopes = {name: trace_reduce.hlo_scopes(text)
                      for name, text in programs.items()}
            return trace_reduce.events_from_xplane(path, scopes)
        finally:
            shutil.rmtree(self.trace_dir, ignore_errors=True)


def hlo_text(jitted, *args) -> str:
    """Optimized HLO of a jitted function's program for these arguments
    (found in the compilation cache; nothing runs)."""
    return jitted.lower(*args).compile().as_text()


def module_name(hlo: str) -> str:
    first = hlo.split("\n", 1)[0].split()
    return first[1].rstrip(",") if len(first) > 1 else ""


def peak_bytes(devices: list) -> int:
    """The peak of the fullest chip: its buffers' peak
    (``peak_bytes_in_use``) and the peak of what the runtime reserves for
    the loaded programs' temporaries (``peak_bytes_reserved``), which
    ``peak_bytes_in_use`` does not count."""
    stats = [d.memory_stats() for d in devices]
    for s in stats:
        print("memory: " + ", ".join(f"{k} {s.get(k)}" for k in (
            "peak_bytes_in_use", "peak_bytes_reserved", "bytes_limit")),
            file=sys.stderr)
    return max(s["peak_bytes_in_use"] + s["peak_bytes_reserved"]
               for s in stats)


def norms_against_init(tree, key, init_leaf, combine) -> np.ndarray:
    """The 2-norm of ``combine(leaf, initial leaf)`` for every leaf of
    ``tree``, in ``tree_leaves`` order.  ``init_leaf(key, path)`` draws a
    leaf's initial value again; the leaves are drawn one after another, so
    that no more than one of them is held beside ``tree``."""
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    paths = tuple(tuple(k.key for k in path) for path, _ in flat)

    @jax.jit
    def norms(leaves, key):
        out, dep = [], jnp.zeros((), jnp.float32)
        for path, x in zip(paths, leaves):
            k, _ = jax.lax.optimization_barrier((key, dep))
            dep = jnp.sqrt(jnp.sum(jnp.square(combine(x, init_leaf(k, path)))))
            out.append(dep)
        return jnp.stack(out)

    return np.asarray(norms([x for _, x in flat], key))
