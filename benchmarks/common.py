"""Shared helpers for the paper-table benchmarks.

Every benchmark runs at a CPU-feasible scale by default (reduced widths /
few steps / synthetic data — the container has one CPU core and no
(Tiny)ImageNet), while preserving the paper's experimental STRUCTURE:
same estimators, same quantizer placement, same schedules, multiple seeds,
mean +/- std reporting.  ``--full`` scales closer to the paper (slower).
"""
from __future__ import annotations

import platform
import statistics
import sys
import time


def report(rows, header):
    """Print a CSV block (benchmark contract: name,value columns)."""
    print(",".join(header))
    for r in rows:
        print(",".join(str(x) for x in r))
    sys.stdout.flush()


def mean_std(vals):
    m = statistics.mean(vals)
    s = statistics.stdev(vals) if len(vals) > 1 else 0.0
    return m, s


def env_metadata() -> dict:
    """Machine/env metadata block for committed ``BENCH_*.json`` records.

    Stamped into every benchmark JSON so future comparisons (the
    ``benchmarks/check_regression.py`` gate) can tell apples from
    oranges: a CPU-interpret record must never be compared 1:1 against a
    real-TPU record, and a jax upgrade explains a step-time shift.
    ``interpret_mode`` is the mode the kernels ran in, from the platform.
    """
    import jax

    from repro.kernels import platform as kernel_platform
    return {
        "schema_version": 1,
        "jax": jax.__version__,
        "platform": jax.default_backend(),
        "device": str(getattr(jax.devices()[0], "device_kind",
                              jax.devices()[0].platform)),
        "python": platform.python_version(),
        "machine": platform.machine(),
        "interpret_mode": kernel_platform.interpret_mode(),
    }


class Timer:
    """Monotonic block timer (``perf_counter``; wall-clock ``time.time``
    is not monotonic and skews short intervals)."""

    def __enter__(self):
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *a):
        self.dt = time.perf_counter() - self.t0
