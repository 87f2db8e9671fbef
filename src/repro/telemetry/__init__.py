"""Quantization telemetry & overflow-guard subsystem.

The paper's in-hindsight estimator works because the accelerator keeps
"output statistics in an online fashion"; this package keeps the REST of
those statistics instead of throwing them away: per-site clipping rate,
range utilization, range drift and SQNR, accumulated jit-side on the
same channels as the min/max statistics (forward stats tree + cotangent
channel), combined exactly across grad-accum microbatches and shards,
and surfaced host-side once per step.

Layers:

  * :mod:`repro.telemetry.config`  — ``TelemetryConfig`` + the extended
    width-10 stats-vector slot layout (``QuantPolicy.telemetry``).
  * :mod:`repro.telemetry.metrics` — jit-side counter computation at the
    quantization sites, and microbatch/shard combine rules.
  * :mod:`repro.telemetry.guard`   — the overflow guard: auto-widen a
    clipping hindsight range (``widen``) or temporarily fall back to
    dynamic current min-max (``dynamic``) after ``patience`` consecutive
    over-threshold steps.
  * :mod:`repro.telemetry.sinks`   — host-side ``collect`` + bounded
    JSONL ring writer and in-memory aggregator.
  * :mod:`repro.telemetry.trace`   — host-side performance tracing:
    ``repro/*`` spans on the profiler's clock, the ``--trace DIR``
    profiler session, ``StepTimer`` step-phase breakdown (data / compile
    / execute / telemetry / checkpoint) with the ``"perf"`` JSONL record
    builder, and process-wide compile and GC counters.
  * :mod:`repro.telemetry.report`  — ``python -m repro.telemetry.report``
    per-site health tables (and ``--perf`` per-phase time tables) from
    a JSONL log.
"""
from .config import (  # noqa: F401
    BASE_WIDTH,
    GUARD_DYNAMIC,
    GUARD_MODES,
    GUARD_WIDEN,
    T_CLIP,
    T_DRIFT,
    T_ERR,
    T_N,
    T_SIG,
    T_STREAK,
    T_UTIL,
    TELEMETRY_WIDTH,
    TelemetryConfig,
)
from .events import GuardEventDetector  # noqa: F401
from .metrics import clip_rate, site_stats, sqnr_db, widen_state  # noqa: F401
from .sinks import (  # noqa: F401
    SCHEMA_VERSION,
    JsonlSink,
    MemorySink,
    collect,
    read_jsonl,
    read_jsonl_full,
    read_jsonl_records,
)
from .trace import StepTimer  # noqa: F401
from . import trace  # noqa: F401
