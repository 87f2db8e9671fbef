"""Host-side performance tracing: program spans and counters on the
profiler's clock.

The paper's claim is a *performance* claim — in-hindsight ranges make the
quantization hot path static and single-pass — so the repo needs to
observe where time goes, not only quantization quality.  This module is
the program's one span-and-counter API:

  * :func:`span` — ``jax.profiler.TraceAnnotation("repro/<name>")``
    around a block of the program's host work.  With a profiler session
    live (:func:`session`, or any ``jax.profiler`` trace the caller
    starts) the span lands in the session's trace on the same clock as
    the runtime's own host events (``PjitFunction(<fn>)``,
    ``ParseArguments``, the executable's ``Execute``, buffer waits) and
    the device's operations.  With none live it costs one native check,
    and the computation never changes.
  * :class:`StepTimer` — splits each training step into the canonical
    phases ``data``, ``compile`` (first-call detection), ``execute``,
    ``telemetry`` and ``checkpoint``.  Each step is a
    ``StepTraceAnnotation("repro/train")`` and each phase a
    ``repro/<phase>`` span; the phases are also timed on
    ``time.perf_counter`` for the straggler watchdog and the ``"perf"``
    JSONL record (``python -m repro.telemetry.report --perf``).
  * :func:`counters` — process-wide counters, live from this module's
    first import: tracings, lowerings and backend compiles (count and
    seconds, from ``jax.monitoring``), persistent-cache hits, misses and
    load seconds, and garbage collections per generation with their
    pause seconds (from ``gc.callbacks``).  Each collection is also a
    ``repro/gc`` span, so that a host stall in a trace shows its cause.

Seconds are ``time.perf_counter`` (GC) or JAX's own clock (compiles).
``compile_s`` is the time of ``compile_or_get_cached``, so a persistent-
cache load counts there as well as in ``cache_load_s``.  A tracing or a
lowering nested in another of its kind (a ``jax.jit`` called while an
outer one traces) is counted once, with the outer one.
"""
from __future__ import annotations

import gc
import threading
import time
from contextlib import contextmanager
from typing import Any, Dict, Optional

import jax
from jax import monitoring

PREFIX = "repro/"


def span(name: str, **args):
    """A host span ``repro/<name>`` in the live profiler session, if any;
    ``args`` become the event's arguments."""
    return jax.profiler.TraceAnnotation(PREFIX + name, **args)


def instant(name: str, **args) -> None:
    """A zero-length span: a point event (e.g. a guard trigger)."""
    with span(name, **args):
        pass


@contextmanager
def session(log_dir: Optional[str]):
    """A profiler session over the block, written under ``log_dir``:
    ``plugins/profile/<run>/*.xplane.pb`` and a ``perfetto_trace.json.gz``
    that https://ui.perfetto.dev loads, with the program's spans and the
    device's operations on one clock.  A false ``log_dir`` traces nothing.
    Python calls are not traced (``python_tracer_level=0``)."""
    if not log_dir:
        yield
        return
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(log_dir), create_perfetto_trace=True,
                             profiler_options=opts)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


# ---------------------------------------------------------------------------
# Process-wide counters.
# ---------------------------------------------------------------------------
# jax.monitoring duration events -> (count key, seconds key).
_TIMED = {
    "/jax/core/compile/jaxpr_trace_duration": ("traces", "trace_s"),
    "/jax/core/compile/jaxpr_to_mlir_module_duration": ("lowerings",
                                                        "lower_s"),
    "/jax/core/compile/backend_compile_duration": ("compiles", "compile_s"),
    "/jax/compilation_cache/cache_retrieval_time_sec": (None,
                                                        "cache_load_s"),
}
# Events whose start JAX also reports (a scalar event on entry).
_PAIRED = {e for e, (count, _) in _TIMED.items() if count is not None}
_COUNTED = {
    "/jax/compilation_cache/cache_hits": "cache_hits",
    "/jax/compilation_cache/cache_misses": "cache_misses",
}
KEYS = ("traces", "trace_s", "lowerings", "lower_s", "compiles",
        "compile_s", "cache_hits", "cache_misses", "cache_load_s",
        "gc_gen0", "gc_gen1", "gc_gen2", "gc_pause_s")


class _Counters:
    """Totals fed by ``jax.monitoring`` listeners and ``gc.callbacks``.

    JAX reports the start of a tracing, lowering or compile as a scalar
    event and its end as a duration event, both on the calling thread; the
    per-thread depth keeps nested ones from counting twice.  The lock is
    re-entrant because a collection (and so :meth:`on_gc`) can start on a
    thread that holds it.
    """

    def __init__(self):
        self._lock = threading.RLock()
        self._vals: Dict[str, float] = dict.fromkeys(KEYS, 0)
        self._depth = threading.local()
        self._gc_open: Any = None           # (span, start) of a collection

    def snapshot(self) -> Dict[str, float]:
        with self._lock:
            return dict(self._vals)

    def _add(self, key: str, value: float) -> None:
        with self._lock:
            self._vals[key] += value

    def on_start(self, event: str, _value, **_kw) -> None:
        if event in _PAIRED:
            d = self._depth.__dict__
            d[event] = d.get(event, 0) + 1

    def on_duration(self, event: str, secs: float, **_kw) -> None:
        keys = _TIMED.get(event)
        if keys is None:
            return
        count, seconds = keys
        if event in _PAIRED:
            d = self._depth.__dict__
            d[event] = max(d.get(event, 1) - 1, 0)
            if d[event]:
                return
            self._add(count, 1)
        self._add(seconds, secs)

    def on_event(self, event: str, **_kw) -> None:
        key = _COUNTED.get(event)
        if key is not None:
            self._add(key, 1)

    def on_gc(self, phase: str, info: dict) -> None:
        # Collections never overlap: the interpreter runs one at a time.
        if phase == "start":
            sp = span("gc", generation=info["generation"])
            sp.__enter__()
            self._gc_open = (sp, time.perf_counter())
            return
        if self._gc_open is None:
            return
        sp, t0 = self._gc_open
        self._gc_open = None
        dt = time.perf_counter() - t0
        sp.__exit__(None, None, None)
        with self._lock:
            self._vals[f"gc_gen{info['generation']}"] += 1
            self._vals["gc_pause_s"] += dt


_COUNTERS = _Counters()
monitoring.register_scalar_listener(_COUNTERS.on_start)
monitoring.register_event_duration_secs_listener(_COUNTERS.on_duration)
monitoring.register_event_listener(_COUNTERS.on_event)
gc.callbacks.append(_COUNTERS.on_gc)


def counters() -> Dict[str, float]:
    """A snapshot of the process-wide counters (keys: ``KEYS``)."""
    return _COUNTERS.snapshot()


def since(before: Dict[str, float]) -> Dict[str, float]:
    """The counters' growth since the snapshot ``before``."""
    now = counters()
    return {k: now[k] - before[k] for k in KEYS}


class StepTimer:
    """Per-step phase breakdown, on the host clock and in the profiler.

    Usage::

        timer = StepTimer()
        for step in range(n):
            with timer.step(step) as st:
                with st.phase("data"):
                    batch = stream.batch(step)
                with st.execute():          # "compile" on the first call
                    state, met = train_step(state, batch)
                    jax.block_until_ready(met)
                with st.phase("telemetry"):
                    ...
            sink.write(step, records, events,
                       perf=timer.perf_record(items=tokens, unit="tokens"))

    ``timer.last`` holds the most recent step record:
    ``{"step", "total_ms", "phases": {name: ms}, "counters": {...}}``.
    Phase times are wall-clock (``perf_counter``) milliseconds and sum to
    ~``total_ms`` (minus the few microseconds between phases);
    ``counters`` is the growth of :func:`counters` over the step.
    """

    def __init__(self):
        self.compile_count = 0
        self.last: Optional[Dict[str, Any]] = None
        self._cur: Optional[Dict[str, Any]] = None

    @contextmanager
    def step(self, step: int):
        rec: Dict[str, Any] = {"step": int(step), "phases": {},
                               "total_ms": 0.0}
        prev, self._cur = self._cur, rec
        before = counters()
        t0 = time.perf_counter()
        try:
            with jax.profiler.StepTraceAnnotation(PREFIX + "train",
                                                  step_num=int(step)):
                yield self
        finally:
            rec["total_ms"] = (time.perf_counter() - t0) * 1e3
            rec["counters"] = since(before)
            self.last = rec
            self._cur = prev

    @contextmanager
    def phase(self, name: str):
        if self._cur is None:
            raise RuntimeError("StepTimer.phase used outside StepTimer.step")
        t0 = time.perf_counter()
        try:
            with span(str(name)):
                yield
        finally:
            dt = (time.perf_counter() - t0) * 1e3
            ph = self._cur["phases"]
            ph[name] = ph.get(name, 0.0) + dt

    @contextmanager
    def execute(self):
        """Device phase with first-call compile detection.

        ``jax.jit`` traces + compiles on the first invocation, so the
        first device phase of a run is dominated by compilation: it is
        recorded as the ``compile`` phase (and counted in
        ``compile_count``); every later call records ``execute``.  The
        caller must fence inside the block (``block_until_ready`` or a
        host transfer) so the phase covers actual device time.
        """
        first = self.compile_count == 0
        if first:
            self.compile_count += 1
        with self.phase("compile" if first else "execute"):
            yield

    def perf_record(self, items: Optional[float] = None,
                    unit: str = "items") -> Dict[str, Any]:
        """The ``"perf"`` JSONL payload for the most recent step.

        ``items`` (tokens, images, ...) divided by the step time gives
        the throughput field; ``unit`` names it (``"tokens"`` ->
        ``"tokens/s"``).  ``counters`` holds the step's non-zero counter
        growth (a recompile, a long collection).
        """
        if self.last is None:
            raise RuntimeError("perf_record before any timed step")
        rec: Dict[str, Any] = {
            "step_time_ms": round(self.last["total_ms"], 4),
            "phases_ms": {k: round(v, 4)
                          for k, v in self.last["phases"].items()},
            "compile_count": self.compile_count,
            "counters": {k: round(v, 6)
                         for k, v in self.last["counters"].items() if v},
        }
        if items is not None and self.last["total_ms"] > 0:
            rec["throughput"] = round(
                float(items) / (self.last["total_ms"] / 1e3), 3)
            rec["throughput_unit"] = f"{unit}/s"
        return rec
