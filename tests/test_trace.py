"""Performance observability layer: program spans in a profiler session,
compile and GC counters, StepTimer phase accounting, "perf" JSONL schema round-trip + backward
compatibility, report --perf rendering, and the benchmark regression
gate."""
import gc
import glob
import json
import os
import time

import jax
import jax.numpy as jnp
import pytest

from repro.telemetry import report
from repro.telemetry import trace as trace_mod
from repro.telemetry.sinks import (
    SCHEMA_VERSION,
    JsonlSink,
    MemorySink,
    read_jsonl_full,
    read_jsonl_records,
)


# ---------------------------------------------------------------------------
# Spans on the profiler's clock, and the process-wide counters.
# ---------------------------------------------------------------------------
def _host_events(log_dir):
    """``[(line, name, start_ns, end_ns, args)]`` of the host planes of the
    one profiler session written under ``log_dir``."""
    from jax.profiler import ProfileData

    path, = glob.glob(os.path.join(str(log_dir), "**", "*.xplane.pb"),
                      recursive=True)
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    start = int(ev.start_ns)
                    out.append((line.name, ev.name, start,
                                start + int(ev.duration_ns),
                                {k: v for k, v in ev.stats}))
    return out


def _one(events, name):
    found = [e for e in events if e[1] == name]
    assert len(found) == 1, (name, [e[1] for e in events])
    return found[0]


def _within(inner, outer):
    return (inner[0] == outer[0] and outer[2] <= inner[2]
            and inner[3] <= outer[3])


def test_spans_land_in_profiler_session_nested(tmp_path):
    f = jax.jit(lambda x: (x * 2.0).sum())
    x = jnp.ones((8, 8))
    f(x).block_until_ready()                   # compiled before the session
    timer = trace_mod.StepTimer()
    with trace_mod.session(str(tmp_path)):
        with timer.step(4) as st:
            with st.phase("data"):
                y = x + 1.0
            with st.execute():
                float(f(y))
    evs = _host_events(tmp_path)
    step = _one(evs, "repro/train")
    assert step[4]["step_num"] == 4
    data, compile_ = _one(evs, "repro/data"), _one(evs, "repro/compile")
    assert _within(data, step) and _within(compile_, step)
    # the runtime's dispatch of the jitted call, named after the function,
    # lies inside the program's span around it, on the same thread
    pjit = [e for e in evs if e[1].startswith("PjitFunction(")
            and _within(e, compile_)]
    assert pjit, sorted({e[1] for e in evs})
    # the operator's file: the same session as a Perfetto trace
    assert glob.glob(os.path.join(str(tmp_path), "**",
                                  "perfetto_trace.json.gz"), recursive=True)
    # the host clock still times the phases
    assert set(timer.last["phases"]) == {"data", "compile"}


def test_spans_without_session_record_nothing(tmp_path):
    timer = trace_mod.StepTimer()
    with timer.step(0) as st:                  # no session live
        with st.phase("data"):
            time.sleep(0.002)
        with trace_mod.span("unseen", k=1):
            pass
    assert timer.last["phases"]["data"] >= 2.0
    assert timer.last["total_ms"] >= timer.last["phases"]["data"]
    with trace_mod.session(str(tmp_path)):
        with trace_mod.span("seen"):
            pass
    names = {e[1] for e in _host_events(tmp_path)}
    assert "repro/seen" in names
    assert not {"repro/unseen", "repro/data", "repro/train"} & names
    # a false directory opens no session at all
    with trace_mod.session(""):
        with trace_mod.span("nowhere"):
            pass


def test_guard_instant_appears_in_session(tmp_path):
    with trace_mod.session(str(tmp_path)):
        trace_mod.instant("guard:widen", site="layers/0/act")
    ev = _one(_host_events(tmp_path), "repro/guard:widen")
    assert ev[4] == {"site": "layers/0/act"}
    assert ev[3] - ev[2] < 1e6                 # a point, not a span of work


def test_fresh_jit_raises_compile_counters():
    x = jnp.arange(7.0)
    inner = jax.jit(lambda v: v * 3.0)
    outer = jax.jit(lambda v: inner(v).sum() + 1.0)
    before = trace_mod.counters()
    float(outer(x))
    grown = trace_mod.since(before)
    # one outer tracing (the inner one nested in it counts with it), one
    # lowering and one compile, each with its seconds
    assert grown["traces"] == 1 and grown["trace_s"] > 0
    assert grown["lowerings"] == 1 and grown["lower_s"] > 0
    assert grown["compiles"] == 1 and grown["compile_s"] > 0
    again = trace_mod.counters()
    float(outer(x))                            # cached: nothing grows
    assert trace_mod.since(again)["compiles"] == 0
    assert set(trace_mod.counters()) == set(trace_mod.KEYS)


def test_counters_hold_under_concurrent_compiles():
    """Sixteen threads compile at once, with the interpreter switching
    threads as often as it can: no update of the counters is lost."""
    import sys
    import threading

    x = jnp.ones(3)
    fns = [jax.jit(lambda v, k=k: v * float(k)) for k in range(16)]
    before = trace_mod.counters()
    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        threads = [threading.Thread(target=lambda f=f: f(x).block_until_ready())
                   for f in fns]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
    finally:
        sys.setswitchinterval(old)
    assert not any(t.is_alive() for t in threads)
    grown = trace_mod.since(before)
    assert grown["traces"] == 16 and grown["compiles"] == 16


def test_gc_collect_raises_gc_counters_and_span(tmp_path):
    before = trace_mod.counters()
    with trace_mod.session(str(tmp_path)):
        gc.collect()
    grown = trace_mod.since(before)
    assert grown["gc_gen2"] >= 1 and grown["gc_pause_s"] > 0
    gcs = [e for e in _host_events(tmp_path) if e[1] == "repro/gc"]
    assert any(e[4] == {"generation": 2} for e in gcs)


def test_perf_record_and_straggler_name_the_step_counters(capsys):
    from repro.launch.train import Watchdog

    timer = trace_mod.StepTimer()
    with timer.step(0) as st:
        with st.execute():
            gc.collect()
    perf = timer.perf_record()
    assert perf["counters"]["gc_gen2"] >= 1
    assert all(v for v in perf["counters"].values())
    wd = Watchdog(factor=3.0)
    for s in range(8):
        wd.step(0.01, s)
    wd.step(1.0, 8, timer.last["counters"])
    out = capsys.readouterr().out
    assert wd.flagged == 1 and "straggler" in out and "gc_gen2 1" in out


# ---------------------------------------------------------------------------
# StepTimer: phase accounting + first-call compile detection.
# ---------------------------------------------------------------------------
def test_step_timer_phases_sum_to_total():
    timer = trace_mod.StepTimer()
    with timer.step(0) as st:
        with st.phase("data"):
            time.sleep(0.004)
        with st.execute():
            time.sleep(0.006)
        with st.phase("telemetry"):
            time.sleep(0.002)
        with st.phase("checkpoint"):
            pass
    rec = timer.last
    assert rec["step"] == 0
    # first device phase is attributed to compilation
    assert "compile" in rec["phases"] and "execute" not in rec["phases"]
    assert set(rec["phases"]) == {"data", "compile", "telemetry",
                                  "checkpoint"}
    total = rec["total_ms"]
    s = sum(rec["phases"].values())
    assert s <= total + 1e-6
    assert s >= 0.9 * total  # phases cover ~all of the step

    with timer.step(1) as st:
        with st.execute():
            time.sleep(0.001)
    assert "execute" in timer.last["phases"]  # second call is not a compile
    assert timer.compile_count == 1


def test_step_timer_perf_record_throughput():
    timer = trace_mod.StepTimer()
    with timer.step(7) as st:
        with st.execute():
            time.sleep(0.01)
    perf = timer.perf_record(items=256, unit="tokens")
    assert perf["step_time_ms"] >= 10.0
    assert perf["throughput_unit"] == "tokens/s"
    assert perf["throughput"] == pytest.approx(
        256 / (perf["step_time_ms"] / 1e3), rel=1e-3)
    assert perf["compile_count"] == 1
    assert "compile" in perf["phases_ms"]


def test_phase_outside_step_raises():
    timer = trace_mod.StepTimer()
    with pytest.raises(RuntimeError):
        with timer.phase("data"):
            pass


# ---------------------------------------------------------------------------
# "perf" records through the JSONL sink: round-trip + back-compat.
# ---------------------------------------------------------------------------
_SITES = {"layers/0/act": {"qmin": -1.0, "qmax": 1.0, "inited": 1.0}}


def _perf(step_ms=10.0, **phases):
    return {"step_time_ms": step_ms,
            "phases_ms": phases or {"execute": step_ms},
            "compile_count": 1,
            "throughput": 100.0, "throughput_unit": "tokens/s"}


def test_perf_roundtrip_through_jsonl_sink(tmp_path):
    path = str(tmp_path / "tele.jsonl")
    sink = JsonlSink(path, max_steps=16)
    sink.write(0, _SITES, None, perf=_perf(12.5, data=2.5, execute=10.0))
    sink.write(1, _SITES)  # no perf on this line
    sink.close()

    recs = read_jsonl_records(path)
    assert [r["v"] for r in recs] == [SCHEMA_VERSION, SCHEMA_VERSION]
    assert recs[0]["perf"]["step_time_ms"] == 12.5
    assert recs[0]["perf"]["phases_ms"] == {"data": 2.5, "execute": 10.0}
    assert recs[1]["perf"] is None
    # the classic reader still sees (step, sites, events)
    full = read_jsonl_full(path)
    assert [s for s, _, _ in full] == [0, 1]
    assert full[0][1] == _SITES


def test_versionless_v1_jsonl_still_parses(tmp_path):
    path = tmp_path / "old.jsonl"
    lines = [
        {"step": 0, "sites": _SITES},                        # v1: no "v"
        {"step": 1, "sites": _SITES, "events": [
            {"site": "s", "step": 1, "action": "widen",
             "old": [-1, 1], "new": [-1.5, 1.5],
             "clip_rate": 0.2, "streak": 3}]},
        "not json at all",                                   # bad line
    ]
    with open(path, "w") as f:
        for ln in lines:
            f.write((ln if isinstance(ln, str) else json.dumps(ln)) + "\n")
    recs = read_jsonl_records(str(path))
    assert [r["step"] for r in recs] == [0, 1]
    assert all(r["v"] == 1 and r["perf"] is None for r in recs)
    assert recs[1]["events"][0]["action"] == "widen"
    assert len(read_jsonl_full(str(path))) == 2


def test_memory_sink_collects_perf():
    sink = MemorySink()
    sink.write(0, _SITES, perf=_perf(5.0))
    sink.write(1, _SITES)
    assert len(sink.perf) == 1
    assert sink.perf[0]["step"] == 0 and sink.perf[0]["step_time_ms"] == 5.0


# ---------------------------------------------------------------------------
# report --perf on a synthetic log.
# ---------------------------------------------------------------------------
def test_report_perf_renders_synthetic_log(tmp_path, capsys):
    path = str(tmp_path / "tele.jsonl")
    sink = JsonlSink(path, max_steps=64)
    sink.write(0, _SITES, None,
               perf=_perf(100.0, compile=95.0, data=3.0, execute=2.0))
    for s in range(1, 6):
        sink.write(s, _SITES, None,
                   perf=_perf(10.0 + s, data=2.0, execute=8.0 + s,
                              telemetry=0.5))
    sink.close()

    out = report.main([path, "--perf"])
    text = capsys.readouterr().out
    assert out["steps"] == 6
    assert out["compile_count"] == 1
    assert set(out["phases"]) == {"compile", "data", "execute", "telemetry"}
    for token in ("phase", "execute", "compile", "slowest", "tokens/s"):
        assert token in text
    # the compile-dominated step 0 is the slowest
    assert "step      0" in text


def test_report_perf_without_records(tmp_path, capsys):
    path = tmp_path / "old.jsonl"
    path.write_text(json.dumps({"step": 0, "sites": _SITES}) + "\n")
    assert report.main([str(path), "--perf"]) is None
    assert "no perf records" in capsys.readouterr().out


# ---------------------------------------------------------------------------
# Benchmark regression gate.
# ---------------------------------------------------------------------------
from benchmarks import check_regression  # noqa: E402


def _bench_record(step_ms=100.0, parity=True):
    return {
        "family": "lm",
        "meta": {"schema_version": 1, "jax": jax.__version__,
                 "platform": "cpu", "interpret_mode": True},
        "simulated": {"compile_s": 5.0, "step_ms_mean": step_ms,
                      "step_ms_std": 1.0, "loss": 0.5},
        "fused": {"compile_s": 9.0, "step_ms_mean": 2 * step_ms,
                  "step_ms_std": 2.0, "loss": 0.5},
        "quant_state_bit_exact": parity,
        "loss_bit_exact": parity,
    }


def _write(tmp_path, name, obj):
    p = tmp_path / name
    p.write_text(json.dumps(obj))
    return str(p)


def test_check_regression_identical_passes(tmp_path):
    base = _write(tmp_path, "base.json", _bench_record())
    fresh = _write(tmp_path, "fresh.json", _bench_record())
    assert check_regression.main(
        [fresh, "--baseline", base, "--tolerance", "0.5"]) == 0


def test_check_regression_fails_on_2x_step_time(tmp_path):
    base = _write(tmp_path, "base.json", _bench_record(step_ms=100.0))
    fresh = _write(tmp_path, "fresh.json", _bench_record(step_ms=200.0))
    assert check_regression.main(
        [fresh, "--baseline", base, "--tolerance", "0.5"]) == 1
    # ... but within tolerance it passes
    ok = _write(tmp_path, "ok.json", _bench_record(step_ms=140.0))
    assert check_regression.main(
        [ok, "--baseline", base, "--tolerance", "0.5"]) == 0
    # ... and warn-only-timing downgrades the 2x regression to a warning
    assert check_regression.main(
        [fresh, "--baseline", base, "--tolerance", "0.5",
         "--warn-only-timing"]) == 0


def test_check_regression_parity_hard_fails(tmp_path):
    base = _write(tmp_path, "base.json", _bench_record(parity=True))
    fresh = _write(tmp_path, "fresh.json", _bench_record(parity=False))
    # parity breaks are not excused by tolerance or warn-only-timing
    assert check_regression.main(
        [fresh, "--baseline", base, "--tolerance", "100.0",
         "--warn-only-timing"]) == 1


def test_check_regression_kernel_correctness_verdicts(tmp_path):
    base = _write(tmp_path, "k.json", {
        "meta": {"jax": jax.__version__, "platform": "cpu",
                 "interpret_mode": True},
        "rows": [{"kernel": "fused_quantize", "correctness": "bit-exact"},
                 {"kernel": "int8_matmul_fused", "correctness": "bit-exact"}],
    })
    good = _write(tmp_path, "kf.json", {
        "meta": {"jax": jax.__version__, "platform": "cpu",
                 "interpret_mode": True},
        "rows": [{"kernel": "fused_quantize",
                  "correctness": "ok(<=1-level ties: 3/65536)"},
                 {"kernel": "int8_matmul_fused", "correctness": "bit-exact"}],
    })
    assert check_regression.main([good, "--baseline", base]) == 0
    bad = _write(tmp_path, "kb.json", {
        "meta": {"jax": jax.__version__, "platform": "cpu",
                 "interpret_mode": True},
        "rows": [{"kernel": "fused_quantize", "correctness": "MISMATCH"},
                 {"kernel": "int8_matmul_fused", "correctness": "bit-exact"}],
    })
    assert check_regression.main([bad, "--baseline", base]) == 1


def test_check_regression_committed_baselines_selfcheck():
    """The committed baselines gate themselves: identical fresh == pass."""
    import os
    for name in ("BENCH_backend.json", "BENCH_conv.json",
                 "BENCH_kernels.json", "BENCH_attention.json"):
        path = os.path.join(check_regression.DEFAULT_BASELINE_DIR, name)
        assert os.path.exists(path), f"committed baseline missing: {name}"
        rec = json.load(open(path))
        assert "meta" in rec and rec["meta"]["jax"], name
        assert check_regression.main([path, "--baseline", path]) == 0


# ---------------------------------------------------------------------------
# Profiler-scoped quant sites: named_scope metadata in the compiled HLO.
# ---------------------------------------------------------------------------
def test_quant_sites_are_named_in_hlo():
    from repro.core import backend
    from repro.core.policy import QuantPolicy

    policy = QuantPolicy.w8a8g8()
    leaf = jnp.array([-1.0, 1.0, 1.0], jnp.float32)
    x = jnp.linspace(-2.0, 2.0, 64, dtype=jnp.float32).reshape(8, 8)

    def f(x, leaf):
        xq, _, _ = backend.act_quantize(policy, x, leaf, jnp.int32(1))
        return xq.sum()

    txt = jax.jit(f).lower(x, leaf).compile().as_text()
    assert "quant_act" in txt  # the site is a named scope, not an
    #                            anonymous fusion, in profiles/HLO dumps
