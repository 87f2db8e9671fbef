"""The readings of the two recorded chip traces, pinned: a change to the
reduction that moves any number an accepted per-layer metric or the
breakdown reads from them fails here (``data/<cell>.readings.json``)."""
import json
import pathlib
import types

import pytest

import run
import trace_reduce as tr

DATA = pathlib.Path(__file__).resolve().parent / "data"
METRICS = pathlib.Path(run.__file__).resolve().parent / "metrics"


def _summary(name):
    return tr.reduce(tr.Events.from_json(str(DATA / f"{name}.json.gz")))


@pytest.mark.parametrize("name", ["lm-train-4k", "lm-serve-1k"])
def test_recorded_readings_are_pinned(name):
    want = json.loads((DATA / f"{name}.readings.json").read_text())
    s = _summary(name)
    assert s.window_ns == want["window_ns"]
    assert s.busy_ns == want["busy_ns"]
    for pats, ns in want["scope_ns"].items():
        assert s.scope_ns(*pats.split(",")) == ns, pats
    for sub, ns in want["program_ns"].items():
        assert s.program_ns(sub) == ns, sub
    # through JSON, as the result line carries it
    assert json.loads(json.dumps(tr.breakdown(s))) == want["breakdown"]


@pytest.mark.parametrize("name,metric", [
    ("lm-train-4k", "device_idle_pct.train"),
    ("lm-train-4k", "quant_share_pct.train"),
    ("lm-train-4k", "attention_share_pct.train"),
    ("lm-serve-1k", "device_idle_pct.serve"),
    ("lm-serve-1k", "quant_share_pct.serve"),
])
def test_recorded_metric_readers_are_pinned(name, metric):
    """The readers that take the summary alone read the pinned numbers."""
    want = json.loads((DATA / f"{name}.readings.json").read_text())
    busy, window = want["busy_ns"], want["window_ns"]
    expected = {
        "device_idle_pct": 100.0 * (1.0 - busy / window),
        "quant_share_pct": 100.0 * want["scope_ns"]["quant_"] / busy,
        "attention_share_pct":
            100.0 * want["scope_ns"]["qattn_int8_,k_attn_fwd"] / busy,
    }[metric.split(".")[0]]
    ctx = types.SimpleNamespace(summary=_summary(name))
    got = run.load(METRICS / f"{metric}.py").read(ctx)
    assert got == expected
