"""The trace reduction, on events whose numbers are known by hand and on
a small trace recorded on the chip."""
import pathlib

import numpy as np
import pytest

import trace_reduce as tr

DATA = pathlib.Path(__file__).resolve().parent / "data"


def _hand_events():
    # window 0..100 ns; two chips.
    host = [["bench:window", 0, 100], ["bench:step", 0, 60],
            ["bench:fetch", 60, 40]]
    ops = {
        "0": [["fusion.1", -10, 20, "jit_train_step",
               "jit(train_step)/quant_act_fused/k_fused_quantize/pallas"],
              ["custom-call.2", 20, 30, "jit_train_step",
               "jit(train_step)/qmatmul_int8_fused/k_int8_matmul_fp/x"],
              ["fusion.3", 40, 20, "jit_train_step",       # overlaps .2
               "jit(train_step)/transpose(jvp(qattn_int8_fused))/dot"],
              ["fusion.4", 95, 10, "jit_decode_step", ""],
              # a loop around fusion.2 and fusion.3: busy, but its time is
              # its body's and is not counted again per operation.
              ["while.9", 20, 40, "jit_train_step", "jit(f)/qattn_int8_x"]],
        "1": [["fusion.1", 0, 50, "jit_train_step", "jit(x)/quant_w/y"]],
    }
    return tr.Events(ops=ops, host=host)


def test_busy_idle_and_scopes_by_hand():
    s = tr.reduce(_hand_events())
    assert s.window_ns == 100
    # chip 0: [0,10] + [20,60] + [95,100] = 55; chip 1: [0,50] = 50.
    assert s.busy_ns == pytest.approx((55 + 50) / 2)
    assert s.idle_share == pytest.approx(1 - 52.5 / 100)
    assert s.scope_ns("quant_") == pytest.approx((10 + 50) / 2)
    assert s.scope_ns("k_int8_matmul_fp") == pytest.approx(30 / 2)
    # the backward's ops carry the forward's scope under transpose(jvp(.))
    assert s.scope_ns("qattn_int8_") == pytest.approx(20 / 2)
    assert s.program_ns("decode_step") == pytest.approx(5 / 2)
    # longest gap: chip 1's [50,100], mid 75 under the fetch span.
    assert s.gaps[0] == (50, "fetch")
    assert (35, "fetch") in s.gaps and (10, "step") in s.gaps
    bd = tr.breakdown(s)
    assert bd["device_ops"][0] == [
        "jit_train_step:fusion.1 [quant_act_fused/k_fused_quantize/pallas]",
        (10 + 50) / 2 / 1e9]
    assert len(bd["idle_gaps"]) <= 10


def test_scope_matches_whole_path_components_only():
    ev = _hand_events()
    ev.ops["0"][2][4] = "jit(f)/my_qattn_int8_fused/dot"
    assert tr.reduce(ev).scope_ns("qattn_int8_") == 0.0


def test_tpu_op_names():
    assert tr._op_name("%fusion.71 = (u32[1]{0}) fusion(u32[2]{0} %key.1), "
                       "kind=kLoop") == "fusion.71"
    assert tr._label("jit_f", "fusion.3", "jit(f)/while/body/closed_call/"
                     "transpose(jvp())/checkpoint/qattn_int8_fused/dot") \
        == "jit_f:fusion.3 [qattn_int8_fused/dot]"


def test_hlo_scopes_parse():
    text = ('HloModule jit_f, entry_computation_layout={}\n'
            '  %fusion.7 = f32[8]{0} fusion(%p), kind=kLoop, '
            'metadata={op_name="jit(f)/quant_act_fused/mul" '
            'source_file="x.py" source_line=3}\n'
            '  ROOT %custom-call.2 = s8[8]{0} custom-call(%a), '
            'custom_call_target="tpu_custom_call", '
            'metadata={op_name="jit(f)/k_int8_matmul_fp/pallas_call"}\n')
    assert tr.hlo_scopes(text) == {
        "fusion.7": "jit(f)/quant_act_fused/mul",
        "custom-call.2": "jit(f)/k_int8_matmul_fp/pallas_call"}


def _timeline(ev, w0, w1):
    """Busy share by brute force on a 1 ns grid, mean over chips."""
    busy = []
    for rows in ev.ops.values():
        t = np.zeros(w1 - w0, bool)
        for _, start, dur, _, _ in rows:
            a, b = max(start, w0) - w0, min(start + dur, w1) - w0
            if b > a:
                t[a:b] = True
        busy.append(t.sum())
    return float(np.mean(busy))


@pytest.mark.parametrize("name", ["lm-train-4k", "lm-serve-1k"])
def test_recorded_chip_trace(name):
    """40 ms of a traced window of the cell, recorded on a TPU v5 lite."""
    ev = tr.Events.from_json(str(DATA / f"{name}.json.gz"))
    s = tr.reduce(ev)
    (_, w0, wd), = [h for h in ev.host if h[0] == tr.WINDOW_SPAN]
    assert s.window_ns == wd
    assert s.busy_ns == pytest.approx(_timeline(ev, w0, w0 + wd))
    assert 0 < s.busy_ns <= s.window_ns
    # operations outside control flow never overlap on one chip: their
    # summed time is at most the busy time.
    assert sum(s.op_ns.values()) <= s.busy_ns
    rows = ev.ops["0"]
    loops = [r for r in rows if tr._CONTROL.match(r[0])]
    assert loops and all(r[0] not in str(s.op_ns) for r in loops[:3])
