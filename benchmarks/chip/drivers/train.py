"""Driver of training mixes: set-up, the timed window, and the check.

Set-up builds the program's compiled step and its state once, then drives
that same object through its first ``check_steps`` steps with the window's
own call and feed (rows that all differ), and reads its state after the
first step and after the last: the readings ``correct`` compares with the
plain reference.  The first call compiles.  The window then runs whole
steps, each the step's data batch, the step and the fetch of its metrics
(as ``repro.launch.train``'s loop does), until ``--seconds`` have passed.
"""
from __future__ import annotations

import math
import sys
import time

import jax
import numpy as np

import flops
import harness
import ref_train
import traffic as traffic_mod


def work(cell) -> dict:
    """The work one step needs, from the config's shapes."""
    c, t = cell.cfg, cell.traffic
    if t["inputs"] == "tokens":
        rows = t["batch"] // t["microbatches"] * t["seq"]
        return {"train_flops": flops.lm_train_flops(c, t["seq"], t["batch"]),
                "int8_contractions": flops.lm_forward_contractions(c, rows)
                * t["microbatches"]}
    return {"train_flops": flops.cnn_train_flops(c, t["batch"]),
            "int8_contractions": flops.cnn_forward_contractions(c,
                                                                t["batch"])}


def program_readings(tr, stream, n: int) -> tuple:
    """Drive the program's step through its first ``n`` steps; returns its
    state after them and the readings."""
    state, out = tr.state, {"losses": []}
    tr.state = None
    for k in range(n):
        state, met = tr.step(state, tr.feed(stream.batch(k)))
        met = {name: float(v) for name, v in met.items()}
        out["losses"].append(met["loss"])
        if k == 0:
            out["grad_norm"] = met["grad_norm"]
            out["grad_leaf_norms"] = tr.first_grad_norms(state)
    out["change_leaf_norms"] = tr.change_norms(state)
    return state, out


def reference_readings(cell, stream, n: int, bits=None) -> dict:
    """The reference's readings; ``bits`` (LM only) computes them with its
    weight contractions' operands on a coarser grid: a control."""
    ref, c, t = cell.ref, cell.cfg, cell.traffic
    if t["inputs"] == "tokens":
        init = jax.jit(lambda k: ref.init_params(k, c))
        lg = ref.loss_and_grad(c, t["microbatches"], bits)
    else:                                      # (params, BatchNorm state)
        init = jax.jit(lambda k: ref.init_params(k, c)[0])
        lg = ref.loss_and_grad(c)
    return ref_train.train_readings(
        t["optimizer"], lambda: init(cell.key), lg,
        [stream.batch(k) for k in range(n)])


def numbers(prog: dict, ref: dict) -> dict:
    """Every number a training cell can compare (its limits file picks).

    Each gap is relative: a norm's gap against the reference's norm of
    that leaf or of the median leaf, whichever is larger.  ``*_gap`` is
    the worst leaf, ``*_gap_median`` the median leaf's gap."""
    g_p, g_r = prog["grad_leaf_norms"], ref["grad_leaf_norms"]
    # Leaves whose reference gradient is nought to rounding (a key's bias
    # under softmax, a BatchNorm shift followed only by convolutions and
    # BatchNorm) move under the optimizer by round-off alone, and any
    # quantization gives them a gradient of their own: both comparisons
    # leave them out.
    moved = g_r >= 1e-3 * np.median(g_r)
    g_p, g_r = g_p[moved], g_r[moved]
    grad = np.abs(g_p - g_r) / np.maximum(g_r, np.median(g_r))
    d_p, d_r = prog["change_leaf_norms"][moved], ref["change_leaf_norms"][moved]
    change = np.abs(d_p - d_r) / np.maximum(d_r, np.median(d_r))
    return {
        "loss_gap": max(abs(a - b) / abs(b)
                        for a, b in zip(prog["losses"], ref["losses"])),
        "grad_norm_gap": abs(prog["grad_norm"] - ref["grad_norm"])
        / ref["grad_norm"],
        "grad_leaf_gap": float(np.max(grad)),
        "grad_leaf_gap_median": float(np.median(grad)),
        "change_leaf_gap": float(np.max(change)),
        "change_leaf_gap_median": float(np.median(change)),
    }


def compare(prog: dict, ref: dict, limits: dict) -> list:
    """The numbers ``correct`` compares, each ``(name, value, limit)``."""
    vals = numbers(prog, ref)
    return [(k, float(vals[k]), limits[k]) for k in limits]


def run(cell) -> dict:
    t = cell.traffic
    stream = traffic_mod.train_stream(cell.cfg, t, cell.seed)
    kw = {"stream": stream} if t["inputs"] == "images" else {}
    tr = cell.prog.Train(cell.cfg, t, cell.key, cell.ref, **kw)
    n = t["check_steps"]
    state, readings = program_readings(tr, stream, n)
    cell.setup_done()

    steps, i, nonfinite = 0, n, 0
    seconds = cell.window_seconds()
    with cell.tracer():
        t0 = time.perf_counter()
        while True:
            with harness.span("data"):
                batch = tr.feed(stream.batch(i))
            with harness.span("step"):
                state, met = tr.step(state, batch)
            with harness.span("fetch"):
                loss = float(met["loss"])
            nonfinite += not math.isfinite(loss)
            steps, i = steps + 1, i + 1
            if time.perf_counter() - t0 >= seconds:
                break
        window = time.perf_counter() - t0
    out = {"attempted": steps, "failed": nonfinite,
           "e2e": {"step_ms": window / steps * 1e3},
           "work": dict(work(cell), steps=steps)}
    if cell.trace:
        out["hlo"] = [harness.hlo_text(tr.step, state, batch)]
    out["memory_peak_bytes"] = harness.peak_bytes(cell.devices)
    del state, met, batch, tr
    t0 = time.perf_counter()
    ref = reference_readings(cell, stream, n)
    print(f"reference: {n} steps in {time.perf_counter() - t0:.1f} s",
          file=sys.stderr)
    out["checks"] = compare(readings, ref, cell.limits)
    return out
