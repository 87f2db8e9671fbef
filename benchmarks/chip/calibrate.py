"""Readings that the limits of ``correct`` are set from, in one process.

    python3 benchmarks/chip/calibrate.py --workload <name> \
        --seeds <n> ... [--control-seeds <n> ...] \
        [--fault <name> --fault-seeds <n> ...] [--out <file.jsonl>]

For each seed it reads the numbers a run of the cell compares, at the
cell's own sizes: the program's, and with ``--control-seeds`` the
control's, the same program with 4-bit quantizers, or, where the traffic
file says ``"control": "reference"``, the reference with its weight
contractions' operands on a 4-bit grid.  A training cell needs
no window for them: set-up's first steps and the reference are the
whole of it.  A serving cell serves the request that a run would check
(request 0 of the seed's stream), and the control reads, at each position
of the same prompts and served tokens, the gap of the token it puts first.
Each reading is one JSON line, on standard output and in ``--out``.

Like ``run.py`` it needs the chip.  The benchmark's own runs never run it.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import sys
import time

import run


def train_readings(cell, driver, names, cache) -> tuple:
    import traffic as traffic_mod
    t = cell.traffic
    stream = traffic_mod.train_stream(cell.cfg, t, cell.seed)
    kw = {"stream": stream} if t["inputs"] == "images" else {}
    if cell.bits != 8 and t.get("control") == "reference":
        # The program's own 4-bit path does not fit the chip at this size:
        # the reference at 4 bits takes the program's place.
        prog = driver.reference_readings(cell, stream, t["check_steps"],
                                         bits=cell.bits)
    else:
        if cell.bits != 8:
            kw["bits"] = cell.bits
        tr = cell.prog.Train(cell.cfg, t, cell.key, cell.ref, **kw)
        state, prog = driver.program_readings(tr, stream, t["check_steps"])
        del state, tr
    if cell.seed not in cache:                # a control seed reuses it
        cache[cell.seed] = driver.reference_readings(cell, stream,
                                                     t["check_steps"])
    ref = cache[cell.seed]
    return driver.numbers(prog, ref), {
        "program": _plain(prog), "reference": _plain(ref)}


def _plain(readings: dict) -> dict:
    return {k: [float(x) for x in v] if hasattr(v, "__len__") else float(v)
            for k, v in readings.items()}


def serve_readings(cell, driver, names, cache) -> tuple:
    import jax
    import jax.numpy as jnp
    import numpy as np
    import traffic as traffic_mod
    c, t = cell.cfg, cell.traffic
    stream = traffic_mod.prompt_stream(c, t, cell.seed)
    prompts = stream.batch(0)["tokens"]
    positions = [jnp.full((t["batch"],), t["prompt"] + i, jnp.int32)
                 for i in range(t["gen"] - 1)]
    bits = cell.bits
    sv = cell.prog.Serve(c, t, cell.key, cell.ref, bits=8)
    served = driver.serve_request(sv, prompts, positions)
    del sv
    chosen = None
    if bits != 8:
        sv = cell.prog.Serve(c, t, cell.key, cell.ref, bits=bits)
        logits, caches = sv.prefill(sv.params, sv.quant, {"tokens": prompts})
        out = [np.asarray(driver.pick(logits))]
        for i, pos in enumerate(positions):
            tok = jax.device_put(served[:, i:i + 1])
            logits, caches = sv.decode(sv.params, sv.quant, tok, pos, caches)
            out.append(np.asarray(driver.pick(logits)))
        chosen = np.concatenate(out, axis=1)
        del sv, caches, logits
    gap = driver.reference_gap(cell, prompts, served, chosen)
    return {k: gap for k in names}, {}


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="*", default=[])
    ap.add_argument("--control-seeds", type=int, nargs="*", default=[])
    ap.add_argument("--fault", default="")
    ap.add_argument("--fault-seeds", type=int, nargs="*", default=[])
    ap.add_argument("--out", default="")
    args = ap.parse_args(argv)

    bench = run.read_json(run.ROOT / "BENCHMARK.json")
    wl, cfg, traffic, limits = run.cell_spec(bench, args.workload)
    devices = run.require_chips(wl["chips"])
    sys.path.insert(0, str(run.ROOT / "src"))
    from repro.launch import compile_cache
    compile_cache.enable()
    import harness

    driver = run.load(run.HERE / "drivers" / f"{traffic['driver']}.py")
    read = train_readings if traffic["driver"] == "train" else serve_readings
    progs = {"program": run.load(run.HERE / "programs"
                                 / f"{cfg['program']}.py")}
    progs["control"] = progs["program"]
    if args.fault:
        import faults
        progs[args.fault] = run.load(run.HERE / "programs"
                                     / f"{cfg['program']}.py")
        getattr(faults, args.fault)(progs[args.fault])
    ref = run.load(run.HERE / "configs" / f"{cfg['reference']}.py")
    cache: dict = {}
    jobs = ([(s, "program") for s in args.seeds]
            + [(s, "control") for s in args.control_seeds]
            + [(s, args.fault) for s in args.fault_seeds])
    sink = open(args.out, "a") if args.out else contextlib.nullcontext()
    with sink as out:
        for seed, kind in jobs:
            cell = harness.Cell(
                name=wl["name"], cfg=cfg, traffic=traffic, limits=limits,
                seed=seed, seconds=0, trace=False, prog=progs[kind],
                ref=ref, devices=devices, started=time.perf_counter(),
                bits=4 if kind == "control" else 8)
            t0 = time.perf_counter()
            readings, detail = read(cell, driver, list(limits), cache)
            row = {"workload": wl["name"], "seed": seed, "kind": kind,
                   "readings": readings,
                   "seconds": time.perf_counter() - t0}
            print(json.dumps(row), flush=True)
            if out:
                out.write(json.dumps(dict(row, detail=detail)) + "\n")
                out.flush()


if __name__ == "__main__":
    main()
