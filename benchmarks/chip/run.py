"""Run one cell of the chip benchmark and print its result line.

    python3 benchmarks/chip/run.py --workload <name> --seed <n> \
        --seconds <s> --trace <0|1>

from the root of a checkout.  Everything a cell needs is found by name
from ``BENCHMARK.json``: its configuration file (``configs/<config>.json``,
which names the program adapter under ``programs/`` and the plain
reference under ``configs/``), its traffic mix (``traffic/<mix>.json``,
which names the driver under ``drivers/``), the limits of its check
(``limits/<workload>.json``) and, in a traced run, one reader per
per-layer metric (``metrics/<metric>.py``).

The run fails, and prints no result, without a TPU, with fewer chips than
the cell asks for, or on a chip that the peak table does not know.  With
``--trace 0`` the result's metrics are the cell's end-to-end metrics, with
``--trace 1`` its per-layer metrics, read from a profiler trace of the
window.  The numbers ``correct`` compares are printed last on standard
error and last in the result line, each beside its limit.
"""
from __future__ import annotations

import time

STARTED = time.perf_counter()

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402
import types  # noqa: E402

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parents[1]
sys.path[:0] = [str(HERE), str(HERE / "configs"), str(HERE / "programs")]


def load(path: pathlib.Path):
    """Import a file of the benchmark by its path (names may hold dots)."""
    spec = importlib.util.spec_from_file_location(
        "bench_" + path.stem.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def read_json(path: pathlib.Path) -> dict:
    with open(path) as f:
        return json.load(f)


def cell_spec(bench: dict, workload: str) -> tuple:
    """``(workload entry, config, traffic, limits)`` of one cell."""
    wl = {w["name"]: w for w in bench["workloads"]}[workload]
    entry = {c["name"]: c for c in bench["configs"]}[wl["config"]]
    return (wl, read_json(ROOT / entry["file"]),
            read_json(HERE / "traffic" / f"{wl['traffic']}.json"),
            read_json(HERE / "limits" / f"{wl['name']}.json"))


def require_chips(n: int) -> list:
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        sys.exit(f"no TPU found (JAX platform {devices[0].platform!r}); "
                 f"the benchmark measures the chip only")
    if len(devices) < n:
        sys.exit(f"{n} chips asked for, {len(devices)} found")
    return devices[:n]


def applies(metric: dict, workload: str) -> bool:
    return workload in metric.get("workloads", [workload])


def per_layer(bench, wl, res, cell, peaks) -> tuple:
    """Reduce the traced window and read each per-layer metric."""
    import harness
    import trace_reduce
    programs = {harness.module_name(h): h for h in res.get("hlo", [])}
    summary = trace_reduce.reduce(cell.trace_events(programs))
    ctx = types.SimpleNamespace(summary=summary, work=res["work"],
                                peaks=peaks, chips=wl["chips"])
    metrics = {}
    for m in bench["per_layer"]:
        if not applies(m, wl["name"]):
            continue
        value = load(HERE / "metrics" / f"{m['name']}.py").read(ctx)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    device = {"busy_s": summary.busy_ns / 1e9,
              "window_s": summary.window_ns / 1e9}
    return metrics, device, trace_reduce.breakdown(summary)


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=[0, 1], default=0)
    args = ap.parse_args(argv)

    bench = read_json(ROOT / "BENCHMARK.json")
    wl, cfg, traffic, limits = cell_spec(bench, args.workload)
    devices = require_chips(wl["chips"])
    import peaks as peaks_mod
    peaks = peaks_mod.peaks_for(devices[0].device_kind)

    sys.path.insert(0, str(ROOT / "src"))
    from repro.launch import compile_cache
    compile_cache.enable()
    import harness

    cell = harness.Cell(
        name=wl["name"], cfg=cfg, traffic=traffic, limits=limits,
        seed=args.seed, seconds=args.seconds, trace=bool(args.trace),
        prog=load(HERE / "programs" / f"{cfg['program']}.py"),
        ref=load(HERE / "configs" / f"{cfg['reference']}.py"),
        devices=devices, started=STARTED)
    res = load(HERE / "drivers" / f"{traffic['driver']}.py").run(cell)

    checks = res["checks"]
    correct = all(math.isfinite(v) and v <= lim for _, v, lim in checks)
    d = devices[0]
    device = {"platform": d.platform, "kind": d.device_kind,
              "count": len(devices),
              "memory_peak_bytes": res["memory_peak_bytes"]}
    line = {"correct": correct, "attempted": res["attempted"],
            "failed": res["failed"]}
    if args.trace:
        metrics, dev, line["breakdown"] = per_layer(bench, wl, res, cell,
                                                    peaks)
        device.update(dev)
    else:
        e2e = dict(res["e2e"], setup_s=cell.setup_s,
                   peak_hbm_gib=res["memory_peak_bytes"] / 2 ** 30)
        metrics = {m["name"]: {"value": e2e[m["name"]], "unit": m["unit"]}
                   for m in bench["end_to_end"] if applies(m, wl["name"])}
    line.update(metrics=metrics, device=device)
    line["checks"] = {k: {"value": v, "limit": lim} for k, v, lim in checks}
    for k, v, lim in checks:
        print(f"check {k} {v!r} limit {lim!r}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(line), flush=True)


if __name__ == "__main__":
    main()
