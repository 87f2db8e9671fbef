"""Drive ``run.main`` for a cell on the CPU at a tiny size.

The harness's look for a chip and its device readings are stubbed; the
rest of a run (set-up, window, the check against the plain reference and
the result line) is the real one, against the cell's real limits.  The
cell's sizes shrink (the LM to the registered reduced starcoder2 at
d_model 256 and a 4096-token vocabulary, the CNN to the bench-scale
MobileNetV2), and the program's
``simulated`` backend stands in for ``fused`` (the repo keeps the two
bit-identical), which the CPU runs much faster.
"""
from __future__ import annotations

import contextlib
import io
import json

import jax

import harness
import peaks
import run


def _shrink(cfg: dict) -> tuple:
    """Tiny sizes of a configuration, and the adapter hook that returns
    the program's matching model config."""
    if cfg["program"] == "lm":
        import dataclasses

        import lm
        from repro import configs
        small = dataclasses.replace(
            configs.get_reduced("starcoder2-3b"), d_model=256, n_heads=4,
            head_dim=64, d_ff=1024, vocab=4096)
        return (dict(cfg, **{k: getattr(small, k) for k in lm.SIZE_KEYS}),
                "arch_config", small)
    from repro.cnn import models
    small = models.bench_config("mobilenetv2", num_classes=10, width=0.25,
                                image_size=16)
    return (dict(cfg, width=small.width, image_size=small.image_size,
                 num_classes=small.num_classes), "cnn_config", small)


TRAFFIC = {
    "lm-train-4k": {"seq": 32, "batch": 4, "microbatches": 2},
    "cnn-train-64": {"batch": 8},
    "lm-serve-1k": {"batch": 4, "prompt": 32, "gen": 16},
}


def run_tiny(monkeypatch, workload: str, patch=None,
             seed: int = 3_000_000_019) -> dict:
    """Run ``workload`` at a tiny size; ``patch(module)`` may break the
    loaded program adapter.  Returns the printed result line."""
    bench = run.read_json(run.ROOT / "BENCHMARK.json")
    wl, cfg, traffic, limits = run.cell_spec(bench, workload)
    traffic = dict(traffic, **TRAFFIC[workload])
    if "backend" in traffic:
        traffic["backend"] = "simulated"
    cfg, hook, model_cfg = _shrink(cfg)
    orig_load = run.load

    def load(path):
        mod = orig_load(path)
        if path.parent.name == "programs":
            setattr(mod, hook, lambda c: model_cfg)
            if patch is not None:
                patch(mod)
        return mod

    monkeypatch.setattr(run, "load", load)
    monkeypatch.setattr(run, "cell_spec",
                        lambda b, w: (wl, cfg, traffic, limits))
    monkeypatch.setattr(run, "require_chips", lambda n: jax.devices()[:n])
    monkeypatch.setattr(peaks, "peaks_for",
                        lambda kind: peaks.PEAKS["TPU v5 lite"])
    monkeypatch.setattr(harness, "peak_bytes", lambda devices: 0)
    out = io.StringIO()
    with contextlib.redirect_stdout(out):
        run.main(["--workload", workload, "--seed", str(seed),
                  "--seconds", "0.5", "--trace", "0"])
    return json.loads(out.getvalue().strip().splitlines()[-1])
