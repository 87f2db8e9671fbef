"""Driver of the expert-model training mixes: ``drivers/train.py``'s
set-up, window and check, with the work of a latent-attention expert model
(``flops_moe.py``): the step's operations, the forward contractions on the
int8 matmul kernel and, apart, the grouped contractions of the held
experts."""
from __future__ import annotations

import importlib.util
import pathlib

import flops_moe

_spec = importlib.util.spec_from_file_location(
    "bench_train_driver", pathlib.Path(__file__).with_name("train.py"))
base = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(base)


def work(cell) -> dict:
    c, t = cell.cfg, cell.traffic
    rows = t["batch"] // t["microbatches"] * t["seq"]
    return {"train_flops": flops_moe.train_flops(c, t["seq"], t["batch"]),
            "int8_contractions": flops_moe.dense_contractions(c, rows)
            * t["microbatches"],
            "gmm_contractions": flops_moe.expert_contractions(c, rows)
            * t["microbatches"]}


base.work = work
run, program_readings, reference_readings, numbers, compare = (
    base.run, base.program_readings, base.reference_readings, base.numbers,
    base.compare)
