"""A run with the timed path broken underneath comes out not correct.

Each fault a cell can have is planted in the program adapter at a tiny
size on the CPU; the rest of the run, the check against the plain
reference under the cell's own limits included, is the real one.  The
fault has to fail a number that the sound run of the same seed and size
passes, so that it is the fault the check catches and not the size.
The 4-bit control is held to the same.
"""
import pytest

import faults
import tiny

CELLS = ["lm-train-4k", "cnn-train-64", "lm-serve-1k"]
FAULTS = [("lm-train-4k", faults.state_unchanged),
          ("lm-train-4k", faults.half_batch),
          ("cnn-train-64", faults.state_unchanged),
          ("cnn-train-64", faults.half_batch),
          ("lm-serve-1k", faults.token_altered)]
# The LM training cell's control is the reference at 4 bits, below.
FAULTS += [(cell, faults.control) for cell in ("cnn-train-64", "lm-serve-1k")]


@pytest.fixture(scope="module")
def sound():
    """The sound run of each cell, at the tiny size and the tests' seed."""
    out = {}
    for w in CELLS:
        with pytest.MonkeyPatch.context() as mp:
            out[w] = tiny.run_tiny(mp, w)
    return out


@pytest.mark.parametrize("workload", CELLS)
def test_result_line_ends_with_checks(sound, workload):
    line = sound[workload]
    assert list(line)[-1] == "checks"
    assert {"correct", "attempted", "failed", "metrics", "device"} <= set(line)
    assert line["attempted"] > 0


@pytest.mark.parametrize("workload,fault", FAULTS,
                         ids=[f"{w}-{f.__name__}" for w, f in FAULTS])
def test_fault_is_not_correct(sound, monkeypatch, workload, fault):
    ok = sound[workload]["checks"]
    line = tiny.run_tiny(monkeypatch, workload, patch=fault)
    assert not line["correct"], line["checks"]
    caught = [k for k, c in line["checks"].items()
              if not c["value"] <= c["limit"]
              and ok[k]["value"] <= ok[k]["limit"]]
    assert caught, (line["checks"], ok)


def test_lm_train_reference_control_is_not_correct():
    """``lm-train-4k``'s control is the reference at 4 bits in the
    program's place (the program's own 4-bit step does not fit the chip):
    its readings against the fp32 reference fail the cell's limits."""
    import jax

    import harness
    import run
    import traffic as traffic_mod

    bench = run.read_json(run.ROOT / "BENCHMARK.json")
    wl, cfg, traffic, limits = run.cell_spec(bench, "lm-train-4k")
    cfg = tiny._shrink(cfg)[0]
    traffic = dict(traffic, **tiny.TRAFFIC["lm-train-4k"])
    driver = run.load(run.HERE / "drivers" / "train.py")
    cell = harness.Cell(
        name=wl["name"], cfg=cfg, traffic=traffic, limits=limits,
        seed=3_000_000_019, seconds=0, trace=False, prog=None,
        ref=run.load(run.HERE / "configs" / f"{cfg['reference']}.py"),
        devices=jax.devices(), started=0.0)
    stream = traffic_mod.train_stream(cfg, traffic, cell.seed)
    n = traffic["check_steps"]
    ref = driver.reference_readings(cell, stream, n)
    low = driver.reference_readings(cell, stream, n, bits=4)
    checks = driver.compare(low, ref, limits)
    assert any(not v <= lim for _, v, lim in checks), checks
