"""The cells of the expert model and of short-sequence training run end to
end on the CPU at a tiny size (``tiny.run_tiny``), and their faults and
the 4-bit reference control come out not correct.

``moe-train-8k`` shrinks to the program's registered reduced Moonlight (3
layers: the dense one and 2 expert layers of 16 experts, 4 held, top-4)
with the reference's keys to match; ``lm-train-1k`` to tiny's starcoder2.
"""
import dataclasses

import jax
import pytest

import faults
import harness
import run
import tiny
import traffic as traffic_mod

TINY_TRAFFIC = {"moe-train-8k": {"seq": 32, "batch": 4, "microbatches": 2},
                "lm-train-1k": {"seq": 16, "batch": 8, "microbatches": 4}}


def _moonlight_tiny(cfg: dict) -> tuple:
    from repro import configs
    small = configs.get_reduced("moonlight-16b-a3b")
    m = small.moe
    first, held = m.held_range
    keys = {
        "hidden_size": small.d_model, "num_attention_heads": small.n_heads,
        "qk_nope_head_dim": small.qk_nope_head_dim,
        "qk_rope_head_dim": small.qk_rope_head_dim,
        "v_head_dim": small.v_head_dim, "kv_lora_rank": small.kv_lora_rank,
        "intermediate_size": small.d_ff, "moe_intermediate_size": m.d_expert,
        "n_routed_experts": m.n_experts, "num_experts_per_tok": m.top_k,
        "experts_first": first, "experts_held": held,
        "vocab_size": small.vocab, "num_hidden_layers": small.n_layers,
        "first_k_dense_replace": small.first_k_dense}
    import lm
    keys.update({k: getattr(small, k) for k in lm.SIZE_KEYS})
    return dict(cfg, **keys), "arch_config", small


@pytest.fixture
def cells(monkeypatch):
    shrink = tiny._shrink
    monkeypatch.setattr(tiny, "_shrink", lambda c: _moonlight_tiny(c)
                        if c["reference"] == "ref_moonlight" else shrink(c))
    for k, v in TINY_TRAFFIC.items():
        monkeypatch.setitem(tiny.TRAFFIC, k, v)
    return monkeypatch


@pytest.mark.parametrize("workload", list(TINY_TRAFFIC))
def test_cell_runs_and_half_batch_is_caught(cells, workload):
    ok = tiny.run_tiny(cells, workload)
    assert list(ok)[-1] == "checks" and ok["attempted"] > 0
    assert ok["failed"] == 0
    assert set(ok["metrics"]) >= {"step_ms", "setup_s", "peak_hbm_gib"}
    bad = tiny.run_tiny(cells, workload, patch=faults.half_batch)
    caught = [k for k, c in bad["checks"].items()
              if not c["value"] <= c["limit"]
              and ok["checks"][k]["value"] <= ok["checks"][k]["limit"]]
    assert caught, (bad["checks"], ok["checks"])


def test_moe_cell_state_unchanged_is_caught(cells):
    line = tiny.run_tiny(cells, "moe-train-8k", patch=faults.state_unchanged)
    assert not line["correct"], line["checks"]


@pytest.mark.parametrize("workload", list(TINY_TRAFFIC))
def test_reference_control_is_not_correct(workload):
    """The cells' control is the reference at 4 bits in the program's
    place; its readings against the fp32 reference fail the limits."""
    bench = run.read_json(run.ROOT / "BENCHMARK.json")
    wl, cfg, traffic, limits = run.cell_spec(bench, workload)
    cfg = (_moonlight_tiny if cfg["reference"] == "ref_moonlight"
           else tiny._shrink)(cfg)[0]
    traffic = dict(traffic, **TINY_TRAFFIC[workload])
    driver = run.load(run.HERE / "drivers" / f"{traffic['driver']}.py")
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


def test_moe_work_counts_the_held_experts():
    """The work ``drivers/train_moe.py`` counts for the cell at its real
    size: 2.28 GFLOP a token for the 5-layer cut."""
    bench = run.read_json(run.ROOT / "BENCHMARK.json")
    wl, cfg, traffic, _ = run.cell_spec(bench, "moe-train-8k")
    driver = run.load(run.HERE / "drivers" / "train_moe.py")
    w = driver.work(dataclasses.make_dataclass("C", ["cfg", "traffic"])(
        cfg, traffic))
    tokens = traffic["seq"] * traffic["batch"]
    assert 2.0e9 < w["train_flops"] / tokens < 2.3e9
    # 4 expert layers x up, gate, down x 2 microbatches
    assert len(w["gmm_contractions"]) == 4 * 3 * 2
    rows = traffic["seq"] * 6 * 8 / 64
    assert w["gmm_contractions"][0][0] == 2 * rows * 2048 * 1408
