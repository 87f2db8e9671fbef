"""Moonlight-16B-A3B (latent attention, a sigmoid-routed dropless expert
layer that holds a share of the experts, a leading dense layer) against
the plain fp32 reference ``tests/ref_moonlight.py``, at a tiny size.

The program runs on the reference's weights (the same parameter layout).
Its compute dtype is float32 here, so that with quantization off the two
differ by fp32 rounding alone.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import ref_moonlight as ref
from repro import configs
from repro.core.policy import QuantPolicy
from repro.kernels import int8_attention, int8_grouped_matmul as gmm
from repro.models import attention, model, moe
from repro.optim import adamw
from repro.optim.schedules import constant
from repro.runtime import steps as steps_mod

B, S = 2, 32


def _cfg():
    return dataclasses.replace(configs.get_reduced("moonlight-16b-a3b"),
                               compute_dtype="float32")


def _ref_config(cfg) -> dict:
    """The reference's (published config.json) keys for ``cfg``."""
    m = cfg.moe
    first, held = m.held_range
    return {
        "hidden_size": cfg.d_model, "num_attention_heads": cfg.n_heads,
        "qk_nope_head_dim": cfg.qk_nope_head_dim,
        "qk_rope_head_dim": cfg.qk_rope_head_dim,
        "v_head_dim": cfg.v_head_dim, "kv_lora_rank": cfg.kv_lora_rank,
        "intermediate_size": cfg.d_ff, "moe_intermediate_size": m.d_expert,
        "n_shared_experts": m.d_shared // m.d_expert,
        "n_routed_experts": m.n_experts, "num_experts_per_tok": m.top_k,
        "experts_first": first, "experts_held": held,
        "vocab_size": cfg.vocab, "first_k_dense_replace": cfg.first_k_dense,
        "num_hidden_layers": cfg.n_layers, "rms_norm_eps": cfg.norm_eps,
        "rope_theta": cfg.rope_theta, "norm_topk_prob": True,
        "routed_scaling_factor": m.routed_scale,
        "router_bias_scale": 0.5,        # large enough to move choices
        "bias_update_speed": moe.BIAS_UPDATE_RATE,
        "seq_aux_alpha": m.aux_loss_coef,
    }


def _batch(cfg, seed=1):
    k = jax.random.PRNGKey(seed)
    toks = jax.random.randint(k, (B, S + 1), 0, cfg.vocab)
    return {"tokens": toks[:, :-1], "labels": toks[:, 1:],
            "mask": jnp.ones((B, S), jnp.float32)}


def _program_loss_grads(cfg, params, batch, policy):
    quant = model.init_quant_state(cfg, policy)

    def f(p):
        return model.loss_fn(p, quant, batch, cfg, policy, 0, 0)[0]

    return jax.jit(jax.value_and_grad(f))(params)


def _ref_loss_grads(c, params, batch):
    bias = params["decoder"]["blocks"]["b0"]["moe"]["router_bias"]
    f = jax.value_and_grad(
        lambda p: ref.loss(p, batch["tokens"], batch["labels"], c, bias,
                           q_block=16)[0])
    return jax.jit(f)(params)


@pytest.fixture(scope="module")
def setup():
    cfg = _cfg()
    c = _ref_config(cfg)
    params = jax.jit(lambda k: ref.init_params(k, c))(jax.random.PRNGKey(0))
    batch = _batch(cfg)
    return cfg, c, params, batch, _ref_loss_grads(c, params, batch)


def _leaf_gaps(g_prog, g_ref):
    """Per-leaf relative norm of the difference (gradient-free leaves, the
    router bias among them, are left out)."""
    out = {}
    for (path, a), b in zip(
            jax.tree_util.tree_flatten_with_path(g_prog)[0],
            jax.tree_util.tree_leaves(g_ref)):
        nb = float(jnp.linalg.norm(b))
        if nb > 0:
            out[jax.tree_util.keystr(path)] = float(jnp.linalg.norm(a - b)) / nb
    return out


def test_layout_matches_program(setup):
    cfg, c, params, _, _ = setup
    theirs = jax.eval_shape(lambda k: model.init_params(k, cfg),
                            jax.random.PRNGKey(0))
    assert jax.tree_util.tree_structure(params) == \
        jax.tree_util.tree_structure(theirs)
    for a, b in zip(jax.tree_util.tree_leaves(params),
                    jax.tree_util.tree_leaves(theirs)):
        assert a.shape == b.shape


def test_fp_loss_and_grads_match_reference(setup):
    """Quantization off: the architecture alone.  fp32 on both sides, so
    the gaps are summation order (1e-5 loss, 1e-4 per gradient leaf)."""
    cfg, c, params, batch, (l_ref, g_ref) = setup
    loss, grads = _program_loss_grads(cfg, params, batch,
                                      QuantPolicy.disabled())
    assert abs(float(loss) - float(l_ref)) < 1e-5 * abs(float(l_ref))
    gaps = _leaf_gaps(grads, g_ref)
    assert max(gaps.values()) < 1e-4, gaps


def test_quantized_loss_and_grads_near_reference(setup):
    """W8A8G8 hindsight (first batch: each range its own min/max) on both
    backends, which agree to fp rounding.  Against the fp32 reference the
    loss moves by about 1e-3 (limit 1e-2).  The gradient leaves move by a
    median 0.17 and at most 0.26 (limits 0.25 and 0.4): at 64 tokens, the
    8-bit residual stream flips the top-k choice of a few tokens, and each
    flip moves whole expert rows.  Without gradient quantization the gaps
    are as large (median 0.16), and the dense reduced starcoder2 reads a
    median of 0.05 under the same policy, so the size sets these limits,
    not int8 gradients."""
    cfg, c, params, batch, (l_ref, g_ref) = setup
    out = {}
    for name in ("simulated", "fused"):
        policy = QuantPolicy.w8a8g8().with_backend(name)
        loss, grads = out[name] = _program_loss_grads(cfg, params, batch,
                                                      policy)
        assert abs(float(loss) - float(l_ref)) < 1e-2 * abs(float(l_ref))
        gaps = _leaf_gaps(grads, g_ref)
        assert max(gaps.values()) < 0.4, gaps
        assert np.median(list(gaps.values())) < 0.25, gaps
    for a, b in zip(jax.tree_util.tree_leaves(out["simulated"]),
                    jax.tree_util.tree_leaves(out["fused"])):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-7)


def test_mla_matches_reference(setup):
    cfg, c, params, batch, _ = setup
    a = params["decoder"]["lead"]["l0"]["attn"]
    x = jax.random.normal(jax.random.PRNGKey(3), (B, S, cfg.d_model))
    pos = jnp.broadcast_to(jnp.arange(S), (B, S))
    want = ref._mla(x, a, pos, c, 16, None)
    sites = attention.init_mla_sites()
    got, _ = attention.mla_layer(
        a, sites, x, n_heads=cfg.n_heads, nope=cfg.qk_nope_head_dim,
        rope=cfg.qk_rope_head_dim, v_dim=cfg.v_head_dim,
        rank=cfg.kv_lora_rank, rope_theta=cfg.rope_theta,
        norm_eps=cfg.norm_eps, policy=QuantPolicy.disabled(),
        seed=jnp.int32(0), step=jnp.int32(0))
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


def test_router_bias_changes_the_choice():
    """Scores (0.9, 0.8, 0.7, 0.1), top-2: unbiased the choice is experts
    0 and 1; a bias of -0.2 on expert 1 makes it 0 and 2, weighed by the
    unbiased scores 0.9 and 0.7 normalised, times the scaling factor."""
    scores = jnp.array([0.9, 0.8, 0.7, 0.1])
    spec = moe.MoeSpec(n_experts=4, top_k=2, d_expert=8, scoring="sigmoid",
                       routed_scale=2.446)
    params = {"router": jnp.eye(4),
              "router_bias": jnp.array([0.0, -0.2, 0.0, 0.0])}
    x = jnp.log(scores / (1 - scores)).reshape(1, 1, 4)     # logits
    idx, w, _, _ = moe.route(params, x, spec)
    assert sorted(np.asarray(idx[0]).tolist()) == [0, 2]
    order = np.argsort(np.asarray(idx[0]))
    np.testing.assert_allclose(np.asarray(w[0])[order],
                               np.array([0.9, 0.7]) / 1.6 * 2.446, rtol=1e-6)
    params["router_bias"] = jnp.zeros(4)
    idx, _, _, _ = moe.route(params, x, spec)
    assert sorted(np.asarray(idx[0]).tolist()) == [0, 1]


def _layer_params(c, seed=5):
    """One expert layer's weights of the reference (all experts held)."""
    c_all = dict(c, experts_first=0, experts_held=c["n_routed_experts"])
    p = jax.jit(lambda k: ref.init_params(k, c_all))(jax.random.PRNGKey(seed))
    return jax.tree_util.tree_map(lambda w: w[0],
                                  p["decoder"]["blocks"]["b0"]["moe"]), c_all


def _share(p, first, count):
    out = dict(p)
    for name in ("w_up", "w_gate", "w_down"):
        out[name] = p[name][first:first + count]
    return out


def test_share_sum_equals_uncut_layer(setup):
    """Guide sec. 4: the expert layer cut to each of the shares of the
    experts, summed over the shares with the shared experts counted once,
    equals the uncut reference layer."""
    cfg, c, _, _, _ = setup
    p, c_all = _layer_params(c)
    x = jax.random.normal(jax.random.PRNGKey(7), (B, S, cfg.d_model))
    want, _, _ = ref._experts(x, p, p["router_bias"], c_all, None)
    e, shares = cfg.moe.n_experts, 4
    per = e // shares
    policy = QuantPolicy.disabled()
    sites = moe.init_moe_sites(cfg.moe)
    shared = ref._swiglu(x, p["shared"]["w_up"], p["shared"]["w_gate"],
                         p["shared"]["w_down"], None)
    total = shared
    for i in range(shares):
        spec = dataclasses.replace(cfg.moe, held=(i * per, per))
        y, _, met = moe.apply_moe(_share(p, i * per, per), sites, x, spec,
                                  policy=policy, seed=jnp.int32(0),
                                  step=jnp.int32(0))
        total = total + (y - shared)
        assert float(met["moe_rows"] + met["moe_unheld"]) == \
            B * S * cfg.moe.top_k
    np.testing.assert_allclose(total, want, rtol=1e-4, atol=1e-5)


def test_dropless_when_every_token_picks_one_expert(setup):
    """A bias that puts one expert in every token's top-k: that expert
    takes all B*S tokens (a capacity dispatch would have dropped most of
    them), and the layer still equals the reference."""
    cfg, c, _, _, _ = setup
    p, c_all = _layer_params(c)
    p = dict(p, router_bias=p["router_bias"].at[3].set(100.0))
    x = jax.random.normal(jax.random.PRNGKey(8), (B, S, cfg.d_model))
    spec = dataclasses.replace(cfg.moe, held=None)
    y, _, met = moe.apply_moe(p, moe.init_moe_sites(spec), x, spec,
                              policy=QuantPolicy.disabled(),
                              seed=jnp.int32(0), step=jnp.int32(0))
    assert float(met["moe_rows"]) == B * S * spec.top_k
    assert float(met["moe_rows_max"]) == B * S
    assert float(met["moe_unheld"]) == 0.0
    want, _, load = ref._experts(x, p, p["router_bias"], c_all, None)
    assert float(load[3]) == B * S
    np.testing.assert_allclose(y, want, rtol=1e-4, atol=1e-5)


def test_grouped_kernel_matches_reference_bit_for_bit():
    """Empty groups, a group that spans two row tiles and ends inside the
    second, a one-row group, and dead tiles after the live ones."""
    k, n = 96, 160
    counts = jnp.array([300, 0, 1, 0, 37], jnp.int32)
    tiles = gmm.plan_tiles(counts, 7)
    assert int(tiles.live[0]) == 4
    assert np.asarray(tiles.tile_rows).tolist() == [256, 44, 1, 37, 0, 0, 0]
    assert np.asarray(tiles.tile_group).tolist()[:4] == [0, 0, 2, 4]
    r = 7 * gmm.GMM_ROWS
    x = jax.random.randint(jax.random.PRNGKey(0), (r, k), 0, 256
                           ).astype(jnp.uint8)
    w = jax.random.randint(jax.random.PRNGKey(1), (5, k, n), -127, 128
                           ).astype(jnp.int8)
    zp, alpha = jnp.float32(117.0), jnp.float32(0.013)
    got = gmm.grouped_matmul(x, w, zp, alpha, tiles)
    want = gmm.grouped_matmul_reference(x, w, zp, alpha, tiles)
    assert bool(jnp.all(got == want))
    # and the arithmetic itself: alpha * (x - zp) @ w_group, rows by group
    rx = np.asarray(x, np.int64) - 117
    wn = np.asarray(w, np.int64)
    for rows, g in ((slice(0, 300), 0), (slice(512, 513), 2),
                    (slice(768, 805), 4)):
        np.testing.assert_allclose(np.asarray(got[rows]),
                                   0.013 * (rx[rows] @ wn[g]), rtol=1e-6)
    valid = np.asarray(gmm.row_valid(tiles))
    assert valid.sum() == 338 and not np.asarray(got)[~valid].any()


def test_attention_kernel_latent_head_dims():
    """Query-key heads of 192, value heads of 128: the kernel equals its
    order-pinned reference bit for bit, and both are the softmax attention
    of the dequantized operands to int8 rounding."""
    sq, hd, hdv, bh = 256, 192, 128, 2
    sched = int8_attention.make_schedule(
        sq=sq, skv=sq, hd=hd, hdv=hdv, bq=128, bkv=128, groups=1,
        mode="causal", sm_scale=hd ** -0.5)
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.randint(ks[0], (bh, sq, hd), 0, 256).astype(jnp.uint8)
    k = jax.random.randint(ks[1], (bh, sq, hd), -127, 128).astype(jnp.int8)
    v = jax.random.randint(ks[2], (bh, sq, hdv), -127, 128).astype(jnp.int8)
    sq_, sk_, sv_ = 0.02, 0.01, 0.03
    alpha_qk = hd ** -0.5 * sq_ * sk_
    regs = jnp.array([[128.0, alpha_qk, 1 / 255, 0.0, sv_ / 255, 0.0, 1.0,
                       0.0]], jnp.float32)
    kvlen = jnp.full((1, 1), sq, jnp.int32)
    out, ml, ps = int8_attention.attention_kernel(q, k, v, regs, kvlen,
                                                  sched=sched)
    r_out, r_ml, r_ps = int8_attention.attention_core_reference(
        q, k, v, regs, kvlen, sched=sched)
    assert out.shape == (bh, sq, hdv)
    assert bool(jnp.all(out == r_out)) and bool(jnp.all(ml == r_ml))
    assert bool(jnp.all(ps == r_ps))
    qf = (q.astype(jnp.float32) - 128) * sq_
    s = jnp.einsum("bqh,bkh->bqk", qf, k * sk_) * hd ** -0.5
    s = jnp.where(jnp.tril(jnp.ones((sq, sq), bool)), s, -jnp.inf)
    want = jnp.einsum("bqk,bkh->bqh", jax.nn.softmax(s, -1), v * sv_)
    np.testing.assert_allclose(out, want, atol=0.02 * float(
        jnp.max(jnp.abs(want))))


def test_bias_update_follows_the_rule():
    """bias += gamma * sign(mean load - load): loads (3, 1, 2, 2) around
    their mean 2 move the bias by (-gamma, +gamma, 0, 0)."""
    old = {"moe": {"router_bias": jnp.array([0.1, 0.2, 0.3, 0.4]),
                   "router": jnp.ones((2, 4))}}
    new = jax.tree_util.tree_map(lambda x: x * 0 + 9.0, old)
    loads = {"moe": {"router_bias": jnp.array([3.0, 1.0, 2.0, 2.0]),
                     "router": jnp.zeros((2, 4))}}
    out = moe.update_router_bias(new, old, loads, 1e-3)
    np.testing.assert_allclose(out["moe"]["router_bias"],
                               [0.099, 0.201, 0.3, 0.4], rtol=1e-6)
    assert bool(jnp.all(out["moe"]["router"] == 9.0))


def test_train_step_moves_the_bias_by_the_rule(setup):
    """In the train step the bias moves by exactly -gamma, 0 or +gamma per
    expert (no optimizer update, no weight decay), against the load that
    the reference's routing of the same batch gives."""
    cfg, c, params, batch, _ = setup
    opt = adamw()
    step = jax.jit(steps_mod.make_train_step(
        cfg, QuantPolicy.disabled(), opt, constant(1e-2), grad_accum=2))
    state = {"params": params, "opt": opt.init(params),
             "quant": model.init_quant_state(cfg),
             "step": jnp.zeros((), jnp.int32)}
    old = params["decoder"]["blocks"]["b0"]["moe"]["router_bias"]
    state, _ = step(state, batch)
    new = state["params"]["decoder"]["blocks"]["b0"]["moe"]["router_bias"]
    _, load = jax.jit(lambda p: ref.loss(p, batch["tokens"], batch["labels"],
                                         c, old, q_block=16))(params)
    rule = old + moe.BIAS_UPDATE_RATE * jnp.sign(
        jnp.mean(load, -1, keepdims=True) - load)
    np.testing.assert_allclose(new, rule, rtol=0, atol=1e-7)
    assert float(jnp.max(jnp.abs(new - old))) > 0


def test_serve_refuses_latent_attention():
    from repro.launch import serve
    with pytest.raises(SystemExit, match="latent attention"):
        serve.main(["--arch", "moonlight-16b-a3b", "--reduced"])


def test_registered_config_is_the_published_one():
    cfg = configs.get("moonlight-16b-a3b")
    m = cfg.moe
    assert (cfg.n_layers, cfg.d_model, cfg.n_heads, cfg.vocab) == \
        (27, 2048, 16, 163840)
    assert (cfg.kv_lora_rank, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
            cfg.v_head_dim) == (512, 128, 64, 128)
    assert (cfg.first_k_dense, cfg.d_ff, cfg.norm_eps, cfg.rope_theta) == \
        (1, 11264, 1e-5, 50000.0)
    assert (m.n_experts, m.top_k, m.d_expert, m.d_shared, m.scoring,
            m.routed_scale) == (64, 6, 1408, 2816, "sigmoid", 2.446)
    assert not cfg.tie_embeddings
    cut = configs.get("moonlight-16b-a3b-5l")
    assert dict((k, (a, b)) for k, a, b in cut.reduced) == {
        "n_layers": (27, 5), "experts_held": (64, 8),
        "vocab": (163840, 20480)}
    assert cut.moe.held == (0, 8) and cut.moe.n_experts == 64


def test_cli_trains_fused_and_records_routing(tmp_path, monkeypatch):
    """``launch/train.py`` trains the config on the fused backend, and its
    "perf" record carries the expert layers' routing counters."""
    import json

    from repro.launch import train
    # the test process keeps its own SIGINT / SIGTERM handlers
    monkeypatch.setattr(train.signal, "signal", lambda *a: None)
    train.main(["--arch", "moonlight-16b-a3b", "--reduced", "--steps", "2",
                "--batch", "2", "--seq", "16", "--backend", "fused",
                "--telemetry", "--telemetry-dir", str(tmp_path)])
    lines = [json.loads(x) for x in
             (tmp_path / "telemetry.jsonl").read_text().splitlines()]
    perf = [x["perf"] for x in lines if "perf" in x]
    assert perf and set(perf[-1]["moe"]) == set(moe.COUNTERS)
    rows = perf[-1]["moe"]
    # 2 expert layers x 32 tokens x top-4 assignments, held or not
    assert rows["moe_rows"] + rows["moe_unheld"] == 2 * 32 * 4
