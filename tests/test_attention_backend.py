"""Cross-backend contract for the backend-dispatched int8 attention core.

The PR-3 bit-parity contract extended to attention: for fully-static
policies the `simulated` and `fused` backends must produce IDENTICAL
losses, gradients, parameters and quantization states under jit — the
simulated backend replays the fused kernel's exact block schedule and
online-softmax recurrence, so equality is bitwise, not approximate.

Also covered here:
  * the fused path computes its min/max statistics IN-KERNEL (zero
    standalone ``tensor_minmax`` passes on the attention sites),
  * ragged (non-block-multiple) shapes and runtime kv_len bounds,
  * fully-masked rows stay NaN-free in forward AND backward,
  * the sliding-window block-local fast path (grid width < nkv) and the
    skip of fully masked tiles, counted by ``AttnSchedule.visited_blocks``,
  * probability-site clip/SQNR counters and the widen guard,
  * the statistics mode: a telemetry-off policy runs the ``"range"``
    core (min and max only), bit-equal on every value training reads to
    the ``"full"`` core a telemetry-on policy runs,
  * ``qattn_int8_*`` / ``k_attn_*`` named scopes in compiled HLO,
  * the fused jitted train step never materializes the full fp score
    tile (checked on the compiled HLO via ``launch.hlo_cost``).
"""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import backend, qlinear, quant
from repro.core.policy import QuantPolicy
from repro.core.state import make_range_state
from repro.kernels import int8_attention as ia
from repro.kernels import tuning
from repro.kernels.int8_attention import make_schedule
from repro.launch import hlo_cost
from repro.models import attention as attn
from repro.telemetry import config as tconfig
from repro.telemetry import metrics as tmetrics

B, D, NH, NKV, HD = 2, 32, 4, 2, 8

MODE_CASES = [
    ("causal", {}),
    ("sliding", {"window": 8}),
    ("prefix", {"prefix_len": 5}),
    ("cross", {}),
]


def _setup(seq, n_heads=NH, n_kv=NKV, policy=None, seed=0):
    key = jax.random.PRNGKey(seed)
    params = attn.init_attention(key, D, n_heads, n_kv, HD, use_bias=False)
    sites = attn.init_attention_sites()
    if policy is not None and policy.stat_width != 3:
        sites = tmetrics.widen_state(sites, policy.stat_width)
    x = jax.random.normal(jax.random.PRNGKey(seed + 1), (B, seq, D),
                          jnp.float32)
    return params, sites, x


def _run_steps(policy, mode, *, seq=24, kv_seq=None, n_heads=NH, n_kv=NKV,
               steps=2, kv_len=None, p_leaf=None, **mode_kw):
    """A tiny 2-step training loop over one attention layer: SGD on the
    params, estimator update on the quant state between steps."""
    params, sites, x = _setup(seq, n_heads, n_kv, policy)
    kv_x = None
    if mode == "cross":
        kv_x = jax.random.normal(jax.random.PRNGKey(7),
                                 (B, kv_seq or seq + 8, D), jnp.float32)
    if p_leaf is not None:
        sites["core"]["p"]["act"] = p_leaf

    @jax.jit
    def one(params, sites, x, step):
        def loss_fn(p):
            y, ns, _ = attn.attention_layer(
                p, sites, x, n_heads=n_heads, n_kv=n_kv, head_dim=HD,
                mode=mode, kv_x=kv_x, kv_len=kv_len, policy=policy,
                seed=jnp.int32(11), step=step, **mode_kw)
            return jnp.sum(y ** 2), ns
        (loss, ns), grads = jax.value_and_grad(loss_fn, has_aux=True)(params)
        new_params = jax.tree_util.tree_map(lambda p, g: p - 1e-3 * g,
                                            params, grads)
        new_sites = qlinear.update_quant_state(policy, sites, ns)
        return loss, new_params, new_sites, grads

    losses, grads = [], None
    for t in range(steps):
        loss, params, sites, grads = one(params, sites, x, jnp.int32(t))
        losses.append(loss)
    return losses, params, sites, grads


def _assert_tree_equal(a, b, what):
    la = jax.tree_util.tree_leaves_with_path(a)
    lb = jax.tree_util.tree_leaves(b)
    assert len(la) == len(lb)
    for (path, x), y in zip(la, lb):
        np.testing.assert_array_equal(
            np.asarray(x), np.asarray(y),
            err_msg=f"{what}{jax.tree_util.keystr(path)}")


def _assert_backends_match(mode, **kw):
    sim = _run_steps(QuantPolicy.w8a8g8(backend="simulated"), mode, **kw)
    fus = _run_steps(QuantPolicy.w8a8g8(backend="fused"), mode, **kw)
    for s, f in zip(sim[0], fus[0]):
        np.testing.assert_array_equal(np.asarray(s), np.asarray(f),
                                      err_msg=f"{mode}: loss")
    _assert_tree_equal(sim[1], fus[1], f"{mode}: params")
    _assert_tree_equal(sim[2], fus[2], f"{mode}: quant state")
    _assert_tree_equal(sim[3], fus[3], f"{mode}: grads")
    return sim


# ---------------------------------------------------------------------------
# Bit parity: simulated == fused for every mask mode, 2 full steps.
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("mode,kw", MODE_CASES,
                         ids=[m for m, _ in MODE_CASES])
def test_backend_parity_all_mask_modes(mode, kw, monkeypatch):
    # Small blocks force a multi-block grid (3x3 kv/q blocks at seq 24).
    monkeypatch.setenv("REPRO_ATTN_BLOCK", "8,8")
    tuning.clear_cache()
    sim = _assert_backends_match(mode, **kw)
    # The core sites were visited and updated into sane hindsight states.
    core = sim[2]["core"]
    for name in ("q", "k", "v", "p"):
        leaf = np.asarray(core[name]["act"])
        assert leaf[2] == 1.0, (name, leaf)
        assert leaf[0] <= leaf[1], (name, leaf)
    p = np.asarray(core["p"]["act"])
    assert 0.0 <= p[0] and p[1] <= 1.0, p  # EMA stays in the softmax codomain


def test_backend_parity_gqa_broadcast(monkeypatch):
    monkeypatch.setenv("REPRO_ATTN_BLOCK", "8,8")
    tuning.clear_cache()
    # 4 query heads share 1 kv head: the kernel broadcasts each kv block
    # over the group via its BlockSpec index map.
    _assert_backends_match("causal", n_heads=4, n_kv=1)


def test_backend_parity_ragged_shapes(monkeypatch):
    # seq 29 is not a multiple of the 16-wide blocks: the kernel sees
    # clamped out-of-bounds tiles, the reference sees zero padding — the
    # masked-p-to-zero rule makes both contribute exactly nothing.
    monkeypatch.setenv("REPRO_ATTN_BLOCK", "16,16")
    tuning.clear_cache()
    _assert_backends_match("causal", seq=29)
    _assert_backends_match("cross", seq=19, kv_seq=29)


def test_runtime_kv_len_bound(monkeypatch):
    monkeypatch.setenv("REPRO_ATTN_BLOCK", "8,8")
    tuning.clear_cache()
    _assert_backends_match("cross", seq=16, kv_seq=24,
                           kv_len=jnp.int32(13))


def test_fully_masked_rows_are_nan_free(monkeypatch):
    """kv_len=0 masks every key: out rows must be exactly zero (l=0 hits
    the 1e-30 denominator guard) and gradients must stay finite on BOTH
    backends."""
    monkeypatch.setenv("REPRO_ATTN_BLOCK", "8,8")
    tuning.clear_cache()
    for bk in ("simulated", "fused"):
        losses, params, _, grads = _run_steps(
            QuantPolicy.w8a8g8(backend=bk), "cross", seq=16, kv_seq=24,
            kv_len=jnp.int32(0), steps=1)
        assert np.isfinite(np.asarray(losses[0]))
        for leaf in jax.tree_util.tree_leaves(grads):
            assert np.all(np.isfinite(np.asarray(leaf))), bk


# ---------------------------------------------------------------------------
# In-kernel statistics: no standalone min/max pass on the fused path.
# ---------------------------------------------------------------------------
def _trace_qattention(policy):
    g = NH // NKV
    q = jax.random.normal(jax.random.PRNGKey(0), (B, 16, NKV, g, HD))
    k = jax.random.normal(jax.random.PRNGKey(1), (B, 16, NKV, HD))
    v = jax.random.normal(jax.random.PRNGKey(2), (B, 16, NKV, HD))
    sites = attn.init_attention_sites()["core"]
    if policy.stat_width != 3:
        sites = tmetrics.widen_state(sites, policy.stat_width)

    def f(q, k, v):
        out, stats = backend.attention(policy, q, k, v, sites,
                                        mode="causal", scale=HD ** -0.5,
                                        step=jnp.int32(3))
        return out, stats
    return jax.make_jaxpr(f)(q, k, v)


def test_fused_core_has_no_standalone_minmax(monkeypatch):
    """The hindsight dataflow claim (paper fig. 4), checked structurally:
    the fused attention core emits its range statistics from the kernel's
    resident tiles, so tracing it calls ``quant.tensor_minmax`` ZERO
    times — while the simulated core needs it (first-batch fallback +
    observed stats)."""
    calls = []
    orig = quant.tensor_minmax
    monkeypatch.setattr(quant, "tensor_minmax",
                        lambda t, *a, **kw: calls.append(1) or orig(t, *a, **kw))

    _trace_qattention(QuantPolicy.w8a8g8(backend="simulated"))
    assert len(calls) > 0  # the monkeypatch sees the simulated path

    calls.clear()
    _trace_qattention(QuantPolicy.w8a8g8(backend="fused"))
    assert len(calls) == 0, "fused attention core ran a standalone minmax"


# ---------------------------------------------------------------------------
# Sliding-window block-local fast path.
# ---------------------------------------------------------------------------
def test_sliding_window_narrows_the_grid():
    sched = make_schedule(sq=256, skv=256, hd=64, bq=64, bkv=64, groups=1,
                          mode="sliding", window=64, sm_scale=0.125)
    assert sched.nkv == 4
    assert sched.width == 2  # each q block touches <= 2 kv blocks, not 4
    full = make_schedule(sq=256, skv=256, hd=64, bq=64, bkv=64, groups=1,
                         mode="causal", sm_scale=0.125)
    assert full.width == 4


def _dense_attend(sq, skv, mode, window):
    q = np.arange(sq)[:, None]
    k = np.arange(skv)[None, :]
    if mode == "bidir":
        return np.ones((sq, skv), bool)
    m = k <= q
    return m & (q - k < window) if mode == "sliding" else m


@pytest.mark.parametrize("seq,block,mode,window,visited", [
    (4096, 128, "causal", 0, 528),        # starcoder2-3b at seq 4096
    (4096, 128, "sliding", 4096, 528),    # its window covers the sequence
    (4096, 128, "bidir", 0, 1024),
    (256, 64, "sliding", 64, 7),          # block-local walk, width 2 of 4
    (256, 64, "sliding", 1000, 10),
])
def test_visited_blocks(seq, block, mode, window, visited):
    """The static count of computed tiles is the count of tiles holding at
    least one attended pair: every other tile is skipped."""
    sched = make_schedule(sq=seq, skv=seq, hd=64, bq=block, bkv=block,
                          groups=1, mode=mode, window=window,
                          sm_scale=0.125)
    n = seq // block
    tiles = _dense_attend(seq, seq, mode, window).reshape(
        n, block, n, block).any(axis=(1, 3))
    assert sched.visited_blocks == int(tiles.sum()) == visited


def _core_inputs(seq, bh, zb, seed=0):
    """Random on-grid core operands with realistic quant registers."""
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.randint(kq, (bh, seq, HD), 0, 256).astype(jnp.uint8)
    k = jax.random.randint(kk, (zb, seq, HD), -127, 128).astype(jnp.int8)
    v = jax.random.randint(kv, (zb, seq, HD), -127, 128).astype(jnp.int8)
    scale_p = 1.0 / 255.0
    regs = jnp.array([[128.0, 0.02 * HD ** -0.5, scale_p, 0.0,
                       scale_p * 0.01, 0.0, 1.0, 0.0]], jnp.float32)
    kvlen = jnp.full((1, 1), seq, jnp.int32)
    return q, k, v, regs, kvlen


def _core_both(sched, args):
    """(kernel, reference) results of one core call, each freshly traced."""
    return (jax.jit(lambda *a: ia.attention_kernel(*a, sched=sched))(*args),
            jax.jit(lambda *a: ia.attention_core_reference(
                *a, sched=sched))(*args))


def _assert_core_equal(a, b, what):
    for name, x, y in zip(("out", "ml", "pstats"), a, b):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y),
                                      err_msg=f"{what}: {name}")


def test_sliding_window_past_sequence_is_causal():
    """A window that covers the sequence skips the same fully masked tiles
    as the causal mask: kernel and reference agree bit for bit, and both
    equal the causal call (outputs, softmax residuals, all statistics)."""
    seq, g = 24, 2
    args = _core_inputs(seq, bh=2 * g, zb=2)
    kw = dict(sq=seq, skv=seq, hd=HD, bq=8, bkv=8, groups=g, sm_scale=1.0)
    sliding = make_schedule(mode="sliding", window=seq + 5, **kw)
    causal = make_schedule(mode="causal", **kw)
    assert sliding.width == causal.width == 3
    assert sliding.visited_blocks == causal.visited_blocks == 6
    fus, sim = _core_both(sliding, args)
    _assert_core_equal(fus, sim, "sliding fused vs simulated")
    _assert_core_equal(fus, _core_both(causal, args)[0], "sliding vs causal")


def test_sliding_skip_outside_window_is_exact(monkeypatch):
    """Block-local walk (width 2 of 3 kv blocks) where q block 3 (rows
    24..31) starts its walk at kv block 0 (keys 0..15), wholly outside its
    window of 8: the block is skipped, kernel and reference agree bit for
    bit, and outputs equal a run that computes every walked tile."""
    seq, g = 48, 2
    args = _core_inputs(seq, bh=2 * g, zb=2, seed=3)
    sched = make_schedule(sq=seq, skv=seq, hd=HD, bq=8, bkv=16, groups=g,
                          mode="sliding", window=8, sm_scale=1.0)
    assert (sched.width, sched.nkv) == (2, 3)
    assert sched.visited_blocks == 8 < sched.nq * sched.width
    assert not ia._block_visited(3, 0, sched)
    skip = _core_both(sched, args)
    _assert_core_equal(*skip, "skipped: fused vs simulated")
    monkeypatch.setattr(ia, "_block_visited", lambda *a, **k: None)
    for every in _core_both(sched, args):
        for name, x, y in zip(("out", "ml"), skip[0], every):
            np.testing.assert_array_equal(np.asarray(x), np.asarray(y),
                                          err_msg=f"every tile: {name}")


# ---------------------------------------------------------------------------
# The fp core: the same block walk in fp32, against a plain dense softmax.
# ---------------------------------------------------------------------------
def _dense_masked_softmax(q, k, v, *, mode, scale, window=0, prefix_len=0,
                          kv_len=None):
    """Plain attention on the cores' flattened layout: q [ZB, G, sq, hd],
    k [ZB, skv, hd], v [ZB, skv, hdv] -> [ZB, G, sq, hdv]; the whole
    [sq, skv] score tile at once."""
    sq, skv = q.shape[2], k.shape[1]
    qp = jnp.arange(sq)[:, None]
    kp = jnp.arange(skv)[None, :]
    if mode in ("cross", "bidir"):
        mask = jnp.ones((sq, skv), bool)
    elif mode == "prefix":
        mask = (kp <= qp) | (kp < prefix_len)
    elif mode == "sliding":
        mask = (kp <= qp) & (qp - kp < window)
    else:
        mask = kp <= qp
    if kv_len is not None:
        mask = mask & (kp < kv_len)
    s = jnp.einsum("zgqh,zkh->zgqk", q, k) * scale
    p = jax.nn.softmax(jnp.where(mask, s, -jnp.inf), axis=-1)
    return jnp.einsum("zgqk,zkh->zgqh", p, v)


FP_CORE_CASES = [
    # (mode, sq, skv, groups, hd, hdv, mode kwargs, runtime kv_len)
    ("causal", 40, 40, 1, 16, 16, {}, None),
    ("sliding", 48, 48, 1, 16, 16, {"window": 12}, None),
    ("prefix", 40, 40, 1, 16, 16, {"prefix_len": 13}, None),
    ("cross", 24, 40, 1, 16, 16, {}, 29),
    ("bidir", 32, 32, 1, 16, 16, {}, None),
    ("causal", 32, 32, 3, 16, 16, {}, None),
    ("causal", 32, 32, 2, 24, 16, {}, None),
]


@pytest.mark.parametrize(
    "mode,sq,skv,g,hd,hdv,kw,kv_len", FP_CORE_CASES,
    ids=["causal", "sliding", "prefix", "cross-kvlen", "bidir", "gqa",
         "hdv-below-hd"])
def test_fp_core_matches_dense_softmax(mode, sq, skv, g, hd, hdv, kw,
                                       kv_len):
    """``attention_core_fp`` on blocks of 8 (several q and kv blocks, the
    narrowed width under a sliding window, skipped blocks under causal and
    prefix masks) equals a plain dense masked softmax, and so do the
    gradients JAX takes through its walk."""
    zb, scale = 2, hd ** -0.5
    kq, kk, kv, kg = jax.random.split(jax.random.PRNGKey(0), 4)
    q = jax.random.normal(kq, (zb * g, sq, hd))
    k = jax.random.normal(kk, (zb, skv, hd))
    v = jax.random.normal(kv, (zb, skv, hdv))
    w = jax.random.normal(kg, (zb * g, sq, hdv))
    sched = make_schedule(sq=sq, skv=skv, hd=hd, hdv=hdv, bq=8, bkv=8,
                          groups=g, mode=mode, sm_scale=scale, **kw)
    assert sched.nq > 1 and sched.nkv > 1
    if mode == "sliding":
        assert sched.width < sched.nkv
    kvlen = jnp.full((1, 1), skv if kv_len is None else kv_len, jnp.int32)

    def core(q, k, v):
        return ia.attention_core_fp(q, k, v, kvlen, sched=sched)

    def plain(q, k, v):
        out = _dense_masked_softmax(q.reshape(zb, g, sq, hd), k, v,
                                    mode=mode, scale=scale, kv_len=kv_len,
                                    **kw)
        return out.reshape(zb * g, sq, hdv)

    got, want = jax.jit(core)(q, k, v), jax.jit(plain)(q, k, v)
    assert got.shape == (zb * g, sq, hdv) and got.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)
    grads = [jax.jit(jax.grad(lambda *a, f=f: jnp.sum(f(*a) * w),
                              argnums=(0, 1, 2)))(q, k, v)
             for f in (core, plain)]
    for name, a, b in zip("qkv", *grads):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-4,
                                   atol=1e-5, err_msg=f"d{name}")


# ---------------------------------------------------------------------------
# Statistics mode: "range" folds (min, max) only, "full" all six slots.
# ---------------------------------------------------------------------------
STATS_MODE_CASES = MODE_CASES + [("bidir", {})]


@pytest.mark.parametrize("mode,kw", STATS_MODE_CASES,
                         ids=[m for m, _ in STATS_MODE_CASES])
def test_range_stats_core(mode, kw, monkeypatch):
    """The "range" core equals the "full" one on out, the softmax
    residuals and (min, max), leaves slots 2-5 at their initial 0, never
    traces the err/sig tree sums, and kernel and reference agree on it
    bit for bit.  seq 20 leaves padded rows and columns in the last
    blocks, so the in-bounds mask of the statistics is exercised."""
    seq, g = 20, 2
    args = _core_inputs(seq, bh=2 * g, zb=2, seed=5)
    base = dict(sq=seq, skv=seq, hd=HD, bq=8, bkv=8, groups=g, mode=mode,
                sm_scale=1.0, **kw)
    rng = make_schedule(stats="range", **base)
    full = make_schedule(stats="full", **base)
    assert (rng.stat_slots, full.stat_slots) == (2, ia.STAT_SLOTS)

    sums = []
    orig = ia._tree_sum_last2
    monkeypatch.setattr(ia, "_tree_sum_last2",
                        lambda v: sums.append(1) or orig(v))
    r_kernel, r_ref = _core_both(rng, args)
    assert not sums, "a range core traced the err/sig tree sums"
    f_kernel, f_ref = _core_both(full, args)
    assert sums

    _assert_core_equal(r_kernel, r_ref, f"{mode} range: kernel vs reference")
    _assert_core_equal(f_kernel, f_ref, f"{mode} full: kernel vs reference")
    for name, x, y in zip(("out", "ml"), r_kernel, f_kernel):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y),
                                      err_msg=f"{mode} range vs full: {name}")
    r_st, f_st = np.asarray(r_kernel[2]), np.asarray(f_kernel[2])
    np.testing.assert_array_equal(r_st[..., :2], f_st[..., :2],
                                  err_msg=f"{mode} range vs full: min, max")
    np.testing.assert_array_equal(r_st[..., 2:], 0.0)
    assert np.all(f_st[..., 3] > 0)            # full counts every tile's n


def _traced_schedules(policy, monkeypatch):
    scheds = []
    orig = ia.make_schedule
    monkeypatch.setattr(
        ia, "make_schedule",
        lambda **kw: scheds.append(orig(**kw)) or scheds[-1])
    _trace_qattention(policy)
    return scheds


@pytest.mark.parametrize("bk", ["simulated", "fused"])
def test_qattention_stats_follow_telemetry(bk, monkeypatch):
    """The policy decides the core's statistics: min and max only with
    telemetry off, all six slots with it on."""
    off = QuantPolicy.w8a8g8(backend=bk)
    (sched,) = _traced_schedules(off, monkeypatch)
    assert (sched.stats, sched.stat_slots) == ("range", 2)
    (sched,) = _traced_schedules(off.with_telemetry(), monkeypatch)
    assert (sched.stats, sched.stat_slots) == ("full", ia.STAT_SLOTS)


@pytest.mark.parametrize("mode,kw", MODE_CASES,
                         ids=[m for m, _ in MODE_CASES])
def test_telemetry_changes_no_training_value(mode, kw, monkeypatch):
    """Two fused steps with telemetry off ("range" core) and on ("full"
    core): losses, params, grads and the range (min, max) of every quant
    state leaf are bit-equal."""
    monkeypatch.setenv("REPRO_ATTN_BLOCK", "8,8")
    tuning.clear_cache()
    off_policy = QuantPolicy.w8a8g8(backend="fused")
    off = _run_steps(off_policy, mode, **kw)
    on = _run_steps(off_policy.with_telemetry(), mode, **kw)
    for a, b in zip(off[0], on[0]):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                      err_msg=f"{mode}: loss")
    _assert_tree_equal(off[1], on[1], f"{mode}: params")
    _assert_tree_equal(off[3], on[3], f"{mode}: grads")
    rng = slice(tconfig.QMIN, tconfig.QMAX + 1)
    _assert_tree_equal(
        jax.tree_util.tree_map(lambda x: x[..., rng], off[2]),
        jax.tree_util.tree_map(lambda x: x[..., rng], on[2]),
        f"{mode}: quant state range")


# ---------------------------------------------------------------------------
# Probability-site telemetry: exact clip/SQNR counters + widen guard.
# ---------------------------------------------------------------------------
def test_p_site_telemetry_counters(monkeypatch):
    monkeypatch.setenv("REPRO_ATTN_BLOCK", "8,8")
    tuning.clear_cache()
    policy = QuantPolicy.w8a8g8(backend="fused").with_telemetry()
    _, _, sites, _ = _run_steps(policy, "causal", steps=1)
    p = np.asarray(sites["core"]["p"]["act"])
    assert p.shape == (tconfig.TELEMETRY_WIDTH,)
    # [0, 1] is the exact softmax codomain: nothing can clip...
    assert p[tconfig.T_CLIP] == 0.0
    # ...and the counters are EXACT full-tensor values (every probability
    # element is seen on a resident tile — bounded by BH * S * Skv).
    n = p[tconfig.T_N]
    assert 0 < n <= B * NH * 24 * 24
    # int8 quantization of a non-degenerate tensor has nonzero error and
    # signal, i.e. a finite positive SQNR.
    assert p[tconfig.T_ERR] > 0 and p[tconfig.T_SIG] > p[tconfig.T_ERR]
    assert 0 < p[tconfig.T_UTIL] <= 1.0 + 1e-6


def test_p_site_widen_guard_fires(monkeypatch):
    """A p range narrowed to [0, 0.25] clips the running-max entries
    (p=1.0 per row); the guard must widen it back within patience=1."""
    monkeypatch.setenv("REPRO_ATTN_BLOCK", "8,8")
    tuning.clear_cache()
    policy = QuantPolicy.w8a8g8(backend="fused").with_telemetry(
        guard=True, patience=1, clip_threshold=0.001)
    narrow = tmetrics.widen_state(make_range_state(0.0, 0.25),
                                  policy.stat_width)
    _, _, sites, _ = _run_steps(policy, "causal", steps=1, p_leaf=narrow)
    p = np.asarray(sites["core"]["p"]["act"])
    assert p[tconfig.T_CLIP] > 0  # the kernel counted the clipped entries
    assert p[tconfig.QMAX] > 0.25  # the widen guard fired on the p site


# ---------------------------------------------------------------------------
# Named scopes in compiled HLO (profiler-visible attention phases).
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("bk", ["simulated", "fused"])
def test_qattention_scopes_in_hlo(bk):
    policy = QuantPolicy.w8a8g8(backend=bk)
    g = NH // NKV
    q = jax.random.normal(jax.random.PRNGKey(0), (B, 16, NKV, g, HD))
    k = jax.random.normal(jax.random.PRNGKey(1), (B, 16, NKV, HD))
    v = jax.random.normal(jax.random.PRNGKey(2), (B, 16, NKV, HD))
    sites = attn.init_attention_sites()["core"]

    def f(q, k, v):
        out, _ = backend.attention(policy, q, k, v, sites, mode="causal",
                                    scale=HD ** -0.5, step=jnp.int32(0))
        return out.sum()

    txt = jax.jit(f).lower(q, k, v).compile().as_text()
    assert f"qattn_int8_{bk}" in txt
    assert "quant_attn_q" in txt
    if bk == "fused":
        assert "k_attn_fwd" in txt


# ---------------------------------------------------------------------------
# The fused train step never materializes the full fp score tile.
# ---------------------------------------------------------------------------
def _train_step_hlo(policy, seq):
    params, sites, x = _setup(seq, policy=policy)

    def step(params, sites, x):
        def loss_fn(p):
            y, ns, _ = attn.attention_layer(
                p, sites, x, n_heads=NH, n_kv=NKV, head_dim=HD,
                mode="causal", policy=policy, seed=jnp.int32(1),
                step=jnp.int32(0))
            return jnp.sum(y ** 2), ns
        (loss, ns), grads = jax.value_and_grad(loss_fn, has_aux=True)(params)
        return loss, ns, grads

    return jax.jit(step).lower(params, sites, x).compile().as_text()


def _score_tile_ops(text, seq):
    """All ops in the compiled module whose result holds an fp buffer with
    a trailing [seq, seq] score tile (parsed with the hlo_cost symbol
    machinery, so fusion bodies are inspected too)."""
    hits = []
    pat = re.compile(rf"\b(f32|bf16|f16)\[(?:\d+,)*{seq},{seq}\]")
    for comp in hlo_cost.parse_module(text).values():
        for op in comp.ops:
            if op.opcode in ("parameter", "get-tuple-element"):
                continue
            if pat.search(op.result_type):
                hits.append(f"{comp.name}/{op.name}: {op.result_type}")
    return hits


def test_fused_step_does_not_materialize_score_tile(monkeypatch):
    seq = 64
    monkeypatch.setenv("REPRO_ATTN_BLOCK", "16,16")
    tuning.clear_cache()
    # Sanity: the detector sees the [S, S] tile of a plain dense softmax.
    q, k, v = (jnp.ones((2, NH // NKV, seq, HD)), jnp.ones((2, seq, HD)),
               jnp.ones((2, seq, HD)))
    dense_txt = jax.jit(jax.grad(lambda q, k, v: jnp.sum(
        _dense_masked_softmax(q, k, v, mode="causal", scale=HD ** -0.5)))
        ).lower(q, k, v).compile().as_text()
    assert _score_tile_ops(dense_txt, seq), "detector lost the score tile"
    # The fused flash path streams kv blocks: nothing in the whole jitted
    # train step (fwd + recompute bwd) may hold a full [S, S] fp tile.
    fused_txt = _train_step_hlo(QuantPolicy.w8a8g8(backend="fused"), seq)
    hits = _score_tile_ops(fused_txt, seq)
    assert not hits, f"full score tile materialized: {hits[:4]}"


# ---------------------------------------------------------------------------
# Dispatch guards.
# ---------------------------------------------------------------------------
def _mla_run(policy):
    nope, rope, v_dim, rank = 16, 8, 8, 16
    params = attn.init_mla(jax.random.PRNGKey(0), D, NH, nope, rope, v_dim,
                           rank)
    sites = attn.init_mla_sites()
    x = jax.random.normal(jax.random.PRNGKey(1), (B, 24, D))
    _, ns = attn.mla_layer(params, sites, x, n_heads=NH, nope=nope,
                           rope=rope, v_dim=v_dim, rank=rank,
                           rope_theta=10000.0, norm_eps=1e-6, policy=policy,
                           seed=jnp.int32(0), step=jnp.int32(0))
    return sites, ns


def _gqa_run(policy):
    params, sites, x = _setup(24, policy=policy)
    _, ns, _ = attn.attention_layer(
        params, sites, x, n_heads=NH, n_kv=NKV, head_dim=HD, mode="causal",
        policy=policy, seed=jnp.int32(0), step=jnp.int32(0))
    return sites, ns


@pytest.mark.parametrize("layer", ["attention_layer", "mla_layer"])
@pytest.mark.parametrize("static", [True, False], ids=["static", "dynamic"])
def test_layers_dispatch_on_the_policy(layer, static, monkeypatch):
    """Both layers hand the core to ``backend.attention``: a static policy
    runs the int8 core (its replay on the simulated backend), a dynamic
    one the fp core, whose stats tree marks every core site unvisited."""
    ran = []
    for name in ("attention_core_reference", "attention_core_fp"):
        orig = getattr(ia, name)
        monkeypatch.setattr(ia, name, lambda *a, _n=name, _f=orig, **kw:
                            ran.append(_n) or _f(*a, **kw))
    policy = QuantPolicy.w8a8g8(
        backend="simulated", act_kind="hindsight" if static else "current")
    assert backend.qattention_eligible(policy) == static
    sites, ns = (_gqa_run if layer == "attention_layer" else _mla_run)(
        policy)
    assert ran == (["attention_core_reference"] if static
                   else ["attention_core_fp"])
    core = ns["core"]
    assert (jax.tree_util.tree_structure(core)
            == jax.tree_util.tree_structure(sites["core"]))
    visited = [bool(np.any(np.asarray(leaf) != 0))
               for leaf in jax.tree_util.tree_leaves(core)]
    assert all(visited) if static else not any(visited)


def test_dynamic_policy_keeps_fp_path():
    policy = QuantPolicy.w8a8g8(act_kind="current")
    assert not backend.qattention_eligible(policy)
    losses, _, sites, _ = _run_steps(policy, "causal", steps=1)
    assert np.isfinite(np.asarray(losses[0]))
    # the core was never visited on the fp path: the q leaf (zero-init)
    # stays uninitialized, the a-priori p leaf keeps its [0, 1] state.
    assert np.asarray(sites["core"]["q"]["act"])[2] == 0.0
    np.testing.assert_array_equal(np.asarray(sites["core"]["p"]["act"]),
                                  [0.0, 1.0, 1.0])


def test_disabled_policy_runs_fp_path():
    policy = QuantPolicy.disabled()
    assert not backend.qattention_eligible(policy)
    losses, _, sites, _ = _run_steps(policy, "causal", steps=1)
    assert np.isfinite(np.asarray(losses[0]))
    assert np.asarray(sites["core"]["q"]["act"])[2] == 0.0
