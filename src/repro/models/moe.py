"""Mixture-of-Experts FFN (Qwen2-MoE / Moonlight family): a dropless expert
layer that is told which experts it holds.

  * router: fp32 dense over ALL experts (NOT quantized — the top-k boundary
    is numerically sensitive and the matmul is tiny; paper practice is to
    keep sensitive ops in fp).  ``scoring="softmax"`` (Qwen2-MoE) takes the
    top-k of the softmax; ``scoring="sigmoid"`` (DeepSeek-V3 / Moonlight)
    chooses the top-k of ``sigmoid(logits) + bias`` and weighs by the
    unbiased scores.  The chosen weights are renormalised to sum 1 and
    scaled by ``routed_scale``.
  * the correction bias of a sigmoid router is non-gradient state kept
    beside the weights (``router_bias``): each step's routing load reaches
    the train step through its cotangent channel (``_load_channel``), and
    the step moves the bias by the aux-loss-free rule (arXiv:2412.19437
    sec. 2.1.2, :func:`update_router_bias`) instead of an optimizer update.
    Its first draw and its update speed are DeepSeek-V3's (``BIAS_*``).
  * the layer holds experts ``[first, first + count)`` of ``n_experts``
    (``MoeSpec.held``; all of them by default, and on a mesh).  It routes
    over every expert, keeps the assignments that land on a held expert,
    sorts them by expert (stable), gathers their rows into a buffer sized
    for the worst case (every assignment held) and runs up/gate/down as
    grouped int8 contractions over the held experts
    (:func:`repro.core.backend.qgmm`; the kernel computes the live row
    tiles only).  No assignment is dropped.  The outputs are weighed by
    the gates, gathered back per token and summed; shared experts (Qwen2-
    MoE: one block of 4; Moonlight: 2) run on every token as a dense GLU.
  * auxiliary losses: softmax routers keep the Shazeer load-balance loss
    and the router z-loss; sigmoid routers the sequence-wise balance loss
    (arXiv:2412.19437 eq. 17-20).

Every routed expert contraction is a quantized site with one per-tensor
range over the held experts (the paper's per-tensor setting), and the up
and gate projections share one activation site.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp

from repro.core import backend, qlinear
from repro.core.policy import QuantPolicy

from .layers import GLU_KINDS, activation, apply_mlp, init_mlp, init_mlp_sites

# Step metrics of an expert layer, summed over the stack's expert layers:
# assignments computed on the held experts, the busiest held expert's
# assignments, assignments routed to experts not held here, and the
# grouped kernel's live and grid row tiles.
COUNTERS = ("moe_rows", "moe_rows_max", "moe_unheld", "moe_tiles_live",
            "moe_tiles_grid")

# A sigmoid router's correction bias: the normal scale of its first draw
# (a trained model's bias is learnt state) and gamma of the aux-loss-free
# update, DeepSeek-V3's 0.001 (arXiv:2412.19437 sec. 4.2).
BIAS_INIT_SCALE = 0.01
BIAS_UPDATE_RATE = 1e-3


@dataclasses.dataclass(frozen=True)
class MoeSpec:
    n_experts: int
    top_k: int
    d_expert: int              # per-expert FFN hidden size
    n_shared: int = 0          # shared experts (always-on)
    d_shared: int = 0          # shared-expert hidden size (total)
    mlp_kind: str = "swiglu"
    scoring: str = "softmax"   # softmax | sigmoid (with a correction bias)
    routed_scale: float = 1.0
    aux_loss_coef: float = 0.01
    z_loss_coef: float = 1e-3
    held: Optional[tuple] = None    # (first, count) experts held; None: all

    @property
    def held_range(self) -> tuple:
        return self.held or (0, self.n_experts)


def init_moe(key, d_model: int, spec: MoeSpec, dtype=jnp.float32) -> dict:
    k_router, k_up, k_gate, k_down, k_shared, k_bias = jax.random.split(key, 6)
    e, f = spec.held_range[1], spec.d_expert
    s_in, s_out = d_model ** -0.5, f ** -0.5
    p = {
        "router": (jax.random.normal(k_router, (d_model, spec.n_experts))
                   * s_in).astype(jnp.float32),
        "w_up": (jax.random.normal(k_up, (e, d_model, f)) * s_in).astype(dtype),
        "w_down": (jax.random.normal(k_down, (e, f, d_model)) * s_out).astype(dtype),
    }
    if spec.scoring == "sigmoid":
        p["router_bias"] = (jax.random.normal(k_bias, (spec.n_experts,))
                            * BIAS_INIT_SCALE).astype(jnp.float32)
    if spec.mlp_kind in GLU_KINDS:
        p["w_gate"] = (jax.random.normal(k_gate, (e, d_model, f)) * s_in).astype(dtype)
    if spec.n_shared:
        p["shared"] = init_mlp(k_shared, d_model, spec.d_shared, spec.mlp_kind,
                               use_bias=False, dtype=dtype)
    return p


def init_moe_sites(spec: MoeSpec) -> dict:
    sites = {"up": qlinear.init_site(), "down": qlinear.init_site()}
    if spec.mlp_kind in GLU_KINDS:
        sites["gate"] = qlinear.init_site()
    if spec.n_shared:
        sites["shared"] = init_mlp_sites(spec.mlp_kind)
    return sites


# ---------------------------------------------------------------------------
# Routing.
# ---------------------------------------------------------------------------
@jax.custom_vjp
def _load_channel(logits, bias, load):
    """Identity on ``logits``; the cotangent of ``bias`` is ``load`` (the
    step's assignments per expert), so the train step receives the load
    beside the gradients, summed over microbatches like them."""
    return logits


def _load_channel_fwd(logits, bias, load):
    return logits, load


def _load_channel_bwd(load, g):
    return g, load, jnp.zeros_like(load)


_load_channel.defvjp(_load_channel_fwd, _load_channel_bwd)


def route(params: dict, x: jax.Array, spec: MoeSpec):
    """x [B, S, D] -> (expert ids [T, K], weights [T, K] fp32, aux, z)."""
    b, s, d = x.shape
    logits = jnp.einsum("td,de->te", x.reshape(b * s, d).astype(jnp.float32),
                        params["router"])                          # fp32 router
    e, k = spec.n_experts, spec.top_k
    if spec.scoring == "sigmoid":
        bias = params["router_bias"]
        choice = jax.nn.sigmoid(jax.lax.stop_gradient(logits)) + \
            jax.lax.stop_gradient(bias)
        _, idx = jax.lax.top_k(choice, k)
    else:
        _, idx = jax.lax.top_k(jax.lax.stop_gradient(logits), k)
    chosen = jnp.sum(jax.nn.one_hot(idx, e, dtype=jnp.float32), axis=1)
    if spec.scoring == "sigmoid":
        scores = jax.nn.sigmoid(_load_channel(logits, bias,
                                              jnp.sum(chosen, axis=0)))
    else:
        scores = jax.nn.softmax(logits, axis=-1)
    w = jnp.take_along_axis(scores, idx, axis=-1)
    w = w / jnp.maximum(jnp.sum(w, axis=-1, keepdims=True), 1e-20) \
        * spec.routed_scale

    if spec.scoring == "sigmoid":
        # Sequence-wise balance loss: per sequence, E / (K S) * chosen
        # counts times the mean of the per-token normalised scores.
        frac = chosen.reshape(b, s, e).sum(1) * (e / (k * s))
        norm = scores / jnp.sum(scores, axis=-1, keepdims=True)
        prob = norm.reshape(b, s, e).mean(1)
        aux = jnp.mean(jnp.sum(frac * prob, axis=-1))
        z = jnp.float32(0.0)
    else:
        # Shazeer load-balance loss: E * mean(fraction routed) . mean(prob).
        aux = e * jnp.sum(jnp.mean(chosen, 0) * jnp.mean(scores, 0))
        z = jnp.mean(jax.nn.logsumexp(logits, axis=-1) ** 2)
    return idx, w, aux, z


def update_router_bias(new_params, params, grads, rate: float):
    """The aux-loss-free rule: each ``router_bias`` leaf of ``new_params``
    becomes its value in ``params`` plus ``rate * sign(mean load - load)``,
    the load being that leaf's cotangent in ``grads``; other leaves pass."""
    def upd(path, new, old, load):
        if getattr(path[-1], "key", None) != "router_bias":
            return new
        mean = jnp.mean(load, axis=-1, keepdims=True)
        return old + rate * jnp.sign(mean - load)
    return jax.tree_util.tree_map_with_path(upd, new_params, params, grads)


# ---------------------------------------------------------------------------
# The expert layer.
# ---------------------------------------------------------------------------
def _dispatch(idx: jax.Array, spec: MoeSpec):
    """Rows of the held experts' buffer for ``idx`` [T, K].

    Returns ``(slot [T, K] buffer row of each assignment (R where not
    held), row_token [R] token of each row (T where none), tiles, counts)``.
    """
    from repro.kernels import int8_grouped_matmul as gmm
    t, k = idx.shape
    first, g = spec.held_range
    n_tiles = -(-t * min(k, g) // gmm.GMM_ROWS) + g
    r = n_tiles * gmm.GMM_ROWS
    local = idx.reshape(-1) - first
    key = jnp.where((local >= 0) & (local < g), local, g)          # [T*K]
    counts = jnp.sum(key[:, None] == jnp.arange(g, dtype=key.dtype), axis=0,
                     dtype=jnp.int32)
    tiles = gmm.plan_tiles(counts, n_tiles)
    order = jnp.argsort(key, stable=True)
    key_s = key[order]
    kc = jnp.minimum(key_s, g - 1)
    start = jnp.cumsum(counts) - counts
    pad_start = jnp.cumsum(tiles.group_rows) - tiles.group_rows
    rank = jnp.arange(t * k, dtype=jnp.int32) - start[kc]
    dest_s = jnp.where(key_s < g, pad_start[kc] + rank, r)
    slot = jnp.zeros((t * k,), jnp.int32).at[order].set(dest_s)
    row_token = jnp.full((r,), t, jnp.int32).at[slot].set(
        jnp.arange(t * k, dtype=jnp.int32) // k, mode="drop")
    return slot.reshape(t, k), row_token, tiles, counts


def _take_rows(x, rows):
    return jnp.take(x, rows, axis=0, mode="fill", fill_value=0)


def _expert_matmul(xr, xrt, w, site, policy, tiles, seed, step):
    wq, wqt = qlinear.quantize_weight_q(w, policy)
    y = backend.qgmm(policy, xr, xrt, wq.astype(xr.dtype), wqt, tiles)
    return qlinear.grad_quant_barrier(y, site["grad"], policy, seed, step)


def apply_moe(
    params: dict,
    sites: dict,
    x: jax.Array,                   # [B, S, D]
    spec: MoeSpec,
    *,
    policy: QuantPolicy,
    seed: jax.Array,
    step: jax.Array,
) -> tuple[jax.Array, dict, dict]:
    """Returns (y, new_sites, metrics: aux_loss, z_loss and COUNTERS)."""
    b, s, d = x.shape
    t = b * s
    with jax.named_scope("moe_route"):
        idx, w, aux, z = route(params, x, spec)
    new_sites = dict(sites)
    # One activation site for the up and gate projections, quantized per
    # token before the gather: the gather moves the int8 image.
    xq, x_stats, xqi = qlinear.act_quant_site(x.reshape(t, d),
                                              sites["up"]["act"], policy, step)
    with jax.named_scope("moe_permute"):
        slot, row_token, tiles, counts = _dispatch(idx, spec)
        xr = _take_rows(xq, row_token)
        xrt = None if xqi is None else xqi._replace(
            q=_take_rows(xqi.q, row_token))
    up = _expert_matmul(xr, xrt, params["w_up"], sites["up"], policy, tiles,
                        seed, step)
    if spec.mlp_kind in GLU_KINDS:
        gate = _expert_matmul(xr, xrt, params["w_gate"], sites["gate"],
                              policy, tiles, seed + 1, step)
        new_sites["gate"] = {"act": qlinear.stats_zeros(policy),
                             "grad": qlinear.stats_zeros(policy)}
        h = activation(gate, {"swiglu": "silu", "geglu": "gelu",
                              "reglu": "relu"}[spec.mlp_kind]) * up
    else:
        h = activation(up, spec.mlp_kind)
    new_sites["up"] = {"act": x_stats, "grad": qlinear.stats_zeros(policy)}
    hq, h_stats, hqi = qlinear.act_quant_site(h, sites["down"]["act"],
                                              policy, step)
    out = _expert_matmul(hq, hqi, params["w_down"], sites["down"], policy,
                         tiles, seed + 2, step)
    new_sites["down"] = {"act": h_stats, "grad": qlinear.stats_zeros(policy)}

    with jax.named_scope("moe_combine"):
        held = slot < out.shape[0]
        gw = jnp.where(held, w, 0.0).astype(out.dtype)
        y = jnp.einsum("tk,tkd->td", gw, _take_rows(out, slot))
        y = y.reshape(b, s, d)

    if spec.n_shared:
        ys, new_sites["shared"] = apply_mlp(
            params["shared"], sites["shared"], x, spec.mlp_kind, policy,
            seed=seed + 3, step=step)
        y = y + ys

    rows = jnp.sum(counts).astype(jnp.float32)
    metrics = {
        "aux_loss": spec.aux_loss_coef * aux,
        "z_loss": spec.z_loss_coef * z,
        "moe_rows": rows,
        "moe_rows_max": jnp.max(counts).astype(jnp.float32),
        "moe_unheld": jnp.float32(t * spec.top_k) - rows,
        "moe_tiles_live": tiles.live[0].astype(jnp.float32),
        "moe_tiles_grid": jnp.float32(tiles.tile_rows.shape[0]),
    }
    return y, new_sites, metrics
