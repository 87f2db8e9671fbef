"""Logical-axis sharding rules -> PartitionSpec / NamedSharding trees.

Mesh axes (see ``repro.launch.mesh``):

    single-pod   (data=16, model=16)
    multi-pod    (pod=2, data=16, model=16)

Parallelism layout (MaxText-style 2D "fsdp x tensor"):

  * batch over the DP axes ``(pod, data)``,
  * weights: the "wide" matmul dim over ``model`` (Megatron TP — column-
    parallel qkv/up, row-parallel o/down, so each matmul pair costs one
    all-reduce), the other dim over ``data`` (ZeRO-3/FSDP — parameters and
    optimizer state scale with the full device count; the all-gathers this
    inserts overlap with compute in XLA's latency-hiding scheduler),
  * MoE experts over ``model`` (expert parallelism),
  * quantization-range state: replicated scalars (the per-shard min/max
    partials reduce with one fused scalar all-reduce — the distributed
    analogue of the paper's accumulator-side statistics logic).

Rules are name+path based so the same table covers raw parameter trees,
optimizer-moment trees (same leaf names under ``m``/``v``), and scanned
stacks (leading ``repeats`` dim -> ``None`` prepended).

``hint(x, ...)`` is the in-model activation-constraint helper: a no-op
unless a hint mapping is active (so CPU unit tests never touch mesh
machinery), and a ``with_sharding_constraint`` under an active mesh.
"""
from __future__ import annotations

import contextlib
from typing import Any, Optional

import jax
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

PyTree = Any

# ---------------------------------------------------------------------------
# Activation hints.
# ---------------------------------------------------------------------------
_HINTS: Optional[dict] = None


@contextlib.contextmanager
def activation_hints(mapping: dict):
    """mapping: logical axis name -> mesh axis (str/tuple) or None."""
    global _HINTS
    prev, _HINTS = _HINTS, mapping
    try:
        yield
    finally:
        _HINTS = prev


def hint(x, *logical_axes):
    """Constrain ``x`` to the active mapping of ``logical_axes`` (one per
    dim; None = unconstrained).  Identity when no mapping is active."""
    if _HINTS is None:
        return x
    spec = P(*[None if a is None else _HINTS.get(a) for a in logical_axes])
    return jax.lax.with_sharding_constraint(x, spec)


def choose_head_axis(kv: int, g: int, msize: int) -> str:
    """'kv' or 'g': which head dim to shard over the model axis.  Exact
    division wins; otherwise the larger dim (GSPMD pads the remainder)."""
    if kv % msize == 0:
        return "kv"
    if g % msize == 0:
        return "g"
    return "g" if g >= kv else "kv"


def replicate_hint(x):
    """Force full replication at this point (int8 weight-gather pinning).
    No-op without an active hint mapping."""
    if _HINTS is None:
        return x
    return jax.lax.with_sharding_constraint(x, P())


def attn_hints(q, k, v):
    """Head sharding for the attention core [B, S, KV, G, hd] / [B, S, KV,
    hd] (``hint_heads``): q's heads exactly where KV or G divides the
    model axis and padded otherwise; k/v's only where KV divides it."""
    q = hint_heads(q, kv_axis=2, g_axis=3)
    if k is not None:
        k = hint_heads(k, kv_axis=2, g_axis=2)
        v = hint_heads(v, kv_axis=2, g_axis=2)
    return q, k, v


def hint_heads(q, kv_axis: int, g_axis: int):
    """Shard an attention tensor over heads on the ``model`` axis.

    GSPMD cannot propagate a model-axis sharding through the
    ``[.., H*hd] -> [.., KV, G, hd]`` reshape when the head counts do not
    divide the axis — it silently falls back to REPLICATING the whole
    attention core over ``model`` (16x redundant compute+memory; found via
    the per-computation HLO byte ranking, see EXPERIMENTS.md §Perf).  This
    hint picks, at trace time, whichever of the KV / G dims divides the
    model-axis size (preferring exact division; otherwise the larger dim,
    accepting GSPMD padding)."""
    if _HINTS is None:
        return q
    maxis = _HINTS.get("model")
    msize = _HINTS.get("model_size")
    bspec = _HINTS.get("batch")
    if maxis is None or not msize:
        return q
    kv, g = q.shape[kv_axis], q.shape[g_axis]
    axes = [None] * q.ndim
    axes[0] = bspec
    if kv_axis == g_axis:
        # single head dim (k/v of GQA): shard only when it divides exactly
        # — padding a small KV dim 8-16x would waste more than replication.
        if kv % msize == 0:
            axes[kv_axis] = maxis
        else:
            return q
    else:
        which = choose_head_axis(kv, g, msize)
        axes[kv_axis if which == "kv" else g_axis] = maxis
    return jax.lax.with_sharding_constraint(q, P(*axes))


# ---------------------------------------------------------------------------
# Parameter rules.
# ---------------------------------------------------------------------------
def _path_str(path) -> str:
    return "/".join(str(getattr(p, "key", p)) for p in path)


DEFAULT_MODEL_SIZE = 16   # model-axis extent of the production meshes


def _param_rule(pathstr: str, name: str, shape: tuple) -> tuple:
    """PartitionSpec entries for the TRAILING logical dims of a leaf."""
    moe_routed = "/moe/" in pathstr + "/" and "shared" not in pathstr
    ms = DEFAULT_MODEL_SIZE
    if name == "embed":
        return ("model", "data")          # [V, D]
    if name == "head":
        return ("data", "model")          # [D, V]
    if name in ("patch_proj", "enc_in"):
        return (None, "model")
    if name == "wq":                      # [D, KV, G, hd] head-major
        kv, g = shape[-3], shape[-2]
        if kv % ms == 0:
            return ("data", "model", None, None)
        if g % ms == 0:
            return ("data", None, "model", None)
        # head counts don't divide the model axis (e.g. nemotron KV=8,
        # G=12): storage falls back to 2D-sharding d_model so parameters +
        # optimizer state still scale with the FULL chip count (mandatory
        # for 340B on 256 chips); the activation-side head sharding uses
        # GSPMD padding via hint_heads.
        return (("data", "model"), None, None, None)
    if name in ("wk", "wv"):              # [D, KV, hd]
        kv = shape[-2]
        if kv % ms == 0:
            return ("data", "model", None)
        return (("data", "model"), None, None)
    if name == "wo":                      # [KV, G, hd, D]
        kv, g = shape[-4], shape[-3]
        if kv % ms == 0:
            return ("model", None, None, "data")
        if g % ms == 0:
            return (None, "model", None, "data")
        return (None, None, None, ("data", "model"))
    if name == "bq":                      # [KV, G, hd]
        kv, g = shape[-3], shape[-2]
        if choose_head_axis(kv, g, ms) == "kv":
            return ("model", None, None)
        return (None, "model", None)
    if name in ("bk", "bv"):              # [KV, hd]
        return ("model" if shape[-2] % ms == 0 else None, None)
    if name == "b_up":
        return ("model",)
    if name in ("bo", "b_down"):
        return (None,)
    if moe_routed:
        if name in ("w_up", "w_gate"):
            return ("model", "data", None)   # [E, D, F]
        if name == "w_down":
            return ("model", None, "data")   # [E, F, D]
        if name == "router":
            return (None, None)
    if name in ("w_up", "w_gate"):
        return ("data", "model")
    if name == "w_down":
        return ("model", "data")
    if "/time/" in pathstr + "/":
        if name in ("w_r", "w_k", "w_v", "w_g"):
            return ("data", "model")
        if name == "w_o":
            return ("model", "data")
    if "/chan/" in pathstr + "/":
        if name in ("w_k", "w_r"):
            return ("data", "model")
        if name == "w_v":
            return ("model", "data")
    if "/rglru/" in pathstr + "/":
        if name in ("w_in", "w_gate"):
            return ("data", "model")
        if name == "w_out":
            return ("model", "data")
        if name in ("w_a", "w_x"):
            return ("model", None)
        if name == "conv_w":
            return (None, "model")
        if name in ("conv_b", "b_a", "b_x", "lambda"):
            return ("model",)
    return None  # replicate (norms, tiny LoRAs, scalars)


def _pad_spec(rule: Optional[tuple], shape: tuple,
              axis_sizes: dict) -> P:
    """Left-pad the rule to the leaf rank and DROP any axis that does not
    divide the dimension — jit input shardings must divide exactly (unlike
    in-graph constraints, which GSPMD pads)."""
    if rule is None:
        return P()
    ndim = len(shape)
    assert ndim >= len(rule), (rule, shape)
    full = (None,) * (ndim - len(rule)) + tuple(rule)
    out = []
    for dim, ax in zip(shape, full):
        if ax is None:
            out.append(None)
            continue
        size = 1
        for a in (ax if isinstance(ax, tuple) else (ax,)):
            size *= axis_sizes.get(a, DEFAULT_MODEL_SIZE)
        out.append(ax if dim % size == 0 else None)
    return P(*out)


def param_pspecs(params: PyTree, mesh=None) -> PyTree:
    """PartitionSpec tree for a parameter-shaped tree (params or optimizer
    moments — rules match by trailing path names)."""
    sizes = dict(mesh.shape) if mesh is not None else \
        {"data": DEFAULT_MODEL_SIZE, "model": DEFAULT_MODEL_SIZE}

    def spec(path, leaf):
        name = str(getattr(path[-1], "key", path[-1]))
        shape = tuple(np.shape(leaf))
        return _pad_spec(_param_rule(_path_str(path), name, shape),
                         shape, sizes)
    return jax.tree_util.tree_map_with_path(spec, params)


def replicated_pspecs(tree: PyTree) -> PyTree:
    return jax.tree_util.tree_map(lambda _: P(), tree)


# ---------------------------------------------------------------------------
# Batch / cache rules.
# ---------------------------------------------------------------------------
def _divides(n: int, mesh, axes) -> bool:
    if axes is None:
        return False
    size = 1
    for a in (axes if isinstance(axes, tuple) else (axes,)):
        size *= mesh.shape[a]
    return n % size == 0


def batch_pspecs(batch: PyTree, mesh, dp_axes) -> PyTree:
    """Shard dim 0 (global batch) over the DP axes when divisible."""
    def spec(leaf):
        shape = leaf.shape
        if len(shape) == 0:
            return P()
        lead = dp_axes if _divides(shape[0], mesh, dp_axes) else None
        return P(lead, *((None,) * (len(shape) - 1)))
    return jax.tree_util.tree_map(spec, batch)


def cache_pspecs(cache: PyTree, mesh, dp_axes) -> PyTree:
    """Decode caches: batch over DP; heads/state channels over model."""
    def spec(path, leaf):
        name = str(getattr(path[-1], "key", path[-1]))
        shape = leaf.shape
        # strip the stacked-repeats dim (caches under 'blocks' carry it).
        stacked = "blocks" in _path_str(path)
        core = shape[1:] if stacked else shape
        lead = (None,) if stacked else ()
        bdim = dp_axes if _divides(core[0], mesh, dp_axes) else None
        if name in ("k", "v"):                       # [B, L, KV, hd]
            # prefer sharding KV heads over model; when the head count
            # doesn't divide (GQA kv=8 on a 16-way axis) shard the cache
            # LENGTH instead — decode softmax over a sharded length is a
            # cheap psum, and the cache (the decode memory bill) scales
            # with the full mesh. (nemotron decode_32k: 527 -> ~40 GB/dev)
            if _divides(core[2], mesh, "model"):
                sp = (bdim, None, "model", None)
            elif _divides(core[1], mesh, "model"):
                sp = (bdim, "model", None, None)
            else:
                sp = (bdim, None, None, None)
        elif name == "pos":                          # [B, L]
            ldim = "model" if _divides(core[1], mesh, "model") else None
            sp = (bdim, ldim)
        elif name == "state":                        # [B, H, hd, hd]
            hdim = "model" if _divides(core[1], mesh, "model") else None
            sp = (bdim, hdim, None, None)
        elif name == "h":                            # [B, C]
            cdim = "model" if _divides(core[1], mesh, "model") else None
            sp = (bdim, cdim)
        elif name == "conv":                         # [B, 3, C]
            cdim = "model" if _divides(core[2], mesh, "model") else None
            sp = (bdim, None, cdim)
        elif name in ("x_time", "x_chan"):           # [B, D]
            sp = (bdim, None)
        else:
            sp = (None,) * len(core)
        return P(*(lead + tuple(sp)))
    return jax.tree_util.tree_map_with_path(spec, cache)


# ---------------------------------------------------------------------------
# NamedSharding helpers.
# ---------------------------------------------------------------------------
def named(tree_pspecs: PyTree, mesh) -> PyTree:
    return jax.tree_util.tree_map(lambda s: NamedSharding(mesh, s),
                                  tree_pspecs,
                                  is_leaf=lambda x: isinstance(x, P))


def train_state_pspecs(state: PyTree, mesh=None) -> PyTree:
    """{params, opt, quant, step} -> specs (quant/step replicated)."""
    return {
        "params": param_pspecs(state["params"], mesh),
        "opt": param_pspecs(state["opt"], mesh),
        "quant": replicated_pspecs(state["quant"]),
        "step": P(),
    }
