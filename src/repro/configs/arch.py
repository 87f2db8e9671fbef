"""Architecture configs: dataclass, shape matrix, registry, input specs.

Every assigned architecture registers an :class:`ArchConfig` (exact figures
from the public source cited in its module) plus a ``reduced()`` variant
used by the CPU smoke tests.  The FULL configs are only ever touched via
``jax.eval_shape`` / ``.lower()`` (dry-run) — never materialized.

The shape matrix (assigned):

    train_4k      seq 4096    global_batch 256   -> train_step
    prefill_32k   seq 32768   global_batch 32    -> prefill_step
    decode_32k    seq 32768   global_batch 128   -> decode_step (1 new token)
    long_500k     seq 524288  global_batch 1     -> decode_step

``long_500k`` requires sub-quadratic attention: it RUNS for rwkv6
(attention-free), recurrentgemma (RG-LRU + local attention) and the
starcoder2 pair (sliding window 4096 -> constant-size ring KV cache), and
is SKIPPED for the pure full-attention archs (see ``Cell.skip_reason``).
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Optional

import jax
import jax.numpy as jnp

from repro.models.moe import MoeSpec


# ---------------------------------------------------------------------------
# Shapes.
# ---------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class ShapeSpec:
    name: str
    seq_len: int
    global_batch: int
    kind: str                      # train | prefill | decode


SHAPES = {
    "train_4k": ShapeSpec("train_4k", 4096, 256, "train"),
    "prefill_32k": ShapeSpec("prefill_32k", 32768, 32, "prefill"),
    "decode_32k": ShapeSpec("decode_32k", 32768, 128, "decode"),
    "long_500k": ShapeSpec("long_500k", 524288, 1, "decode"),
}


# ---------------------------------------------------------------------------
# ArchConfig.
# ---------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class ArchConfig:
    name: str
    family: str                    # dense | moe | rwkv | hybrid | encdec | vlm
    n_layers: int
    d_model: int
    n_heads: int
    n_kv: int
    head_dim: int
    d_ff: int
    vocab: int
    source: str = ""
    # A configuration cut to fit one chip names each key it changed from
    # its source as (key, published, here) and the deployment it stands for.
    reduced: tuple = ()
    deployment: str = ""

    mlp_kind: str = "gelu"         # gelu|relu|sq_relu|swiglu|geglu|reglu
    norm_kind: str = "rmsnorm"
    norm_eps: Optional[float] = None   # None: the norm's own default
    use_bias: bool = False
    rope_theta: Optional[float] = 10000.0
    tie_embeddings: bool = False
    embed_scale: bool = False      # gemma-style sqrt(d) embedding scale
    sliding_window: Optional[int] = None

    pattern: tuple = ("attn",)
    first_k_dense: int = 0         # dense "attn" layers before the pattern
    # latent attention (MLA); kv_lora_rank 0 = the head-split projections.
    # head_dim is then the query-key head dim (nope + rope).
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    # hybrid
    local_window: Optional[int] = None
    lru_width: Optional[int] = None
    # rwkv
    rwkv_chunk: int = 32
    # moe
    moe: Optional[MoeSpec] = None
    # enc-dec
    enc_pattern: tuple = ("enc",)
    enc_layers: int = 0
    frontend_dim: Optional[int] = None
    # vlm
    n_patches: int = 0

    # compute policy
    param_dtype: str = "float32"
    compute_dtype: str = "bfloat16"
    cache_dtype: str = "bfloat16"
    loss_chunk: int = 512
    logit_z_coef: float = 0.0
    remat: bool = True

    # distribution knobs (overridable per shape via grad_accum map)
    grad_accum: tuple = (("train_4k", 1),)
    # optimizer for the train cells: "adamw" | "sgdm".  SGD+momentum is the
    # paper's optimizer AND halves optimizer-state HBM (1 moment) — required
    # for the 340B arch to fit 256 chips (see EXPERIMENTS.md §Dry-run).
    optimizer: str = "adamw"

    def grad_accum_for(self, shape_name: str) -> int:
        return dict(self.grad_accum).get(shape_name, 1)

    def enc_len(self, dec_len: int) -> int:
        """Cross-attention cache length paired with a decoder cache of
        ``dec_len`` (= the encoder sequence the cell feeds)."""
        return dec_len

    @property
    def sub_quadratic(self) -> bool:
        if self.family in ("rwkv", "hybrid"):
            return True
        return self.sliding_window is not None

    def supports(self, shape_name: str) -> tuple[bool, str]:
        if shape_name == "long_500k" and not self.sub_quadratic:
            return False, ("full attention: 512k decode needs an O(S) KV "
                           "cache per token; skipped per assignment rules")
        return True, ""


# ---------------------------------------------------------------------------
# Registry.
# ---------------------------------------------------------------------------
_REGISTRY: dict = {}


def register(cfg: ArchConfig, reduced: Callable[[], ArchConfig]):
    _REGISTRY[cfg.name] = (cfg, reduced)
    return cfg


def get(name: str) -> ArchConfig:
    _ensure_loaded()
    return _REGISTRY[name][0]


def get_reduced(name: str) -> ArchConfig:
    _ensure_loaded()
    return _REGISTRY[name][1]()


def names() -> list:
    _ensure_loaded()
    return sorted(_REGISTRY)


def _ensure_loaded():
    if not _REGISTRY:
        from . import (command_r_35b, moonlight_16b_a3b,        # noqa: F401
                       nemotron_4_340b, paligemma_3b,
                       qwen2_moe_a2_7b, recurrentgemma_9b, rwkv6_7b,
                       seamless_m4t_medium, starcoder2_3b, starcoder2_7b)


# ---------------------------------------------------------------------------
# Cells: the (arch x shape) dry-run matrix.
# ---------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class Cell:
    arch: str
    shape: str
    runnable: bool
    skip_reason: str = ""


def cells() -> list:
    _ensure_loaded()
    out = []
    for a in names():
        cfg = get(a)
        for s in SHAPES:
            ok, why = cfg.supports(s)
            out.append(Cell(a, s, ok, why))
    return out


# ---------------------------------------------------------------------------
# Input specs (ShapeDtypeStruct stand-ins; no allocation).
# ---------------------------------------------------------------------------
def _sds(shape, dtype):
    return jax.ShapeDtypeStruct(shape, jnp.dtype(dtype))


def input_specs(cfg: ArchConfig, shape: ShapeSpec) -> dict:
    """Model inputs for one cell, as ShapeDtypeStructs.

    For train/prefill, ``tokens`` spans the full seq_len (VLM: image prefix
    + text fills seq_len; enc-dec: encoder frames at seq_len, decoder
    tokens at seq_len for train / 1 for prefill)."""
    b, s = shape.global_batch, shape.seq_len
    i32, f32 = jnp.int32, jnp.float32
    comp = cfg.compute_dtype

    if shape.kind == "train":
        if cfg.family == "encdec":
            return {
                "frames": _sds((b, s, cfg.frontend_dim), comp),
                "tokens": _sds((b, s), i32),
                "labels": _sds((b, s), i32),
                "mask": _sds((b, s), f32),
            }
        if cfg.family == "vlm":
            st = s - cfg.n_patches
            return {
                "patches": _sds((b, cfg.n_patches, cfg.frontend_dim), comp),
                "tokens": _sds((b, st), i32),
                "labels": _sds((b, st), i32),
                "mask": _sds((b, st), f32),
            }
        return {
            "tokens": _sds((b, s), i32),
            "labels": _sds((b, s), i32),
            "mask": _sds((b, s), f32),
        }

    if shape.kind == "prefill":
        if cfg.family == "encdec":
            return {"frames": _sds((b, s, cfg.frontend_dim), comp),
                    "tokens": _sds((b, 1), i32)}
        if cfg.family == "vlm":
            return {"patches": _sds((b, cfg.n_patches, cfg.frontend_dim), comp),
                    "tokens": _sds((b, s - cfg.n_patches), i32)}
        return {"tokens": _sds((b, s), i32)}

    # decode: one new token against a cache of length seq_len.
    return {"token": _sds((b, 1), i32), "pos": _sds((b,), i32)}
