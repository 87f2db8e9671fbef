"""starcoder2-3b — BigCode StarCoder2 3B (arXiv:2402.19173; hf).

30 layers, d_model 3072, 24 q heads / 2 kv heads, head_dim 128, d_ff 12288,
vocab 49152, RoPE, biases, LayerNorm, gelu, sliding window 4096.

``starcoder2-3b-4l`` is the same model cut to 4 layers, the depth one TPU
v5e chip (16 GB) trains at seq 4096 with AdamW state; every width is the
published one.
"""
import dataclasses

from .arch import ArchConfig, register

CONFIG = ArchConfig(
    name="starcoder2-3b",
    family="dense",
    n_layers=30,
    d_model=3072,
    n_heads=24,
    n_kv=2,
    head_dim=128,
    d_ff=12288,
    vocab=49152,
    source="arXiv:2402.19173; hf",
    mlp_kind="gelu",
    norm_kind="layernorm",
    use_bias=True,
    rope_theta=100000.0,
    sliding_window=4096,
    pattern=("attn",),
    grad_accum=(("train_4k", 4),),
)


def reduced() -> ArchConfig:
    return dataclasses.replace(
        CONFIG, n_layers=2, d_model=64, n_heads=4, n_kv=2, head_dim=16,
        d_ff=128, vocab=512, sliding_window=16, loss_chunk=16,
        grad_accum=(("train_4k", 1),))


register(CONFIG, reduced)

# One chip's share of a training deployment at the published widths: the
# 30 layers are split into pipeline stages of at most 4 layers, and this
# chip holds one 4-layer stage.  The other 26 layers are the further stages
# on further chips.  The embedding and the LM head, which belong to the
# first and last stages, stay here too so that the loss is the model's.
CHIP_CONFIG = register(dataclasses.replace(
    CONFIG,
    name="starcoder2-3b-4l",
    n_layers=4,
    reduced=(("n_layers", 30, 4),),
    deployment=("pipeline-parallel training of starcoder2-3b, 4 of its 30 "
                "layers per chip; the other 26 layers are further stages"),
), reduced)
