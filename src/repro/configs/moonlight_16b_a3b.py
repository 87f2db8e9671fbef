"""moonlight-16b-a3b — Moonlight-16B-A3B, a DeepSeek-V3-type MoE
(https://huggingface.co/moonshotai/Moonlight-16B-A3B/blob/main/config.json,
``model_type: deepseek_v3``).

27 layers, d_model 2048, 16 heads, vocabulary 163840, untied embeddings,
RMSNorm eps 1e-5, RoPE theta 50000 (no scaling), context 8192.  Latent
attention: ``kv_lora_rank`` 512, no query LoRA, query-key heads of 128
(nope) + 64 (rope, one key shared by all heads), value heads of 128.
Layer 0 is dense (``first_k_dense_replace`` 1, SwiGLU 11264); layers 1-26
are expert layers of 64 routed SwiGLU experts of 1408, top-6, plus 2
shared experts (2 x 1408).  Router: sigmoid scores, top-6 of the scores
plus a correction bias (``noaux_tc``, one group), weights the chosen
scores normalised to sum 1 times 2.446.

``moonlight-16b-a3b-5l`` is one chip's share of an expert-parallel
training deployment at the published widths: layer 0 and 4 expert layers,
8 of each expert layer's 64 experts (experts 0-7; the router keeps all 64
outputs and its top-6), and an eighth of the vocabulary.

Not from the source (``assumed``): the correction bias's first draw
(normal, scale 0.01), its update speed (gamma 0.001) and the sequence-wise
balance loss's weight (alpha 1e-4), both DeepSeek-V3's (arXiv:2412.19437
sec. 4.2).  Moonlight was trained with the Muon optimizer; this program
trains it with AdamW.
"""
import dataclasses

from repro.models.moe import MoeSpec

from .arch import ArchConfig, register

CONFIG = ArchConfig(
    name="moonlight-16b-a3b",
    family="moe",
    n_layers=27,
    d_model=2048,
    n_heads=16,
    n_kv=16,
    head_dim=192,              # query-key head dim: 128 nope + 64 rope
    d_ff=11264,                # the dense layer's intermediate size
    vocab=163840,
    source="https://huggingface.co/moonshotai/Moonlight-16B-A3B",
    mlp_kind="swiglu",
    norm_kind="rmsnorm",
    norm_eps=1e-5,
    rope_theta=50000.0,
    pattern=("moe",),
    first_k_dense=1,
    kv_lora_rank=512,
    qk_nope_head_dim=128,
    qk_rope_head_dim=64,
    v_head_dim=128,
    moe=MoeSpec(n_experts=64, top_k=6, d_expert=1408, n_shared=2,
                d_shared=2816, mlp_kind="swiglu", scoring="sigmoid",
                routed_scale=2.446, aux_loss_coef=1e-4, z_loss_coef=0.0),
    grad_accum=(("train_4k", 2),),
)


def reduced() -> ArchConfig:
    return dataclasses.replace(
        CONFIG, n_layers=3, d_model=64, n_heads=4, n_kv=4, head_dim=24,
        d_ff=96, vocab=512, loss_chunk=16,
        kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8,
        v_head_dim=16,
        moe=dataclasses.replace(CONFIG.moe, n_experts=16, top_k=4,
                                d_expert=32, d_shared=64, held=(4, 4)),
        grad_accum=(("train_4k", 1),))


register(CONFIG, reduced)

# One chip's share of an expert-parallel training deployment: each expert
# layer's 64 experts are spread over 8 chips (this one holds experts 0-7),
# the vocabulary is split 8 ways (this chip's slice is the model's
# vocabulary here), and the layers form pipeline stages of 5 (the dense
# layer 0 and 4 expert layers, the floor of 4 after the dense one); the
# embedding and the head stay here so that the loss is the model's.  Six
# layers do not fit at seq 8192 in microbatches of 1: the chip's compiler
# asks 16.10G of 15.75G (program 8.62G beside 7.48G of arguments).
CHIP_CONFIG = register(dataclasses.replace(
    CONFIG,
    name="moonlight-16b-a3b-5l",
    n_layers=5,
    vocab=20480,
    moe=dataclasses.replace(CONFIG.moe, held=(0, 8)),
    reduced=(("n_layers", 27, 5), ("experts_held", 64, 8),
             ("vocab", 163840, 20480)),
    deployment=("expert-parallel training, each MoE layer's 64 experts over "
                "8 chips (8 here), vocabulary split 8 ways, layers in "
                "pipeline stages of 5 (dense layer 0 + 4 MoE) with the "
                "embedding and head kept here so the loss is the model's"),
), reduced)
