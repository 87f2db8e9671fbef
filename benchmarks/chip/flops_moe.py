"""Work a DeepSeek-V3-type expert model needs (latent attention, leading
dense layers, expert layers holding a share of the experts), counted from
a configuration's shapes in the keys of its published config.json.

As ``flops.py``: the model's own operations (nothing recomputed, no
padding), a multiply and an add counting as two.  The routed experts count
at balanced routing: of each token's ``num_experts_per_tok`` assignments,
the share that lands on the ``experts_held`` of ``n_routed_experts`` held
here.  Attention counts query-key products at ``qk_nope_head_dim +
qk_rope_head_dim`` and probability-value products at ``v_head_dim``.
"""
from __future__ import annotations


def _dims(c: dict) -> tuple:
    return (c["hidden_size"], c["num_attention_heads"],
            c["qk_nope_head_dim"] + c["qk_rope_head_dim"],
            c["qk_rope_head_dim"], c["v_head_dim"], c["kv_lora_rank"])


def attn_matmuls(c: dict) -> list:
    """``(name, K, N)`` of the latent attention's weight contractions."""
    d, h, qk, rope, v, r = _dims(c)
    return [("q_proj", d, h * qk), ("kv_a", d, r + rope),
            ("kv_b", r, h * (c["qk_nope_head_dim"] + v)), ("o_proj", h * v, d)]


def glu_matmuls(d: int, f: int) -> list:
    return [("up", d, f), ("gate", d, f), ("down", f, d)]


def layer_counts(c: dict) -> tuple:
    lead = c["first_k_dense_replace"]
    return lead, c["num_hidden_layers"] - lead


def routed_share(c: dict) -> float:
    """Assignments per token that land on a held expert, at balanced
    routing."""
    return c["num_experts_per_tok"] * c["experts_held"] / c["n_routed_experts"]


def linear_flops_per_token(c: dict) -> float:
    """Forward operations of the weight contractions per token: every
    layer's attention, the dense layers' MLP, the expert layers' router,
    shared experts and held experts (balanced), and the LM head."""
    d = c["hidden_size"]
    lead, moe = layer_counts(c)
    attn = sum(k * n for _, k, n in attn_matmuls(c))
    dense = sum(k * n for _, k, n in glu_matmuls(d, c["intermediate_size"]))
    fe = c["moe_intermediate_size"]
    shared = sum(k * n for _, k, n in glu_matmuls(d, fe * c["n_shared_experts"]))
    expert = sum(k * n for _, k, n in glu_matmuls(d, fe))
    per_moe = attn + shared + d * c["n_routed_experts"] \
        + routed_share(c) * expert
    return 2 * (lead * (attn + dense) + moe * per_moe
                + d * c["vocab_size"])


def attn_flops(c: dict, seq: int) -> int:
    """Forward operations of causal attention over one sequence."""
    _, h, qk, _, v, _ = _dims(c)
    pairs = seq * (seq + 1) // 2
    return 2 * pairs * h * (qk + v) * c["num_hidden_layers"]


def train_flops(c: dict, seq: int, batch: int) -> float:
    """Forward and backward of one step (backward = twice the forward)."""
    return 3 * batch * (seq * linear_flops_per_token(c) + attn_flops(c, seq))


def _contraction(rows: float, k: int, n: int, w_bytes: int) -> tuple:
    return (2 * rows * k * n, rows * k + w_bytes + 4 * rows * n)


def dense_contractions(c: dict, tokens: int) -> list:
    """``(ops, bytes)`` of each int8 weight contraction of one forward pass
    over ``tokens`` rows on the matmul kernel (all but the routed experts
    and the fp32 router): int8 operands in, fp32 out, unpadded."""
    d = c["hidden_size"]
    lead, moe = layer_counts(c)
    fs = c["moe_intermediate_size"] * c["n_shared_experts"]
    out = []
    for _, k, n in attn_matmuls(c):
        out += [_contraction(tokens, k, n, k * n)] * (lead + moe)
    for _, k, n in glu_matmuls(d, c["intermediate_size"]):
        out += [_contraction(tokens, k, n, k * n)] * lead
    for _, k, n in glu_matmuls(d, fs):
        out += [_contraction(tokens, k, n, k * n)] * moe
    out.append(_contraction(tokens, d, c["vocab_size"], d * c["vocab_size"]))
    return out


def expert_contractions(c: dict, tokens: int) -> list:
    """``(ops, bytes)`` of each grouped int8 contraction of the held experts
    in one forward pass over ``tokens`` rows, at balanced routing: the
    routed rows, every held expert's weights read once."""
    d, fe, g = c["hidden_size"], c["moe_intermediate_size"], c["experts_held"]
    rows = tokens * routed_share(c)
    _, moe = layer_counts(c)
    return [_contraction(rows, k, n, g * k * n)
            for _, k, n in glu_matmuls(d, fe)] * moe
