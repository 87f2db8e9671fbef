"""The expert model's work (``flops_moe.py``) against hand counts."""
import flops_moe

# One dense layer and one expert layer at toy widths: hidden 8, 2 heads of
# query-key 4 + 2 and value 3, latent 5; dense MLP 7; 8 routed experts of
# 3 (2 held, top-4) and 2 shared; vocabulary 11.
C = {"hidden_size": 8, "num_attention_heads": 2, "qk_nope_head_dim": 4,
     "qk_rope_head_dim": 2, "v_head_dim": 3, "kv_lora_rank": 5,
     "intermediate_size": 7, "moe_intermediate_size": 3,
     "n_shared_experts": 2, "n_routed_experts": 8, "experts_held": 2,
     "num_experts_per_tok": 4, "vocab_size": 11, "first_k_dense_replace": 1,
     "num_hidden_layers": 2}


def test_layer_counts_by_hand():
    # attention: q 8x12, kv_a 8x7, kv_b 5x14, o 6x8
    attn = 8 * 12 + 8 * 7 + 5 * 14 + 6 * 8
    assert attn == 270
    dense = 3 * 8 * 7                      # up, gate, down of 7
    shared = 3 * 8 * 6                     # 2 shared experts of 3
    router = 8 * 8
    held = 4 * 2 / 8 * (3 * 8 * 3)         # balanced: 1 assignment held
    head = 8 * 11
    per_token = 2 * ((attn + dense) + (attn + shared + router + held) + head)
    assert flops_moe.linear_flops_per_token(C) == per_token == 2152
    # causal pairs of 4 tokens: 10; q.k at 6 and p.v at 3, 2 heads, 2 layers
    assert flops_moe.attn_flops(C, 4) == 2 * 10 * 2 * (6 + 3) * 2 == 720
    assert flops_moe.train_flops(C, 4, 3) == 3 * 3 * (4 * 2152 + 720)


def test_contractions_by_hand():
    dense = flops_moe.dense_contractions(C, 5)
    # 4 attention contractions in each of 2 layers, the dense MLP's 3, the
    # shared experts' 3 and the head
    assert len(dense) == 4 * 2 + 3 + 3 + 1
    assert dense[0] == (2 * 5 * 8 * 12, 5 * 8 + 8 * 12 + 4 * 5 * 12)
    assert dense[-1] == (2 * 5 * 8 * 11, 5 * 8 + 8 * 11 + 4 * 5 * 11)
    experts = flops_moe.expert_contractions(C, 5)
    rows = 5 * 4 * 2 / 8                    # 5 held rows at balance
    assert experts == [(2 * rows * 8 * 3, rows * 8 + 2 * 8 * 3 + 4 * rows * 3),
                       (2 * rows * 8 * 3, rows * 8 + 2 * 8 * 3 + 4 * rows * 3),
                       (2 * rows * 3 * 8, rows * 3 + 2 * 3 * 8 + 4 * rows * 8)]
