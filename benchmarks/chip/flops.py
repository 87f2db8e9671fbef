"""Work the algorithm needs, counted from a configuration's shapes.

These counts are the numerators of every utilization and roofline share:
the model's own operations, not what the compiled program happens to
execute (no recomputation, no padding, no im2col copies).  A multiply and
an add count as two operations.
"""
from __future__ import annotations


# ---------------------------------------------------------------------------
# Decoder-only transformer (keys as in configs/<name>.json).
# ---------------------------------------------------------------------------
def lm_layer_matmuls(c: dict) -> list:
    """``(name, K, N)`` of each weight contraction of one layer."""
    d, h, kv, hd, f = (c["d_model"], c["n_heads"], c["n_kv"], c["head_dim"],
                       c["d_ff"])
    mats = [("q", d, h * hd), ("k", d, kv * hd), ("v", d, kv * hd),
            ("o", h * hd, d), ("up", d, f), ("down", f, d)]
    if c["mlp_kind"] in ("swiglu", "geglu", "reglu"):
        mats.append(("gate", d, f))
    return mats


def lm_linear_params(c: dict, head: bool = True) -> int:
    """Weights that take part in a contraction per token (embedding lookup
    excluded, LM head included unless ``head`` is False)."""
    n = c["n_layers"] * sum(k * n for _, k, n in lm_layer_matmuls(c))
    return n + (c["d_model"] * c["vocab"] if head else 0)


def attn_pairs(seq: int, window) -> int:
    """Query-key pairs a causal (optionally sliding) attention computes."""
    w = window or seq
    return sum(min(q + 1, w) for q in range(seq))


def _attn_flops(c: dict, pairs: int) -> int:
    # q.k and p.v: 2 * head_dim operations each per pair and head.
    return 4 * c["head_dim"] * c["n_heads"] * c["n_layers"] * pairs


def lm_train_flops(c: dict, seq: int, batch: int) -> int:
    """Forward and backward of one step (backward = twice the forward)."""
    fwd = 2 * batch * seq * lm_linear_params(c) \
        + batch * _attn_flops(c, attn_pairs(seq, c.get("sliding_window")))
    return 3 * fwd


def lm_prefill_flops(c: dict, seq: int, batch: int) -> int:
    """Prefill of ``batch`` prompts of ``seq`` tokens; logits only for the
    last position, as the served path computes them."""
    return (2 * batch * seq * lm_linear_params(c, head=False)
            + 2 * batch * c["d_model"] * c["vocab"]
            + batch * _attn_flops(c, attn_pairs(seq, c.get("sliding_window"))))


def _attended(c: dict, ctx: int) -> int:
    w = c.get("sliding_window")
    return min(ctx, w) if w else ctx


def lm_decode_flops(c: dict, ctx: int, batch: int) -> int:
    """One decode step: one new token per row, attending ``ctx`` tokens."""
    return batch * (2 * lm_linear_params(c)
                    + _attn_flops(c, _attended(c, ctx)))


def lm_decode_bytes(c: dict, ctx: int, batch: int, cache_bytes: int) -> int:
    """Least bytes one decode step reads: every weight once as int8 (W8)
    and the attended part of the KV cache."""
    kv = (c["n_layers"] * 2 * c["n_kv"] * c["head_dim"] * _attended(c, ctx)
          * batch * cache_bytes)
    return lm_linear_params(c) + kv


def lm_forward_contractions(c: dict, tokens: int) -> list:
    """``(ops, bytes)`` of each int8 weight contraction of one forward pass
    over ``tokens`` rows: int8 operands in, fp32 out, unpadded."""
    out = []
    for _, k, n in lm_layer_matmuls(c):
        out += [(2 * tokens * k * n, tokens * k + k * n + 4 * tokens * n)] \
            * c["n_layers"]
    d, v = c["d_model"], c["vocab"]
    out.append((2 * tokens * d * v, tokens * d + d * v + 4 * tokens * v))
    return out


# ---------------------------------------------------------------------------
# CNN (MobileNetV2 plan of configs/<name>.json).
# ---------------------------------------------------------------------------
def _scaled(c: dict, ch: int) -> int:
    return max(8, int(ch * c["width"] + 0.5) // 8 * 8)


def cnn_convs(c: dict) -> list:
    """Every conv of the network as a dict of its geometry, in order."""
    convs = []
    hw = c["image_size"]

    def conv(name, k, cin, cout, stride, groups=1):
        nonlocal hw
        h_in = hw
        hw = -(-hw // stride)                      # SAME padding
        convs.append({"name": name, "k": k, "cin": cin, "cout": cout,
                      "groups": groups, "h_in": h_in, "h_out": hw})

    c0 = _scaled(c, c["stem_channels"])
    conv("stem", 3, c["channels"], c0, c["stem_stride"])
    cin, idx = c0, 0
    for t, ch, n, s in c["plan"]:
        ch = _scaled(c, ch)
        for bi in range(n):
            mid = cin * t
            if t != 1:
                conv(f"b{idx}.expand", 1, cin, mid, 1)
            conv(f"b{idx}.dw", 3, mid, mid, s if bi == 0 else 1, groups=mid)
            conv(f"b{idx}.project", 1, mid, ch, 1)
            cin, idx = ch, idx + 1
    conv("head", 1, cin, _scaled(c, c["head_channels"]), 1)
    return convs


def conv_macs(cv: dict) -> int:
    return (cv["h_out"] ** 2 * cv["k"] ** 2 * (cv["cin"] // cv["groups"])
            * cv["cout"])


def cnn_macs(c: dict) -> int:
    """Multiply-accumulates of one image's forward pass: every conv (a
    depthwise conv counts k*k per output element) and the classifier."""
    fc = _scaled(c, c["head_channels"]) * c["num_classes"]
    return sum(conv_macs(cv) for cv in cnn_convs(c)) + fc


def cnn_train_flops(c: dict, batch: int) -> int:
    return 3 * 2 * cnn_macs(c) * batch


def cnn_forward_contractions(c: dict, batch: int) -> list:
    """``(ops, bytes)`` of each int8 contraction (the convs and the
    classifier) of one forward pass: int8 operands in, fp32 out."""
    out = []
    for cv in cnn_convs(c):
        w = cv["k"] ** 2 * (cv["cin"] // cv["groups"]) * cv["cout"]
        out.append((2 * batch * conv_macs(cv),
                    batch * cv["h_in"] ** 2 * cv["cin"] + w
                    + 4 * batch * cv["h_out"] ** 2 * cv["cout"]))
    hc = _scaled(c, c["head_channels"])
    out.append((2 * batch * hc * c["num_classes"],
                batch * hc + hc * c["num_classes"]
                + 4 * batch * c["num_classes"]))
    return out


def least_seconds(contractions: list, peaks: dict) -> float:
    """Least time the chip could take for these calls: per call the larger
    of its operations over the int8 peak and its bytes over HBM's."""
    return sum(max(ops / peaks["int8_ops_per_s"],
                   b / peaks["hbm_bytes_per_s"]) for ops, b in contractions)
