"""Model FLOPs of one LoRA training pass of the Granite-4.0-H stack
(``ssm-moe``), per token.

Counted, per token and layer: the mixer as the families count it, Mamba-2
as ``ssm.py`` (in/out projections, adapters, the depthwise convolution, the
chunked SSD scan) and attention as ``dense.py`` (q/k/v/o, adapters, scores
and values over the whole S×S block); then the MoE: the router's product
and its activation gradient over every expert, 4·D·E; the shared SwiGLU
expert, 4 per frozen weight and 6·r·(d_in + d_out) per adapter; and the
routed SwiGLU experts at the same rate for k·E_held/E (token, expert) pairs
a token, the pairs that land on the experts held here under even routing.
The tied head's product and its activation gradient.  Not counted: sorting
and gathering the pairs, the frozen weights' gradients, merging,
recomputation, and elementwise work (gates, norms, decays, the loss).
"""


def _adapted(shapes, r: int) -> int:
    """Frozen products forward and backward plus the adapters."""
    return sum(4 * i * o + 6 * r * (i + o) for i, o in shapes)


def flops_per_token(cfg: dict, seq: int) -> float:
    D, V, r = cfg["d_model"], cfg["vocab_size"], cfg["lora_rank"]
    N, P, W = cfg["ssm_state"], cfg["ssm_head_dim"], cfg["ssm_conv_width"]
    d_inner = cfg["ssm_expand"] * D
    Hs = d_inner // P
    Q = min(cfg["ssm_chunk"], seq)
    H, Kv, hd = cfg["num_heads"], cfg["num_kv_heads"], cfg["head_dim"]
    F, Fs, k = cfg["d_ff"], cfg["shared_d_ff"], cfg["num_experts_per_tok"]
    E_all, E = cfg["router_experts"], cfg["num_experts"]

    mamba = (_adapted([(D, 2 * d_inner + 2 * N + Hs), (d_inner, D)], r)
             + 3 * 2 * W * (d_inner + 2 * N)
             + 3 * (2 * Q * N + 2 * Hs * Q * P + 4 * Hs * P * N))
    attention = (_adapted([(D, H * hd), (D, Kv * hd), (D, Kv * hd), (H * hd, D)], r)
                 + 3 * 4 * seq * H * hd)
    moe = (4 * D * E_all + _adapted([(D, Fs), (D, Fs), (Fs, D)], r)
           + k * E / E_all * _adapted([(D, F), (D, F), (F, D)], r))
    pattern = cfg["layer_pattern"]
    mixers = [pattern[i % len(pattern)] for i in range(cfg["num_layers"])]
    layers = sum(mamba if ch == "M" else attention for ch in mixers)
    return layers + cfg["num_layers"] * moe + 4 * D * V
