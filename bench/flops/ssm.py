"""Model FLOPs of one LoRA training pass of the Mamba-2 stack, per token.

Counted, per token: the in/out projections against the frozen weights
(2 per weight forward, 2 more for the activation gradient); each adapter's
forward and backward, 6·r·(d_in + d_out); the depthwise convolution, 2·W per
channel forward and twice that backward; the SSD scan in its chunked form
with chunk Q = min(chunk, S), forward and twice that backward: C·Bᵀ within
a chunk (2·Q·N), the scores times the inputs (2·H·Q·P), the chunk states
(2·H·P·N) and their read-out (2·H·P·N); the tied head's product and its
activation gradient.  Not counted: the frozen weights' gradients, merging,
recomputation, and elementwise work (decays, gating, norms, the loss).
"""


def flops_per_token(cfg: dict, seq: int) -> float:
    D, V, r = cfg["d_model"], cfg["vocab_size"], cfg["lora_rank"]
    N, P, W = cfg["ssm_state"], cfg["ssm_head_dim"], cfg["ssm_conv_width"]
    d_inner = cfg["ssm_expand"] * D
    H = d_inner // P
    Q = min(cfg["ssm_chunk"], seq)
    shapes = [(D, 2 * d_inner + 2 * N + H), (d_inner, D)]
    frozen = sum(i * o for i, o in shapes)
    adapters = sum(6 * r * (i + o) for i, o in shapes)
    conv = 3 * 2 * W * (d_inner + 2 * N)
    scan = 3 * (2 * Q * N + 2 * H * Q * P + 4 * H * P * N)
    layer = 4 * frozen + adapters + conv + scan
    return cfg["num_layers"] * layer + 4 * D * V
