"""Model FLOPs of one LoRA training pass of the dense decoder, per token.

Counted, per token: the forward matrix products against the frozen weights
(2 per weight) and their activation gradients (2 more); each adapter's
forward (x·A, then ·B) and backward (the gradients of A, of B and of the
adapter's input), 6·r·(d_in + d_out); attention's score and value products,
2·2·S·H·hd forward and twice that backward, over the whole S×S block as
PaLM's count does; the head's product and its activation gradient.  Not
counted: the frozen weights' own gradients, merging the adapters, and any
recomputation.  Norms, RoPE, softmax and the loss are elementwise and left
out.
"""


def flops_per_token(cfg: dict, seq: int) -> float:
    D, F, V = cfg["d_model"], cfg["d_ff"], cfg["vocab_size"]
    H, Kv, hd, r = cfg["num_heads"], cfg["num_kv_heads"], cfg["head_dim"], cfg["lora_rank"]
    shapes = [(D, H * hd), (D, Kv * hd), (D, Kv * hd), (H * hd, D),
              (D, F), (D, F), (F, D)]
    frozen = sum(i * o for i, o in shapes)
    adapters = sum(6 * r * (i + o) for i, o in shapes)
    attention = 3 * 4 * seq * H * hd
    layer = 4 * frozen + adapters + attention
    return cfg["num_layers"] * layer + 4 * D * V
