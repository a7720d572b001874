"""Model registry: build/init/apply entry points + analytic parameter counts."""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.config import ModelConfig
from repro.models import transformer as T


class Model(NamedTuple):
    """Bound model API (params passed explicitly — pure functions)."""

    cfg: ModelConfig
    init: Callable  # (key=None, abstract=False) -> (params, axes)
    loss: Callable  # (params, batch) -> (loss, metrics)
    forward: Callable  # (params, batch) -> (logits, aux)
    prefill: Callable  # (params, batch, cache) -> (logits, cache)
    decode_step: Callable  # (params, tokens, cache, pos) -> (logits, cache)
    init_cache: Callable  # (batch, max_seq) -> cache


def build_model(cfg: ModelConfig, remat: bool = False) -> Model:
    def init(key=None, abstract=False):
        return T.init_params(cfg, key=key, abstract=abstract)

    def loss(params, batch):
        return T.loss_fn(params, batch, cfg, remat=remat)

    def forward(params, batch):
        return T.forward(params, batch, cfg, remat=remat)

    def prefill_fn(params, batch, cache):
        return T.prefill(params, batch, cfg, cache)

    def decode_fn(params, tokens, cache, pos, enc_out=None):
        return T.decode_step(params, tokens, cache, pos, cfg, enc_out=enc_out)

    def cache_fn(batch_size, max_seq, dtype=None):
        return T.init_cache(cfg, batch_size, max_seq, dtype=dtype)

    return Model(cfg, init, loss, forward, prefill_fn, decode_fn, cache_fn)


# ---------------------------------------------------------------------------
# Analytic parameter counts (used by the paper's delay model + roofline)
# ---------------------------------------------------------------------------


def _attn_params(cfg: ModelConfig) -> int:
    D, H, Kv, hd = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    n = D * H * hd + 2 * D * Kv * hd + H * hd * D
    if cfg.use_bias:
        n += H * hd + 2 * Kv * hd + D
    if cfg.qk_norm:
        n += 2 * hd
    return n


def _mlp_params(cfg: ModelConfig) -> int:
    D, F = cfg.d_model, cfg.d_ff
    if cfg.mlp_activation in ("swiglu", "geglu"):
        return 3 * D * F
    n = 2 * D * F
    if cfg.use_bias:
        n += F + D
    return n


def _moe_params(cfg: ModelConfig) -> int:
    D, F, E = cfg.d_model, cfg.d_ff, cfg.num_experts
    return D * cfg.num_router_experts + 3 * E * D * F + 3 * D * cfg.shared_d_ff


def _mamba_params(cfg: ModelConfig) -> int:
    from repro.models.mamba2 import dims

    d_inner, H, P, N, conv_ch = dims(cfg)
    D = cfg.d_model
    in_dim = 2 * d_inner + 2 * N + H
    return (D * in_dim + cfg.ssm_conv_width * conv_ch + conv_ch
            + 3 * H + d_inner + d_inner * D)


def _rglru_params(cfg: ModelConfig) -> int:
    D, W = cfg.d_model, cfg.lru_width
    return 2 * D * W + cfg.ssm_conv_width * W + W + 5 * W + W * D


def _sublayer_params(cfg: ModelConfig, ch: str, cross: bool = False) -> int:
    D = cfg.d_model
    norm = D
    if ch in ("G", "L"):
        n = norm + _attn_params(cfg)
        if cross:
            n += norm + _attn_params(cfg)
        if not cfg.parallel_block:
            n += norm
        if cfg.use_post_norm:
            n += 2 * norm
        n += _moe_params(cfg) if cfg.num_experts else _mlp_params(cfg)
        return n
    if ch == "M":
        ffn = norm + (_moe_params(cfg) if cfg.num_experts else _mlp_params(cfg))
        return norm + _mamba_params(cfg) + (ffn if cfg.ssm_ffn else 0)
    if ch == "R":
        return norm + _rglru_params(cfg) + norm + _mlp_params(cfg)
    raise ValueError(ch)


def count_params(cfg: ModelConfig, trainable_only: bool = False) -> int:
    """Total parameter count; with trainable_only, LoRA adapter params only."""
    if trainable_only:
        from repro.core.lora import lora_param_count

        return lora_param_count(cfg)
    D = cfg.d_model
    n = cfg.vocab_size * D  # embed
    if not cfg.tie_embeddings:
        n += D * cfg.vocab_size
    cross = cfg.family == "encdec"
    for ch in cfg.pattern:
        n += _sublayer_params(cfg, ch, cross=cross)
    n += D  # final norm
    if cfg.family == "encdec":
        for _ in range(cfg.num_encoder_layers):
            n += _sublayer_params(cfg, "G", cross=False)
        n += D + cfg.encoder_seq * D + 32768 * D
    if cfg.family == "vlm":
        n += 1024 * D + D + D * D + D
    return n


def active_param_count(cfg: ModelConfig) -> int:
    """MoE: params touched per token (for MODEL_FLOPS = 6·N_active·D)."""
    if not cfg.num_experts:
        return count_params(cfg)
    act = cfg.replace(num_experts=cfg.num_experts_per_tok,
                      router_experts=cfg.num_router_experts)
    # router counted fully; experts scaled to top-k
    return count_params(act)
