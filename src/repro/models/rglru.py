"""RecurrentGemma / Griffin recurrent block: RG-LRU with conv1d + GeGLU gate.

The diagonal linear recurrence h_t = a_t * h_{t-1} + b_t is evaluated with
``jax.lax.associative_scan`` (log-depth, fully materialised ops — the
TPU-idiomatic equivalent of Griffin's custom linear-scan kernel; also keeps
all FLOPs visible to HLO cost analysis).  Decode is a single-step update.

Cache layout per recurrent layer: (conv_state (B, W-1, lru), h (B, lru) fp32).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from repro.config import ModelConfig
from repro.models.layers import project
from repro.parallel import make_param, shard

_C = 8.0  # RG-LRU decay sharpness constant (Griffin)


def init_rglru_block(key, cfg: ModelConfig, abstract=False):
    D, W = cfg.d_model, cfg.lru_width
    ks = jax.random.split(key, 7) if key is not None else [None] * 7
    return {
        # two input branches: recurrent branch + gate branch
        "w_rec_in": make_param(ks[0], (D, W), ("embed", "mlp"), cfg.param_dtype, abstract=abstract),
        "w_gate_in": make_param(ks[1], (D, W), ("embed", "mlp"), cfg.param_dtype, abstract=abstract),
        "conv_w": make_param(ks[2], (cfg.ssm_conv_width, W), ("conv", "mlp"), cfg.param_dtype,
                             scale=1.0 / math.sqrt(cfg.ssm_conv_width), abstract=abstract),
        "conv_b": make_param(ks[2], (W,), ("mlp",), cfg.param_dtype, init="zeros", abstract=abstract),
        # RG-LRU gates (per-channel diagonal)
        "w_a": make_param(ks[3], (W,), ("mlp",), "float32", init="zeros", abstract=abstract),
        "b_a": make_param(ks[3], (W,), ("mlp",), "float32", init="zeros", abstract=abstract),
        "w_x": make_param(ks[4], (W,), ("mlp",), "float32", init="ones", abstract=abstract),
        "b_x": make_param(ks[4], (W,), ("mlp",), "float32", init="zeros", abstract=abstract),
        "lambda_p": make_param(ks[5], (W,), ("mlp",), "float32", init="ones", abstract=abstract),
        "w_out": make_param(ks[6], (W, D), ("mlp", "embed"), cfg.param_dtype,
                            scale=0.02 / math.sqrt(2 * cfg.num_layers), abstract=abstract),
    }


def _rglru_coeffs(p, x):
    """Per-step gates. x: (B,S,W) (post-conv). Returns (a, b) fp32."""
    xf = x.astype(jnp.float32)
    r = jax.nn.sigmoid(xf * p["w_a"] + p["b_a"])  # recurrence gate
    i = jax.nn.sigmoid(xf * p["w_x"] + p["b_x"])  # input gate
    log_a = -_C * jax.nn.softplus(p["lambda_p"]) * r
    a = jnp.exp(log_a)
    gated_x = i * xf
    b = jnp.sqrt(jnp.maximum(1.0 - jnp.exp(2.0 * log_a), 1e-12)) * gated_x
    return a, b


def _linear_scan(a, b, h0=None):
    """h_t = a_t h_{t-1} + b_t via associative scan over axis 1 (seq)."""
    if h0 is not None:
        b = b.at[:, 0].add(a[:, 0] * h0)

    def combine(c1, c2):
        a1, b1 = c1
        a2, b2 = c2
        return a2 * a1, a2 * b1 + b2

    _, h = jax.lax.associative_scan(combine, (a, b), axis=1)
    return h


def apply_rglru_block(p, u, cfg: ModelConfig, cache=None):
    """u: (B,S,D); cache: (conv_state, h) or None. Returns (out, new_cache)."""
    from repro.models.mamba2 import _causal_conv

    B, S, D = u.shape
    rec = project(u, p["w_rec_in"])  # (B,S,W)
    gate = jax.nn.gelu(project(u, p["w_gate_in"]), approximate=True)

    conv_state = cache[0] if cache is not None else None
    rec, new_conv_state = _causal_conv(rec, p["conv_w"], p["conv_b"], conv_state)

    a, b = _rglru_coeffs(p, rec)
    if cache is not None and S == 1:
        h_prev = cache[1]
        h = a[:, 0] * h_prev + b[:, 0]
        y = h[:, None]
        new_h = h
    else:
        h0 = cache[1] if cache is not None else None
        y = _linear_scan(a, b, h0)
        new_h = y[:, -1]

    y = shard(y.astype(u.dtype), ("batch", "seq", "mlp"))
    out = project(y * gate, p["w_out"])
    new_cache = (new_conv_state, new_h) if cache is not None else None
    return out, new_cache


def init_rglru_cache(cfg: ModelConfig, batch: int, dtype):
    conv_state = jnp.zeros((batch, cfg.ssm_conv_width - 1, cfg.lru_width), dtype)
    h = jnp.zeros((batch, cfg.lru_width), jnp.float32)
    return conv_state, h
