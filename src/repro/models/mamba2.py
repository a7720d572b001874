"""Mamba-2 (state-space duality / SSD) block, chunked TPU-friendly form.

The sequence is split into chunks of ``ssm_chunk``; the quadratic intra-chunk
part is a batched (attention-like) einsum that maps onto the MXU, and only the
tiny inter-chunk state recurrence (B, H, P, N) is a sequential scan — so the
heavy FLOPs stay outside ``lax.scan`` (correct cost accounting, full MXU
utilisation).  Decode is a single-step state update (O(1) per token, no KV
cache growth — this is why mamba2 runs the ``long_500k`` cell).

State cache layout: (conv_state (B, W-1, conv_ch), ssd_state (B, H, P, N)).
"""

from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp

from repro.config import ModelConfig
from repro.models.layers import project
from repro.parallel import make_param, shard


def dims(cfg: ModelConfig):
    d_inner = cfg.ssm_expand * cfg.d_model
    H = d_inner // cfg.ssm_head_dim  # ssm heads
    P = cfg.ssm_head_dim
    N = cfg.ssm_state
    conv_ch = d_inner + 2 * N  # x, B, C pass through the conv
    return d_inner, H, P, N, conv_ch


def init_mamba(key, cfg: ModelConfig, abstract=False):
    D = cfg.d_model
    d_inner, H, P, N, conv_ch = dims(cfg)
    ks = jax.random.split(key, 6) if key is not None else [None] * 6
    in_dim = 2 * d_inner + 2 * N + H  # z, x, B, C, dt
    return {
        "in_proj": make_param(ks[0], (D, in_dim), ("embed", "heads"), cfg.param_dtype, abstract=abstract),
        "conv_w": make_param(ks[1], (cfg.ssm_conv_width, conv_ch), ("conv", None), cfg.param_dtype,
                             scale=1.0 / math.sqrt(cfg.ssm_conv_width), abstract=abstract),
        "conv_b": make_param(ks[1], (conv_ch,), (None,), cfg.param_dtype, init="zeros", abstract=abstract),
        "A_log": make_param(ks[2], (H,), (None,), "float32", init="zeros", abstract=abstract),
        "D_skip": make_param(ks[3], (H,), (None,), "float32", init="ones", abstract=abstract),
        "dt_bias": make_param(ks[4], (H,), (None,), "float32", init="zeros", abstract=abstract),
        "norm_scale": make_param(ks[5], (d_inner,), (None,), cfg.param_dtype, init="ones", abstract=abstract),
        "out_proj": make_param(ks[5], (d_inner, D), ("heads", "embed"), cfg.param_dtype,
                               scale=0.02 / math.sqrt(2 * cfg.num_layers), abstract=abstract),
    }


def _causal_conv(xBC, w, b, state: Optional[jax.Array]):
    """Depthwise causal conv, width W.  xBC: (B,S,ch); state: (B,W-1,ch)|None.

    Returns (out (B,S,ch), new_state)."""
    W = w.shape[0]
    B, S, ch = xBC.shape
    if state is None:
        pad = jnp.zeros((B, W - 1, ch), xBC.dtype)
    else:
        pad = state.astype(xBC.dtype)
    full = jnp.concatenate([pad, xBC], axis=1)  # (B, S+W-1, ch)
    out = jnp.zeros((B, S, ch), jnp.float32)
    for i in range(W):  # W=4: tiny static unroll
        out = out + full[:, i : i + S, :].astype(jnp.float32) * w[i].astype(jnp.float32)
    out = jax.nn.silu(out + b.astype(jnp.float32)).astype(xBC.dtype)
    new_state = full[:, S:, :] if S >= W - 1 else jnp.concatenate([pad, xBC], axis=1)[:, -(W - 1):, :]
    return out, new_state


def _segsum(log_a):
    """log_a: (..., Q) -> (..., Q, Q) lower-triangular cumulative sums
    L[q, s] = sum_{t=s+1..q} log_a_t (for s <= q)."""
    c = jnp.cumsum(log_a, axis=-1)
    diff = c[..., :, None] - c[..., None, :]  # (..., q, s)
    Q = log_a.shape[-1]
    mask = jnp.tril(jnp.ones((Q, Q), bool))
    return jnp.where(mask, diff, -jnp.inf)


def ssd_chunked(x, dt, A, Bm, Cm, chunk: int, initial_state=None):
    """Chunked SSD. x: (B,S,H,P); dt: (B,S,H); A: (H,) (negative);
    Bm/Cm: (B,S,N).  Returns (y (B,S,H,P), final_state (B,H,P,N)).
    Traced under the scope ``model.ssd``."""
    with jax.named_scope("model.ssd"):
        return _ssd_chunked(x, dt, A, Bm, Cm, chunk, initial_state)


def _ssd_chunked(x, dt, A, Bm, Cm, chunk: int, initial_state=None):
    B, S, H, P = x.shape
    N = Bm.shape[-1]
    Q = min(chunk, S)
    if S % Q:
        # pad to a chunk multiple: dt=0 -> decay 1, input 0 (state-neutral)
        pad = Q - S % Q
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        Bm = jnp.pad(Bm, ((0, 0), (0, pad), (0, 0)))
        Cm = jnp.pad(Cm, ((0, 0), (0, pad), (0, 0)))
        y, h = _ssd_chunked(x, dt, A, Bm, Cm, Q, initial_state)
        return y[:, :S], h
    nc = S // Q

    dtf = dt.astype(jnp.float32)
    log_a = dtf * A  # (B,S,H), negative
    xw = (x.astype(jnp.float32) * dtf[..., None])  # dt-weighted inputs

    # reshape into chunks
    la = log_a.reshape(B, nc, Q, H)
    xc = xw.reshape(B, nc, Q, H, P)
    Bc = Bm.astype(jnp.float32).reshape(B, nc, Q, N)
    Cc = Cm.astype(jnp.float32).reshape(B, nc, Q, N)

    # ---- intra-chunk (quadratic, vectorised over chunks) --------------------
    Lmat = jnp.exp(_segsum(jnp.moveaxis(la, -1, -2)))  # (B,nc,H,Q,Q)
    scores = jnp.einsum("bcqn,bcsn->bcqs", Cc, Bc)[:, :, None] * Lmat  # (B,nc,H,Q,Q)
    y_intra = jnp.einsum("bchqs,bcshp->bcqhp", scores, xc)

    # ---- chunk states --------------------------------------------------------
    la_sum = jnp.sum(la, axis=2)  # (B,nc,H) total decay per chunk
    decay_to_end = jnp.exp(la_sum[:, :, None, :] - jnp.cumsum(la, axis=2))  # (B,nc,Q,H)
    states = jnp.einsum("bcqn,bcqh,bcqhp->bchpn", Bc, decay_to_end, xc)  # (B,nc,H,P,N)

    # ---- inter-chunk recurrence (small sequential scan) ----------------------
    if initial_state is None:
        h0 = jnp.zeros((B, H, P, N), jnp.float32)
    else:
        h0 = initial_state.astype(jnp.float32)

    def step(h, inp):
        s_c, a_c = inp  # (B,H,P,N), (B,H)
        h_prev = h
        h = h * jnp.exp(a_c)[:, :, None, None] + s_c
        return h, h_prev

    states_t = jnp.moveaxis(states, 1, 0)  # (nc,B,H,P,N)
    la_sum_t = jnp.moveaxis(la_sum, 1, 0)  # (nc,B,H)
    h_final, h_prevs = jax.lax.scan(step, h0, (states_t, la_sum_t))
    h_prevs = jnp.moveaxis(h_prevs, 0, 1)  # (B,nc,H,P,N) state entering chunk

    # ---- inter-chunk contribution -------------------------------------------
    decay_from_start = jnp.exp(jnp.cumsum(la, axis=2))  # (B,nc,Q,H)
    y_inter = jnp.einsum("bcqn,bcqh,bchpn->bcqhp", Cc, decay_from_start, h_prevs)

    y = (y_intra + y_inter).reshape(B, S, H, P)
    return y, h_final


def ssd_decode_step(x, dt, A, Bm, Cm, state):
    """Single-token SSD update. x: (B,1,H,P); state: (B,H,P,N)."""
    B = x.shape[0]
    dtf = dt.astype(jnp.float32)[:, 0]  # (B,H)
    a = jnp.exp(dtf * A)  # (B,H)
    xw = x.astype(jnp.float32)[:, 0] * dtf[..., None]  # (B,H,P)
    Bv = Bm.astype(jnp.float32)[:, 0]  # (B,N)
    Cv = Cm.astype(jnp.float32)[:, 0]
    new_state = state * a[:, :, None, None] + jnp.einsum("bhp,bn->bhpn", xw, Bv)
    y = jnp.einsum("bhpn,bn->bhp", new_state, Cv)
    return y[:, None], new_state  # (B,1,H,P)


def apply_mamba(p, u, cfg: ModelConfig, cache=None):
    """u: (B,S,D). cache: (conv_state, ssd_state) or None.

    Returns (out (B,S,D), new_cache)."""
    B, S, D = u.shape
    d_inner, H, P, N, conv_ch = dims(cfg)
    zxbcdt = project(u, p["in_proj"])
    z = zxbcdt[..., :d_inner]
    xBC = zxbcdt[..., d_inner : d_inner + conv_ch]
    dt_raw = zxbcdt[..., d_inner + conv_ch :]  # (B,S,H)

    conv_state = cache[0] if cache is not None else None
    xBC, new_conv_state = _causal_conv(xBC, p["conv_w"], p["conv_b"], conv_state)
    x = xBC[..., :d_inner].reshape(B, S, H, P)
    Bm = xBC[..., d_inner : d_inner + N]
    Cm = xBC[..., d_inner + N :]

    dt = jax.nn.softplus(dt_raw.astype(jnp.float32) + p["dt_bias"].astype(jnp.float32))
    A = -jnp.exp(p["A_log"].astype(jnp.float32))

    if cache is not None and S == 1:
        y, new_state = ssd_decode_step(x, dt, A, Bm, Cm, cache[1])
    else:
        init_state = cache[1] if cache is not None else None
        y, new_state = ssd_chunked(x, dt, A, Bm, Cm, cfg.ssm_chunk, init_state)

    y = y + x.astype(jnp.float32) * p["D_skip"].astype(jnp.float32)[None, None, :, None]
    y = y.reshape(B, S, d_inner).astype(u.dtype)

    # gated RMSNorm (mamba2: norm(y * silu(z)))
    g = y * jax.nn.silu(z)
    gf = g.astype(jnp.float32)
    var = jnp.mean(gf * gf, axis=-1, keepdims=True)
    g = (gf * jax.lax.rsqrt(var + cfg.norm_eps) * p["norm_scale"].astype(jnp.float32)).astype(u.dtype)

    out = project(g, p["out_proj"])
    new_cache = (new_conv_state, new_state) if cache is not None else None
    return out, new_cache


def init_mamba_cache(cfg: ModelConfig, batch: int, dtype):
    d_inner, H, P, N, conv_ch = dims(cfg)
    conv_state = jnp.zeros((batch, cfg.ssm_conv_width - 1, conv_ch), dtype)
    ssd_state = jnp.zeros((batch, H, P, N), jnp.float32)
    return conv_state, ssd_state
