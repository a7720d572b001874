"""Shared transformer layers: norms, RoPE, GQA attention (global / sliding
window / softcap / qk-norm), MLP variants, embeddings and logit heads.

All parameters are declared through ``make_param`` so every leaf carries its
logical sharding axes.  All functions are pure; attention supports three
modes: full-sequence (train / prefill), block-banded local attention, and
single-step decode against a (possibly ring-buffer) KV cache.
"""

from __future__ import annotations

import functools
import math
from typing import Any, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.config import ModelConfig
from repro.parallel import make_param, shard

# ---------------------------------------------------------------------------
# Projections
# ---------------------------------------------------------------------------


@jax.tree_util.register_pytree_node_class
class Adapted:
    """A frozen projection weight ``w`` with its LoRA pair attached, applied
    unmerged by ``project`` as x·w + scale·(x·a)·b.  ``a`` and ``b`` keep
    ``w``'s leading (stacked-layer, expert) dims, so ``lax.scan`` and
    ``shard_map`` slice all three together; ``scale`` is static."""

    def __init__(self, w, a, b, scale: float):
        self.w, self.a, self.b, self.scale = w, a, b, scale

    def tree_flatten(self):
        return (self.w, self.a, self.b), self.scale

    @classmethod
    def tree_unflatten(cls, scale, children):
        return cls(*children, scale)


def project(x, w, spec: Optional[str] = None, group_sizes=None):
    """x times a projection weight: ``x @ w``, given an einsum ``spec``
    ``einsum(spec, x, w)``, or given ``group_sizes`` the grouped product
    ``grouped_dot`` (rows of x sorted by expert, w stacked over experts), in
    x's dtype.  An ``Adapted`` weight adds its adapter unmerged: x·W
    accumulates in float32, the adapter (traced under the scope
    ``lora.adapter``) is float32 from x·A on, and the sum is rounded once.
    Its gradient never forms a W-sized array."""
    def dot(u, v, **kw):
        if group_sizes is not None:
            return grouped_dot(u, v, group_sizes, kw.get("preferred_element_type"))
        return jnp.matmul(u, v, **kw) if spec is None else jnp.einsum(spec, u, v, **kw)

    if not isinstance(w, Adapted):
        return dot(x, w.astype(x.dtype))
    y = dot(x, w.w.astype(x.dtype), preferred_element_type=jnp.float32)
    with jax.named_scope("lora.adapter"):
        xa = dot(x, w.a.astype(x.dtype), preferred_element_type=jnp.float32)
        y = y + w.scale * dot(xa, w.b.astype(jnp.float32))
    return y.astype(x.dtype)


# Grouped products over experts.  ``lax.ragged_dot`` batches under ``vmap``
# only when every operand is batched, and a cohort's frozen expert weights
# are not: broadcasting them would copy every expert once per client.  So
# the two products below carry their own batching rule, one ragged product
# per batch element against the shared (or that element's) weights, and
# ``grouped_dot``'s gradient is written with them.

def _ragged_dot(x, w, gs, pet):
    """(m, k) rows sorted by group × (g, k, n) -> (m, n); rows past the
    last group are 0 (the TPU's grouped product leaves them unwritten, and
    what the buffer held, NaN or inf among it, must not reach a sum)."""
    y = jax.lax.ragged_dot(x, w, gs, preferred_element_type=pet)
    return jnp.where(jnp.arange(x.shape[0])[:, None] < jnp.sum(gs), y, 0)


_RAGGED_OUTER = jax.lax.RaggedDotDimensionNumbers(
    dot_dimension_numbers=(([0], [0]), ([], [])), lhs_ragged_dimensions=[0],
    rhs_group_dimensions=[])


def _ragged_outer(x, ct, gs, pet):
    """Per group, the rows' xᵀ·ct: (m, k), (m, n) -> (g, k, n); 0 for an
    empty group (which, as above, may be left unwritten)."""
    y = jax.lax.ragged_dot_general(x, ct, gs, _RAGGED_OUTER, preferred_element_type=pet)
    return jnp.where((gs > 0)[:, None, None], y, 0)


@functools.lru_cache(maxsize=None)
def _per_element(fn, pet):
    """``fn`` at output dtype ``pet``, batched as one call per batch element."""
    def call(a, b, gs):
        return fn(a, b, gs, pet)

    f = jax.custom_batching.custom_vmap(call)

    @f.def_vmap
    def _rule(axis_size, in_batched, a, b, gs):
        pick = [(lambda t, c: t[c]) if bat else (lambda t, c: t) for bat in in_batched]
        return jnp.stack([f(pick[0](a, c), pick[1](b, c), pick[2](gs, c))
                          for c in range(axis_size)]), True

    return f


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def grouped_dot(x, w, group_sizes, preferred_element_type=None):
    """Rows of ``x`` (m, k), sorted by group with ``group_sizes`` (g,) rows
    each, times their group's ``w`` (g, k, n): (m, n), rows past the last
    group 0.  Batches under ``vmap`` with or without a batched ``w``."""
    pet = jnp.dtype(preferred_element_type or x.dtype)
    return _per_element(_ragged_dot, pet)(x, w, group_sizes)


def _grouped_fwd(x, w, group_sizes, preferred_element_type):
    return grouped_dot(x, w, group_sizes, preferred_element_type), (x, w, group_sizes)


def _grouped_bwd(preferred_element_type, res, ct):
    x, w, gs = res
    dx = _per_element(_ragged_dot, jnp.dtype(jnp.float32))(
        ct.astype(w.dtype), jnp.swapaxes(w, -1, -2), gs)
    dw = _per_element(_ragged_outer, jnp.dtype(jnp.float32))(x, ct.astype(x.dtype), gs)
    return dx.astype(x.dtype), dw.astype(w.dtype), None


grouped_dot.defvjp(_grouped_fwd, _grouped_bwd)


# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------


def init_norm(key, cfg: ModelConfig, dim: int, prefix=(), abstract=False):
    p = {"scale": make_param(key, (dim,), ("embed",), cfg.param_dtype, init="ones", abstract=abstract)}
    if cfg.norm_type == "layernorm" and cfg.use_bias:
        p["bias"] = make_param(key, (dim,), ("embed",), cfg.param_dtype, init="zeros", abstract=abstract)
    return p


def apply_norm(p, x, cfg: ModelConfig):
    eps = cfg.norm_eps
    xf = x.astype(jnp.float32)
    if cfg.norm_type == "rmsnorm":
        var = jnp.mean(xf * xf, axis=-1, keepdims=True)
        y = xf * jax.lax.rsqrt(var + eps)
    else:  # layernorm
        mu = jnp.mean(xf, axis=-1, keepdims=True)
        var = jnp.var(xf, axis=-1, keepdims=True)
        y = (xf - mu) * jax.lax.rsqrt(var + eps)
    y = y * p["scale"].astype(jnp.float32)
    if "bias" in p:
        y = y + p["bias"].astype(jnp.float32)
    return y.astype(x.dtype)


def rms_norm_only(w, x, eps: float = 1e-6):
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    return (xf * jax.lax.rsqrt(var + eps) * w.astype(jnp.float32)).astype(x.dtype)


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------


def rope_frequencies(head_dim: int, theta: float) -> jax.Array:
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))


def apply_rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: (B, S, H, hd); positions: (B, S) or (S,)."""
    hd = x.shape[-1]
    freqs = rope_frequencies(hd, theta)  # (hd/2,)
    if positions.ndim == 1:
        positions = positions[None, :]
    angles = positions[..., None].astype(jnp.float32) * freqs  # (B,S,hd/2)
    cos = jnp.cos(angles)[:, :, None, :]
    sin = jnp.sin(angles)[:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# Attention
# ---------------------------------------------------------------------------


class KVCache(NamedTuple):
    """Per-layer-stack KV cache.

    k/v: (groups, B, S_cache, kv_heads, head_dim) — stacked over scan groups.
    For sliding-window layers S_cache = window (ring buffer addressed by
    ``pos % window``); for global layers S_cache = max_seq.
    """

    k: jax.Array
    v: jax.Array


def init_attn(key, cfg: ModelConfig, prefix="attn", abstract=False):
    D, H, Kv, hd = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    ks = jax.random.split(key, 8) if key is not None else [None] * 8
    scale = 0.02
    p = {
        "wq": make_param(ks[0], (D, H * hd), ("embed", "heads"), cfg.param_dtype, scale=scale, abstract=abstract),
        "wk": make_param(ks[1], (D, Kv * hd), ("embed", "kv_heads"), cfg.param_dtype, scale=scale, abstract=abstract),
        "wv": make_param(ks[2], (D, Kv * hd), ("embed", "kv_heads"), cfg.param_dtype, scale=scale, abstract=abstract),
        "wo": make_param(ks[3], (H * hd, D), ("heads", "embed"), cfg.param_dtype, scale=scale / math.sqrt(2 * cfg.num_layers), abstract=abstract),
    }
    if cfg.use_bias:
        p["bq"] = make_param(ks[4], (H * hd,), ("heads",), cfg.param_dtype, init="zeros", abstract=abstract)
        p["bk"] = make_param(ks[5], (Kv * hd,), ("kv_heads",), cfg.param_dtype, init="zeros", abstract=abstract)
        p["bv"] = make_param(ks[6], (Kv * hd,), ("kv_heads",), cfg.param_dtype, init="zeros", abstract=abstract)
        p["bo"] = make_param(ks[7], (D,), ("embed",), cfg.param_dtype, init="zeros", abstract=abstract)
    if cfg.qk_norm:
        p["q_norm"] = make_param(ks[4], (hd,), (None,), cfg.param_dtype, init="ones", abstract=abstract)
        p["k_norm"] = make_param(ks[5], (hd,), (None,), cfg.param_dtype, init="ones", abstract=abstract)
    return p


def _softcap(logits, cap: float):
    if cap and cap > 0:
        return cap * jnp.tanh(logits / cap)
    return logits


def score_divisor(cfg: ModelConfig) -> float:
    """What attention scores are divided by: 1/attention_multiplier, else
    √head_dim."""
    m = cfg.attention_multiplier
    return 1.0 / m if m else math.sqrt(cfg.head_dim)


def _attend_full(q, k, v, *, causal: bool, window: int, softcap: float,
                 q_offset: jax.Array | int = 0, kv_offset: jax.Array | int = 0,
                 divisor: Optional[float] = None):
    """Dense masked attention. q: (B,Sq,H,hd); k/v: (B,Skv,Kv,hd); scores
    over ``divisor`` (default √hd)."""
    B, Sq, H, hd = q.shape
    Kv = k.shape[2]
    rep = H // Kv
    qh = q.reshape(B, Sq, Kv, rep, hd)
    logits = jnp.einsum("bqkrh,bskh->bkrqs", qh, k, preferred_element_type=jnp.float32)
    logits = logits / (divisor or math.sqrt(hd))
    logits = _softcap(logits, softcap)
    qpos = jnp.arange(Sq) + q_offset  # absolute positions
    kpos = jnp.arange(k.shape[1]) + kv_offset
    mask = jnp.ones((Sq, k.shape[1]), dtype=bool)
    if causal:
        mask &= kpos[None, :] <= qpos[:, None]
    if window and window > 0:
        mask &= kpos[None, :] > (qpos[:, None] - window)
    logits = jnp.where(mask[None, None, None], logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    out = jnp.einsum("bkrqs,bskh->bqkrh", probs, v)
    return out.reshape(B, Sq, H * hd)


def _attend_banded(q, k, v, *, window: int, softcap: float, divisor: Optional[float] = None):
    """Block-banded sliding-window attention: exact for causal window ≤ block.

    Splits seq into blocks of ``window``; block i attends to blocks {i-1, i}.
    Flops O(S·2w·hd) instead of O(S²·hd).
    """
    B, S, H, hd = q.shape
    Kv = k.shape[2]
    assert S % window == 0, (S, window)
    nb = S // window
    rep = H // Kv
    qb = q.reshape(B, nb, window, Kv, rep, hd)
    kb = k.reshape(B, nb, window, Kv, hd)
    vb = v.reshape(B, nb, window, Kv, hd)
    # previous block (block -1 = zeros, masked out anyway)
    kprev = jnp.pad(kb[:, :-1], ((0, 0), (1, 0), (0, 0), (0, 0), (0, 0)))
    vprev = jnp.pad(vb[:, :-1], ((0, 0), (1, 0), (0, 0), (0, 0), (0, 0)))
    k2 = jnp.concatenate([kprev, kb], axis=2)  # (B,nb,2w,Kv,hd)
    v2 = jnp.concatenate([vprev, vb], axis=2)
    logits = jnp.einsum("bnqkrh,bnskh->bnkrqs", qb, k2, preferred_element_type=jnp.float32)
    logits = _softcap(logits / (divisor or math.sqrt(hd)), softcap)
    qpos = jnp.arange(window)[:, None]  # within-block index
    kpos = jnp.arange(2 * window)[None, :] - window  # relative to block start
    mask = (kpos <= qpos) & (kpos > qpos - window)
    first_block = jnp.arange(nb) == 0  # block 0 has no prev block
    mask_full = mask[None, :, :] & ~(first_block[:, None, None] & (kpos[None] < 0))
    logits = jnp.where(mask_full[None, :, None, None], logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    out = jnp.einsum("bnkrqs,bnskh->bnqkrh", probs, v2)
    return out.reshape(B, S, H * hd)


def _attend_chunked_q(q, k, v, *, causal: bool, window: int, softcap: float,
                      chunk: int, unroll: bool = False, divisor: Optional[float] = None):
    """Query-chunked attention (bounds logits memory to S·chunk per head).

    Used for long prefill.  The KV tensors stay whole (flash-style online
    softmax lives in the Pallas kernel; this jnp path chunks queries only,
    which is enough to bound memory since kv is shared)."""
    B, S, H, hd = q.shape
    nq = S // chunk

    def one(i):
        qs = jax.lax.dynamic_slice_in_dim(q, i * chunk, chunk, axis=1)
        return _attend_full(qs, k, v, causal=causal, window=window, softcap=softcap,
                            q_offset=i * chunk, kv_offset=0, divisor=divisor)

    if unroll:
        outs = [one(i) for i in range(nq)]
        return jnp.concatenate(outs, axis=1)
    outs = jax.lax.map(one, jnp.arange(nq))  # (nq, B, chunk, H*hd)
    return jnp.moveaxis(outs, 0, 1).reshape(B, S, H * hd)


def _attend(q, k, v, cfg: ModelConfig, *, causal: bool, window: int, q_chunk: int,
            unroll: bool):
    """The attention core over in-flight k/v (banded, query-chunked or full),
    traced under the scope ``model.attention``; the projections are the
    caller's."""
    S = q.shape[1]
    softcap = cfg.attn_logit_softcap
    div = score_divisor(cfg)
    with jax.named_scope("model.attention"):
        if causal and window and window > 0 and S % window == 0 and S > window:
            return _attend_banded(q, k, v, window=window, softcap=softcap, divisor=div)
        if q_chunk and S > q_chunk:
            return _attend_chunked_q(q, k, v, causal=causal, window=window,
                                     softcap=softcap, chunk=q_chunk, unroll=unroll,
                                     divisor=div)
        return _attend_full(q, k, v, causal=causal, window=window, softcap=softcap,
                            divisor=div)


def attention(
    p,
    x: jax.Array,
    cfg: ModelConfig,
    *,
    window: int = 0,
    positions: Optional[jax.Array] = None,
    cache: Optional[tuple[jax.Array, jax.Array]] = None,
    cache_pos: Optional[jax.Array] = None,
    q_chunk: int = 0,
    unroll_chunks: bool = False,
    causal: bool = True,
):
    """GQA attention. Returns (out, new_cache_kv or None).

    cache: (k, v) each (B, S_cache, Kv, hd); decode mode when x seq==1 (or
    small) and cache is given; cache_pos = current absolute position (int32
    scalar array).
    """
    B, S, D = x.shape
    H, Kv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    q = project(x, p["wq"])
    k = project(x, p["wk"])
    v = project(x, p["wv"])
    if "bq" in p:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    q = q.reshape(B, S, H, hd)
    k = k.reshape(B, S, Kv, hd)
    v = v.reshape(B, S, Kv, hd)
    if cfg.qk_norm:
        q = rms_norm_only(p["q_norm"], q)
        k = rms_norm_only(p["k_norm"], k)
    if positions is None:
        positions = jnp.arange(S)[None, :] + (0 if cache_pos is None else cache_pos)
    if cfg.use_rope:
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)

    q = shard(q, ("batch", "seq", "heads", None))
    k = shard(k, ("batch", "seq", "kv_heads", None))
    v = shard(v, ("batch", "seq", "kv_heads", None))

    new_cache = None
    if cache is not None and S == 1:
        # ---- decode: single token vs cache --------------------------------
        ck, cv = cache
        S_cache = ck.shape[1]
        if window and window > 0 and S_cache == window:
            # ring buffer: overwrite slot pos % window
            slot = jnp.mod(cache_pos, window)
            ck = jax.lax.dynamic_update_slice_in_dim(ck, k.astype(ck.dtype), slot, axis=1)
            cv = jax.lax.dynamic_update_slice_in_dim(cv, v.astype(cv.dtype), slot, axis=1)
            kpos_abs = _ring_positions(cache_pos, window)
        else:
            ck = jax.lax.dynamic_update_slice_in_dim(ck, k.astype(ck.dtype), cache_pos, axis=1)
            cv = jax.lax.dynamic_update_slice_in_dim(cv, v.astype(cv.dtype), cache_pos, axis=1)
            kpos_abs = None
        ck = shard(ck, ("batch", "kv_seq", "kv_heads", None))
        cv = shard(cv, ("batch", "kv_seq", "kv_heads", None))
        new_cache = (ck, cv)
        with jax.named_scope("model.attention"):
            out = _decode_attend(q, ck, cv, cfg=cfg, window=window, cache_pos=cache_pos,
                                 kpos_abs=kpos_abs)
    elif cache is not None:
        # ---- prefill: attend with in-flight k/v, write the cache ----------
        ck, cv = cache
        S_cache = ck.shape[1]
        if S >= S_cache:
            # ring-buffer (or exactly-full) cache keeps the last S_cache keys;
            # slot layout matches _ring_positions when S % S_cache == 0
            ck = k[:, S - S_cache:].astype(ck.dtype)
            cv = v[:, S - S_cache:].astype(cv.dtype)
        else:
            ck = jax.lax.dynamic_update_slice_in_dim(ck, k.astype(ck.dtype), 0, axis=1)
            cv = jax.lax.dynamic_update_slice_in_dim(cv, v.astype(cv.dtype), 0, axis=1)
        ck = shard(ck, ("batch", "kv_seq", "kv_heads", None))
        cv = shard(cv, ("batch", "kv_seq", "kv_heads", None))
        new_cache = (ck, cv)
        out = _attend(q, k, v, cfg, causal=causal, window=window, q_chunk=q_chunk,
                      unroll=unroll_chunks)
    else:
        out = _attend(q, k, v, cfg, causal=causal, window=window, q_chunk=q_chunk,
                      unroll=unroll_chunks)
    out = shard(out, ("batch", "seq", "heads"))
    y = project(out, p["wo"])
    if "bo" in p:
        y = y + p["bo"]
    return y, new_cache


def _ring_positions(cache_pos, window):
    """Absolute positions stored in each ring-buffer slot after writing at
    slot = cache_pos % window.  Slot j holds position: the largest p <= cache_pos
    with p % window == j."""
    slots = jnp.arange(window)
    cur = jnp.mod(cache_pos, window)
    base = cache_pos - cur
    pos = jnp.where(slots <= cur, base + slots, base - window + slots)
    return pos  # (window,) may be negative for not-yet-written slots


def _decode_attend(q, ck, cv, *, cfg: ModelConfig, window: int, cache_pos, kpos_abs):
    """q: (B,1,H,hd) vs cache (B,Sc,Kv,hd)."""
    B, Sq, H, hd = q.shape
    Kv = ck.shape[2]
    rep = H // Kv
    qh = q.reshape(B, Sq, Kv, rep, hd)
    logits = jnp.einsum("bqkrh,bskh->bkrqs", qh, ck.astype(q.dtype),
                        preferred_element_type=jnp.float32) / score_divisor(cfg)
    logits = _softcap(logits, cfg.attn_logit_softcap)
    if kpos_abs is not None:  # ring buffer
        valid = (kpos_abs >= 0) & (kpos_abs <= cache_pos)
        if window:
            valid &= kpos_abs > cache_pos - window
        mask = valid[None, None, None, None, :]
    else:
        kpos = jnp.arange(ck.shape[1])
        valid = kpos <= cache_pos
        if window and window > 0:
            valid &= kpos > cache_pos - window
        mask = valid[None, None, None, None, :]
    logits = jnp.where(mask, logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    out = jnp.einsum("bkrqs,bskh->bqkrh", probs, cv.astype(q.dtype))
    return out.reshape(B, Sq, H * hd)


# ---------------------------------------------------------------------------
# MLP
# ---------------------------------------------------------------------------


def init_mlp(key, cfg: ModelConfig, abstract=False):
    D, F = cfg.d_model, cfg.d_ff
    ks = jax.random.split(key, 3) if key is not None else [None] * 3
    act = cfg.mlp_activation
    p = {}
    if act in ("swiglu", "geglu"):
        p["w_gate"] = make_param(ks[0], (D, F), ("embed", "mlp"), cfg.param_dtype, abstract=abstract)
        p["w_up"] = make_param(ks[1], (D, F), ("embed", "mlp"), cfg.param_dtype, abstract=abstract)
    else:
        p["w_up"] = make_param(ks[1], (D, F), ("embed", "mlp"), cfg.param_dtype, abstract=abstract)
        if cfg.use_bias:
            p["b_up"] = make_param(ks[1], (F,), ("mlp",), cfg.param_dtype, init="zeros", abstract=abstract)
    p["w_down"] = make_param(ks[2], (F, D), ("mlp", "embed"), cfg.param_dtype,
                             scale=0.02 / math.sqrt(2 * cfg.num_layers), abstract=abstract)
    if cfg.use_bias:
        p["b_down"] = make_param(ks[2], (D,), ("embed",), cfg.param_dtype, init="zeros", abstract=abstract)
    return p


def apply_mlp(p, x, cfg: ModelConfig):
    act = cfg.mlp_activation
    if act == "swiglu":
        h = jax.nn.silu(project(x, p["w_gate"])) * project(x, p["w_up"])
    elif act == "geglu":
        h = jax.nn.gelu(project(x, p["w_gate"]), approximate=True) * project(x, p["w_up"])
    else:
        h = project(x, p["w_up"])
        if "b_up" in p:
            h = h + p["b_up"]
        h = jax.nn.gelu(h, approximate=True)
    h = shard(h, ("batch", "seq", "mlp"))
    y = project(h, p["w_down"])
    if "b_down" in p:
        y = y + p["b_down"]
    return y


# ---------------------------------------------------------------------------
# Embedding / head
# ---------------------------------------------------------------------------


def init_embed(key, cfg: ModelConfig, abstract=False):
    ks = jax.random.split(key, 2) if key is not None else [None, None]
    p = {"tokens": make_param(ks[0], (cfg.vocab_size, cfg.d_model), ("vocab", "embed"),
                              cfg.param_dtype, scale=0.02, abstract=abstract)}
    if not cfg.tie_embeddings:
        p["head"] = make_param(ks[1], (cfg.d_model, cfg.vocab_size), ("embed", "vocab"),
                               cfg.param_dtype, abstract=abstract)
    return p


def embed_tokens(p, tokens, cfg: ModelConfig):
    x = jnp.take(p["tokens"].astype(cfg.dtype), tokens, axis=0)
    if cfg.embedding_multiplier != 1.0:
        x = x * jnp.asarray(cfg.embedding_multiplier, dtype=x.dtype)
    return shard(x, ("batch", "seq", "embed"))


def lm_logits(p, x, cfg: ModelConfig):
    w = p["tokens"].T if cfg.tie_embeddings else p["head"]
    logits = jnp.einsum("bsd,dv->bsv", x, w.astype(x.dtype), preferred_element_type=jnp.float32)
    if cfg.logit_scale != 1.0:
        logits = logits * cfg.logit_scale
    logits = _softcap(logits, cfg.final_logit_softcap)
    return shard(logits, ("batch", "seq", "vocab"))


def cross_entropy(logits, labels, mask=None):
    """Mean token cross-entropy in fp32. logits (B,S,V), labels (B,S)."""
    logits = logits.astype(jnp.float32)
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    nll = logz - gold
    if mask is not None:
        return jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)
    return jnp.mean(nll)


def fused_cross_entropy(params_embed, x, labels, cfg, mask=None, chunk: int = 256,
                        unroll: bool = False):
    """Sequence-chunked CE: logits for a token chunk are computed, reduced to
    (logsumexp, gold-logit) partials, and *discarded* — the full (B, S, V)
    fp32 logits tensor never exists (§Perf iter 5: it dominated HBM bytes for
    every large-vocab train cell; command-r train_4k memory term 29.3s).

    Gold logits are extracted with a one-hot contraction so the vocab dim can
    stay ``model``-sharded (take_along_axis would force an all-gather)."""
    B, S, D = x.shape
    V = cfg.vocab_size
    w = params_embed["tokens"].T if cfg.tie_embeddings else params_embed["head"]
    chunk = min(chunk, S)
    if S % chunk:
        pad = chunk - S % chunk
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0)))
        labels = jnp.pad(labels, ((0, 0), (0, pad)))
        mask = jnp.pad(mask if mask is not None else jnp.ones((B, S), jnp.float32),
                       ((0, 0), (0, pad)))
    elif mask is None:
        mask = jnp.ones((B, S), jnp.float32)
    Sp = x.shape[1]
    nc = Sp // chunk

    def body(carry, i):
        nll_sum, cnt = carry
        xs = jax.lax.dynamic_slice_in_dim(x, i * chunk, chunk, axis=1)
        ls = jax.lax.dynamic_slice_in_dim(labels, i * chunk, chunk, axis=1)
        ms = jax.lax.dynamic_slice_in_dim(mask, i * chunk, chunk, axis=1)
        logits = jnp.einsum("bsd,dv->bsv", xs, w.astype(xs.dtype),
                            preferred_element_type=jnp.float32)
        if cfg.logit_scale != 1.0:
            logits = logits * cfg.logit_scale
        logits = _softcap(logits, cfg.final_logit_softcap)
        logits = shard(logits, ("batch", "seq", "vocab"))
        logz = jax.nn.logsumexp(logits, axis=-1)  # (B, c)
        onehot = jax.nn.one_hot(ls, V, dtype=logits.dtype)
        onehot = shard(onehot, ("batch", "seq", "vocab"))
        gold = jnp.einsum("bsv,bsv->bs", logits, onehot)
        nll_sum = nll_sum + jnp.sum((logz - gold) * ms)
        cnt = cnt + jnp.sum(ms)
        return (nll_sum, cnt), None

    body = jax.checkpoint(body, policy=jax.checkpoint_policies.nothing_saveable)
    (nll_sum, cnt), _ = jax.lax.scan(body, (jnp.zeros((), jnp.float32),
                                            jnp.zeros((), jnp.float32)),
                                     jnp.arange(nc), unroll=unroll)
    return nll_sum / jnp.maximum(cnt, 1.0)
