"""Generic multi-family transformer stack.

One code path covers all 10 assigned architectures via ``layer_pattern``
chars: G (global attention), L (local / sliding-window attention),
M (Mamba-2 SSD), R (RG-LRU recurrent).  Layers are grouped into one copy of
the pattern and the group stack is evaluated with ``lax.scan`` over stacked
parameters (HLO size independent of depth).  A non-divisible remainder
("tail") is applied unscanned so e.g. recurrentgemma's 38 = 12x(RRL) + RR
is exact.

The same group-apply function is reused by (a) full forward, (b) the
split-learning client/server partition (slicing the stacked group params),
and (c) the roofline calibration lowering (single group, loop-free).
"""

from __future__ import annotations

import functools
from typing import Any, NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.config import ModelConfig
from repro.models import layers as L
from repro.models import mamba2 as M2
from repro.models import moe as MOE
from repro.models import rglru as RG
from repro.parallel import ParamLeaf, make_param, shard, split_param_tree

# ---------------------------------------------------------------------------
# Structure helpers
# ---------------------------------------------------------------------------


def group_chars(cfg: ModelConfig) -> str:
    return cfg.layer_pattern


def n_full_groups(cfg: ModelConfig) -> int:
    return cfg.num_layers // len(cfg.layer_pattern)


def tail_chars(cfg: ModelConfig) -> str:
    rem = cfg.num_layers % len(cfg.layer_pattern)
    return cfg.layer_pattern[:rem]


def _char_window(cfg: ModelConfig, ch: str) -> int:
    if ch == "L":
        return cfg.sliding_window
    return 0


# ---------------------------------------------------------------------------
# Single layer (one pattern char)
# ---------------------------------------------------------------------------


def init_sublayer(key, cfg: ModelConfig, ch: str, abstract=False, cross_attn=False):
    ks = jax.random.split(key, 6) if key is not None else [None] * 6
    p: dict[str, Any] = {"norm1": L.init_norm(ks[0], cfg, cfg.d_model, abstract=abstract)}
    if ch in ("G", "L"):
        p["attn"] = L.init_attn(ks[1], cfg, abstract=abstract)
        if cross_attn:
            p["norm_x"] = L.init_norm(ks[2], cfg, cfg.d_model, abstract=abstract)
            p["xattn"] = L.init_attn(ks[3], cfg, abstract=abstract)
        if not cfg.parallel_block:
            p["norm2"] = L.init_norm(ks[2], cfg, cfg.d_model, abstract=abstract)
        if cfg.use_post_norm:
            p["post_norm1"] = L.init_norm(ks[4], cfg, cfg.d_model, abstract=abstract)
            p["post_norm2"] = L.init_norm(ks[4], cfg, cfg.d_model, abstract=abstract)
        p.update(_init_ffn(ks[5], cfg, abstract))
    elif ch == "M":
        p["mamba"] = M2.init_mamba(ks[1], cfg, abstract=abstract)
        if cfg.ssm_ffn:
            p["norm2"] = L.init_norm(ks[2], cfg, cfg.d_model, abstract=abstract)
            p.update(_init_ffn(ks[5], cfg, abstract))
    elif ch == "R":
        p["rglru"] = RG.init_rglru_block(ks[1], cfg, abstract=abstract)
        p["norm2"] = L.init_norm(ks[2], cfg, cfg.d_model, abstract=abstract)
        p["mlp"] = L.init_mlp(ks[5], cfg, abstract=abstract)
    else:
        raise ValueError(ch)
    return p


def _init_ffn(key, cfg: ModelConfig, abstract: bool) -> dict:
    if cfg.num_experts:
        return {"moe": MOE.init_moe(key, cfg, abstract=abstract)}
    return {"mlp": L.init_mlp(key, cfg, abstract=abstract)}


def _ffn(p, h, cfg: ModelConfig):
    """The layer's feed-forward block: (output, aux)."""
    if "moe" in p:
        return MOE.apply_moe(p["moe"], h, cfg)
    return L.apply_mlp(p["mlp"], h, cfg), {}


def _residual(x, y, cfg: ModelConfig):
    """x + residual_multiplier·y."""
    if cfg.residual_multiplier != 1.0:
        y = y * jnp.asarray(cfg.residual_multiplier, y.dtype)
    return x + y


def apply_sublayer(
    p,
    x,
    cfg: ModelConfig,
    ch: str,
    *,
    cache=None,
    cache_pos=None,
    positions=None,
    causal=True,
    enc_out=None,
    q_chunk=0,
    unroll_chunks=False,
):
    """Apply one layer. Returns (x, new_cache, aux)."""
    aux = {}
    new_cache: Any = None
    if ch in ("G", "L"):
        window = _char_window(cfg, ch)
        h = L.apply_norm(p["norm1"], x, cfg)
        attn_cache = cache.get("attn") if cache else None
        a, new_attn_cache = L.attention(
            p["attn"], h, cfg, window=window, positions=positions, cache=attn_cache,
            cache_pos=cache_pos, q_chunk=q_chunk, unroll_chunks=unroll_chunks,
            causal=causal,
        )
        if cfg.use_post_norm:
            a = L.apply_norm(p["post_norm1"], a, cfg)
        if cfg.parallel_block:
            # command-r: attn and mlp both read norm1 output, summed residual
            m = L.apply_mlp(p["mlp"], h, cfg) if "mlp" in p else None
            if m is None:
                m, aux = MOE.apply_moe(p["moe"], h, cfg)
            x = x + a + m
            new_cache = {"attn": new_attn_cache} if new_attn_cache is not None else None
            return x, new_cache, aux
        x = _residual(x, a, cfg)
        if "xattn" in p and enc_out is not None:
            hx = L.apply_norm(p["norm_x"], x, cfg)
            # the cross-KV cache is only valid for decode (q_len == 1);
            # prefill recomputes it from the encoder output and stores it
            cached_cross = cache.get("cross") if (cache and x.shape[1] == 1) else None
            xa, new_x_cache = _cross_attention(p["xattn"], hx, enc_out, cfg,
                                               cached_cross)
            x = x + xa
        else:
            new_x_cache = None
        h2 = L.apply_norm(p["norm2"], x, cfg)
        m, aux = _ffn(p, h2, cfg)
        if cfg.use_post_norm:
            m = L.apply_norm(p["post_norm2"], m, cfg)
        x = _residual(x, m, cfg)
        c = {}
        if new_attn_cache is not None:
            c["attn"] = new_attn_cache
        if new_x_cache is not None:
            c["cross"] = new_x_cache
        new_cache = c or None
    elif ch == "M":
        h = L.apply_norm(p["norm1"], x, cfg)
        m_cache = cache.get("ssm") if cache else None
        y, new_m = M2.apply_mamba(p["mamba"], h, cfg, cache=m_cache)
        x = _residual(x, y, cfg)
        if "norm2" in p:
            m, aux = _ffn(p, L.apply_norm(p["norm2"], x, cfg), cfg)
            x = _residual(x, m, cfg)
        new_cache = {"ssm": new_m} if new_m is not None else None
    elif ch == "R":
        h = L.apply_norm(p["norm1"], x, cfg)
        r_cache = cache.get("rec") if cache else None
        y, new_r = RG.apply_rglru_block(p["rglru"], h, cfg, cache=r_cache)
        x = x + y
        h2 = L.apply_norm(p["norm2"], x, cfg)
        x = x + L.apply_mlp(p["mlp"], h2, cfg)
        new_cache = {"rec": new_r} if new_r is not None else None
    else:
        raise ValueError(ch)
    return x, new_cache, aux


def _cross_attention(p, x, enc_out, cfg: ModelConfig, cached_kv):
    """Cross-attention: q from x, k/v from encoder output (or cache)."""
    B, S, D = x.shape
    H, Kv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    q = L.project(x, p["wq"]).reshape(B, S, H, hd)
    if cached_kv is not None:
        k, v = cached_kv
        k = k.astype(x.dtype)
        v = v.astype(x.dtype)
        new_kv = cached_kv
    else:
        k = L.project(enc_out, p["wk"]).reshape(B, -1, Kv, hd)
        v = L.project(enc_out, p["wv"]).reshape(B, -1, Kv, hd)
        new_kv = (k, v)
    out = L._attend_full(q, k, v, causal=False, window=0, softcap=0.0)
    return L.project(out, p["wo"]), new_kv


# ---------------------------------------------------------------------------
# Group (one copy of the pattern) — the scan body
# ---------------------------------------------------------------------------


def init_group(key, cfg: ModelConfig, abstract=False, cross_attn=False):
    chars = group_chars(cfg)
    ks = jax.random.split(key, len(chars)) if key is not None else [None] * len(chars)
    return {f"sub_{i}": init_sublayer(ks[i], cfg, ch, abstract=abstract, cross_attn=cross_attn)
            for i, ch in enumerate(chars)}


def apply_group(gp, x, cfg: ModelConfig, *, chars=None, subs=None, cache=None, cache_pos=None,
                positions=None, causal=True, enc_out=None, q_chunk=0, unroll_chunks=False):
    """One copy of the pattern, or of its sublayers ``subs`` (indices into
    it) alone.  Returns (x, new_cache, aux)."""
    chars = chars or group_chars(cfg)
    new_cache = {}
    auxes = []
    for i in (range(len(chars)) if subs is None else subs):
        sub_cache = cache.get(f"sub_{i}") if cache else None
        x, nc, aux = apply_sublayer(
            gp[f"sub_{i}"], x, cfg, chars[i], cache=sub_cache, cache_pos=cache_pos,
            positions=positions, causal=causal, enc_out=enc_out,
            q_chunk=q_chunk, unroll_chunks=unroll_chunks,
        )
        if nc is not None:
            new_cache[f"sub_{i}"] = nc
        auxes.append(aux)
    return x, (new_cache or None), _merge_aux(auxes)


def _merge_aux(auxes) -> dict:
    """Layers' aux in order: load-balancing losses summed, the held experts'
    loads stacked (layers, E_held)."""
    auxes = [a for a in auxes if a]
    if not auxes:
        return {}
    out = {"moe_aux_loss": functools.reduce(jnp.add, [a["moe_aux_loss"] for a in auxes])}
    loads = [a["moe_loads"] for a in auxes if "moe_loads" in a]
    if loads:
        out["moe_loads"] = jnp.concatenate(loads, axis=0)
    return out


def layer_view(groups, cfg: ModelConfig, start: int, stop: int):
    """The stacked group params (or adapters, keyed the same) of layers
    [start, stop) of the scanned stack: ``sub_i`` keeps the groups g with
    start <= g·p + i < stop, p the pattern's period.  A cut at a layer
    boundary inside a group leaves the first sublayers of that group with
    one entry more on the client's side and the rest on the server's."""
    p = len(cfg.layer_pattern)
    return {k: jax.tree.map(lambda a, i=int(k[4:]): a[-((i - start) // p): -((i - stop) // p)], v)
            for k, v in groups.items()}


def n_layers(groups) -> int:
    """The layers a stack of groups, or a ``layer_view`` of one, holds."""
    return sum(jax.tree.leaves(v)[0].shape[0] for v in groups.values())


# ---------------------------------------------------------------------------
# Full model params
# ---------------------------------------------------------------------------


def init_params(cfg: ModelConfig, key=None, abstract=False):
    """Returns (values_tree, axes_tree). With abstract=True, leaves are
    ShapeDtypeStructs (no allocation — used by the dry-run)."""
    if key is None and not abstract:
        key = jax.random.PRNGKey(0)
    nk = 8
    ks = jax.random.split(key, nk) if key is not None else [None] * nk

    tree: dict[str, Any] = {"embed": L.init_embed(ks[0], cfg, abstract=abstract)}
    ng = n_full_groups(cfg)
    cross = cfg.family == "encdec"

    # stacked groups
    if abstract:
        one = init_group(None, cfg, abstract=True, cross_attn=cross)
        stacked = jax.tree.map(
            lambda p: ParamLeaf(jax.ShapeDtypeStruct((ng,) + p.value.shape, p.value.dtype),
                                ("layers",) + p.axes),
            one, is_leaf=lambda t: isinstance(t, ParamLeaf))
    else:
        gkeys = jax.random.split(ks[1], ng)

        def mk(k):
            return split_param_tree(init_group(k, cfg, cross_attn=cross))[0]

        vals = jax.vmap(mk)(gkeys)
        axes = split_param_tree(init_group(jax.random.PRNGKey(0), cfg, cross_attn=cross))[1]
        stacked = jax.tree.map(lambda v, a: ParamLeaf(v, ("layers",) + a), vals, axes,
                               is_leaf=lambda t: isinstance(t, tuple) and not isinstance(t, ParamLeaf) and all(isinstance(e, (str, type(None))) for e in t))
    tree["groups"] = stacked

    # unscanned tail layers
    tchars = tail_chars(cfg)
    if tchars:
        tkeys = jax.random.split(ks[2], len(tchars)) if not abstract else [None] * len(tchars)
        for i, ch in enumerate(tchars):
            tree[f"tail_{i}"] = init_sublayer(tkeys[i], cfg, ch, abstract=abstract, cross_attn=cross)

    tree["final_norm"] = L.init_norm(ks[3], cfg, cfg.d_model, abstract=abstract)

    if cfg.family == "encdec":
        eng = cfg.num_encoder_layers
        if abstract:
            eone = init_group(None, cfg.replace(layer_pattern="G"), abstract=True)
            tree["enc_groups"] = jax.tree.map(
                lambda p: ParamLeaf(jax.ShapeDtypeStruct((eng,) + p.value.shape, p.value.dtype),
                                    ("layers",) + p.axes),
                eone, is_leaf=lambda t: isinstance(t, ParamLeaf))
        else:
            ekeys = jax.random.split(ks[4], eng)

            def mke(k):
                return split_param_tree(init_group(k, cfg.replace(layer_pattern="G")))[0]

            evals = jax.vmap(mke)(ekeys)
            eaxes = split_param_tree(init_group(jax.random.PRNGKey(0), cfg.replace(layer_pattern="G")))[1]
            tree["enc_groups"] = jax.tree.map(lambda v, a: ParamLeaf(v, ("layers",) + a), evals, eaxes,
                                              is_leaf=lambda t: isinstance(t, tuple) and not isinstance(t, ParamLeaf) and all(isinstance(e, (str, type(None))) for e in t))
        tree["enc_final_norm"] = L.init_norm(ks[5], cfg, cfg.d_model, abstract=abstract)
        # learned positional embeddings (whisper style)
        tree["enc_pos"] = make_param(ks[5], (cfg.encoder_seq, cfg.d_model), (None, "embed"),
                                     cfg.param_dtype, abstract=abstract)
        tree["dec_pos"] = make_param(ks[6], (32768, cfg.d_model), (None, "embed"),
                                     cfg.param_dtype, abstract=abstract)

    if cfg.family == "vlm":
        vd = 1024  # vision encoder width (CLIP-L); frontend itself is a stub
        tree["projector"] = {
            "w1": make_param(ks[4], (vd, cfg.d_model), (None, "embed"), cfg.param_dtype, abstract=abstract),
            "b1": make_param(ks[4], (cfg.d_model,), ("embed",), cfg.param_dtype, init="zeros", abstract=abstract),
            "w2": make_param(ks[5], (cfg.d_model, cfg.d_model), ("embed", "embed"), cfg.param_dtype, abstract=abstract),
            "b2": make_param(ks[5], (cfg.d_model,), ("embed",), cfg.param_dtype, init="zeros", abstract=abstract),
        }

    return split_param_tree(tree)


# ---------------------------------------------------------------------------
# Forward passes
# ---------------------------------------------------------------------------


def _embed_inputs(params, batch, cfg: ModelConfig):
    """Token (+modality-stub) embedding. Returns (x, positions)."""
    tokens = batch["tokens"]
    x = L.embed_tokens(params["embed"], tokens, cfg)
    if cfg.family == "vlm" and "vision_embeds" in batch:
        pj = params["projector"]
        v = batch["vision_embeds"].astype(cfg.dtype)
        v = jax.nn.gelu(v @ pj["w1"].astype(v.dtype) + pj["b1"], approximate=True)
        v = v @ pj["w2"].astype(v.dtype) + pj["b2"]
        v = shard(v, ("batch", "seq", "embed"))
        x = jnp.concatenate([v, x], axis=1)
    S = x.shape[1]
    positions = jnp.arange(S)[None, :]
    if cfg.family == "encdec":
        x = x + params["dec_pos"][None, :S].astype(x.dtype)
    return x, positions


def _run_encoder(params, batch, cfg: ModelConfig):
    frames = batch["frame_embeds"].astype(cfg.dtype)  # stub: precomputed
    Senc = frames.shape[1]
    x = frames + params["enc_pos"][None, :Senc].astype(frames.dtype)
    ecfg = cfg.replace(layer_pattern="G", use_rope=False)

    def body(h, gp):
        h, _, _ = apply_group(gp, h, ecfg, chars="G", causal=False)
        return h, None

    x, _ = jax.lax.scan(body, x, params["enc_groups"])
    return L.apply_norm(params["enc_final_norm"], x, cfg)


def _scan_groups(params, x, cfg: ModelConfig, *, cache=None, cache_pos=None,
                 positions=None, enc_out=None, q_chunk=0, remat=False,
                 groups_slice=None, include_tail=True, unroll=False, start=0):
    """Run the scanned group stack (+ tail). cache is threaded through scan.
    Without a cache ``params["groups"]`` may be a ``layer_view`` whose first
    layer is ``start``.  Returns (x, new_cache, aux): aux's ``moe_aux_loss``
    summed over the layers, and for expert layers ``moe_loads`` (layers,
    E_held)."""
    gparams = params["groups"] if groups_slice is None else groups_slice
    kw = dict(cache_pos=cache_pos, positions=positions, enc_out=enc_out, q_chunk=q_chunk)

    if cache is None:
        # ``gparams`` may be a ``layer_view`` of layers [start, stop): a group
        # entered past its start runs its last sublayers first (lead), one
        # left before its end its first sublayers last (trail)
        auxes = []
        p = len(gparams)
        stop = start + n_layers(gparams)
        g0 = -(-start // p) * p  # the first group boundary at or after start
        lead = [i % p for i in range(start, min(g0, stop))]
        if lead:
            x, _, aux = apply_group(_entry(gparams, lead, 0), x, cfg, subs=lead, **kw)
            auxes.append(aux)
            gparams = {k: jax.tree.map(lambda a: a[1:], v) if int(k[4:]) in lead else v
                       for k, v in gparams.items()}
        full = max(stop - g0, 0) // p
        trail = [i % p for i in range(max(g0 + full * p, start), stop)]

        def body(carry, gp):
            h = carry
            h, _, aux = apply_group(gp, h, cfg, cache=None, **kw)
            return h, aux or {"moe_aux_loss": jnp.zeros((), jnp.float32)}

        if remat:
            body = jax.checkpoint(body, policy=jax.checkpoint_policies.nothing_saveable)
        if full:
            scanned = gparams if not trail else {
                k: jax.tree.map(lambda a: a[:full], v) for k, v in gparams.items()}
            x, aux_stack = jax.lax.scan(body, x, scanned, unroll=unroll)
            aux = {"moe_aux_loss": jnp.sum(aux_stack["moe_aux_loss"])}
            if "moe_loads" in aux_stack:
                loads = aux_stack["moe_loads"]
                aux["moe_loads"] = loads.reshape(-1, loads.shape[-1])
            auxes.append(aux)
        if trail:
            x, _, aux = apply_group(_entry(gparams, trail, full), x, cfg, subs=trail, **kw)
            auxes.append(aux)
        new_cache = None
    else:
        # Cache rides in the scan CARRY as one stacked buffer updated with
        # dynamic_update_index_in_dim — threading it through xs/ys made XLA
        # materialise a full cache copy per step (§Perf iter: decode temp
        # bytes 151 GB vs the 21.5 GB cache on command-r decode_32k).
        ng = jax.tree.leaves(gparams)[0].shape[0]

        def body(carry, xs):
            h, cache_all = carry
            gp, i = xs
            gc = jax.tree.map(lambda a: jax.lax.dynamic_index_in_dim(a, i, 0, keepdims=False),
                              cache_all)
            h, new_c, aux = apply_group(gp, h, cfg, cache=gc, cache_pos=cache_pos,
                                        positions=positions, enc_out=enc_out,
                                        q_chunk=q_chunk)
            cache_all = jax.tree.map(
                lambda a, u: jax.lax.dynamic_update_index_in_dim(a, u.astype(a.dtype), i, 0),
                cache_all, new_c)
            return (h, cache_all), aux.get("moe_aux_loss", jnp.zeros((), jnp.float32))

        (x, new_group_cache), aux_stack = jax.lax.scan(
            body, (x, cache["groups"]), (gparams, jnp.arange(ng)), unroll=unroll)
        auxes = [{"moe_aux_loss": jnp.sum(aux_stack)}]
        new_cache = {"groups": new_group_cache}
    # tail layers (unscanned)
    tchars = tail_chars(cfg) if include_tail else ""
    for i, ch in enumerate(tchars):
        tc = cache.get(f"tail_{i}") if cache else None
        x, nc, aux = apply_sublayer(params[f"tail_{i}"], x, cfg, ch, cache=tc,
                                    cache_pos=cache_pos, positions=positions,
                                    enc_out=enc_out, q_chunk=q_chunk)
        if cache is not None:
            new_cache[f"tail_{i}"] = nc
        auxes.append(aux)
    return x, new_cache, _merge_aux(auxes) or {"moe_aux_loss": jnp.zeros((), jnp.float32)}


def _entry(groups, subs, j: int) -> dict:
    """Entry ``j`` of the stacks of sublayers ``subs``: one (partial) group."""
    return {f"sub_{i}": jax.tree.map(lambda a: a[j], groups[f"sub_{i}"]) for i in subs}


def forward(params, batch, cfg: ModelConfig, *, kind: str = "train",
            q_chunk: int = 0, remat: bool = False, unroll: bool = False):
    """Full forward -> logits (B, S, V). kind: train|prefill."""
    enc_out = _run_encoder(params, batch, cfg) if cfg.family == "encdec" else None
    x, positions = _embed_inputs(params, batch, cfg)
    if q_chunk == 0 and x.shape[1] >= 16384:
        q_chunk = 2048
    x, _, aux = _scan_groups(params, x, cfg, positions=positions, enc_out=enc_out,
                             q_chunk=q_chunk, remat=remat, unroll=unroll)
    x = L.apply_norm(params["final_norm"], x, cfg)
    logits = L.lm_logits(params["embed"], x, cfg)
    return logits, aux["moe_aux_loss"]


def hidden_states(params, batch, cfg: ModelConfig, *, q_chunk: int = 0,
                  remat: bool = False, unroll: bool = False):
    """Forward up to the final norm (pre-logits). Returns (x, aux)."""
    enc_out = _run_encoder(params, batch, cfg) if cfg.family == "encdec" else None
    x, positions = _embed_inputs(params, batch, cfg)
    if q_chunk == 0 and x.shape[1] >= 16384:
        q_chunk = 2048
    x, _, aux = _scan_groups(params, x, cfg, positions=positions, enc_out=enc_out,
                             q_chunk=q_chunk, remat=remat, unroll=unroll)
    return L.apply_norm(params["final_norm"], x, cfg), aux["moe_aux_loss"]


def loss_fn(params, batch, cfg: ModelConfig, *, remat: bool = False, aux_weight=0.01,
            unroll: bool = False):
    """Training loss with sequence-chunked CE (the full (B,S,V) fp32 logits
    tensor never materialises — §Perf iter 5)."""
    x, aux = hidden_states(params, batch, cfg, remat=remat, unroll=unroll)
    loss = L.fused_cross_entropy(params["embed"], x, batch["labels"], cfg,
                                 mask=batch.get("mask"), unroll=unroll)
    return loss + aux_weight * aux, {"ce_loss": loss, "moe_aux": aux}


# ---------------------------------------------------------------------------
# KV / state cache
# ---------------------------------------------------------------------------


def _sublayer_cache(cfg: ModelConfig, ch: str, batch: int, max_seq: int, dtype,
                    cross: bool = False):
    if ch in ("G", "L"):
        window = _char_window(cfg, ch)
        S_c = min(window, max_seq) if window else max_seq
        kv = {
            "attn": (
                jnp.zeros((batch, S_c, cfg.num_kv_heads, cfg.head_dim), dtype),
                jnp.zeros((batch, S_c, cfg.num_kv_heads, cfg.head_dim), dtype),
            )
        }
        if cross:
            kv["cross"] = (
                jnp.zeros((batch, cfg.encoder_seq, cfg.num_kv_heads, cfg.head_dim), dtype),
                jnp.zeros((batch, cfg.encoder_seq, cfg.num_kv_heads, cfg.head_dim), dtype),
            )
        return kv
    if ch == "M":
        return {"ssm": M2.init_mamba_cache(cfg, batch, dtype)}
    if ch == "R":
        return {"rec": RG.init_rglru_cache(cfg, batch, dtype)}
    raise ValueError(ch)


def init_cache(cfg: ModelConfig, batch: int, max_seq: int, dtype=None):
    dtype = dtype or jnp.dtype(cfg.dtype)
    cross = cfg.family == "encdec"
    ng = n_full_groups(cfg)
    one = {f"sub_{i}": _sublayer_cache(cfg, ch, batch, max_seq, dtype, cross)
           for i, ch in enumerate(group_chars(cfg))}
    groups = jax.tree.map(lambda a: jnp.broadcast_to(a, (ng,) + a.shape), one)
    cache = {"groups": groups}
    for i, ch in enumerate(tail_chars(cfg)):
        cache[f"tail_{i}"] = _sublayer_cache(cfg, ch, batch, max_seq, dtype, cross)
    return cache


def cache_axes(cache):
    """Logical sharding axes for a cache tree (matched by rank)."""

    def one(a):
        if a.ndim == 5:  # (layers, B, S, Kv, hd)
            return ("layers", "batch", "kv_seq", "kv_heads", None)
        if a.ndim == 4:  # stacked conv/ssd states
            return ("layers", "batch", None, None)
        if a.ndim == 3:
            return ("layers", "batch", None)
        if a.ndim == 2:
            return ("batch", None)
        return tuple([None] * a.ndim)

    return jax.tree.map(one, cache)


# ---------------------------------------------------------------------------
# Decode
# ---------------------------------------------------------------------------


def decode_step(params, tokens, cache, cache_pos, cfg: ModelConfig, enc_out=None,
                unroll: bool = False):
    """One-token decode. tokens: (B, 1). Returns (logits (B,1,V), new_cache)."""
    x = L.embed_tokens(params["embed"], tokens, cfg)
    if cfg.family == "encdec":
        x = x + jax.lax.dynamic_slice_in_dim(params["dec_pos"], cache_pos, 1, axis=0)[None].astype(x.dtype)
    positions = jnp.full((tokens.shape[0], 1), cache_pos, dtype=jnp.int32)
    x, new_cache, _ = _scan_groups(params, x, cfg, cache=cache, cache_pos=cache_pos,
                                   positions=positions, enc_out=enc_out, unroll=unroll)
    x = L.apply_norm(params["final_norm"], x, cfg)
    logits = L.lm_logits(params["embed"], x, cfg)
    return logits, new_cache


def prefill(params, batch, cfg: ModelConfig, cache, unroll: bool = False):
    """Prefill: run full sequence, writing the cache. Returns (logits, cache)."""
    enc_out = _run_encoder(params, batch, cfg) if cfg.family == "encdec" else None
    x, positions = _embed_inputs(params, batch, cfg)
    q_chunk = 2048 if x.shape[1] >= 16384 else 0
    x, new_cache, _ = _scan_groups(params, x, cfg, cache=cache, cache_pos=jnp.array(0, jnp.int32),
                                   positions=positions, enc_out=enc_out, q_chunk=q_chunk,
                                   unroll=unroll)
    x = L.apply_norm(params["final_norm"], x, cfg)
    logits = L.lm_logits(params["embed"], x, cfg)
    return logits, new_cache
