"""Mixture-of-Experts FFN: top-k routing over every expert of the layer,
its held experts' part of the result, and an optional shared expert.

An expert layer holds ``cfg.num_experts`` experts, [expert_offset,
expert_offset + num_experts) of the router's ``cfg.num_router_experts``:
one chip's share of an expert-parallel layer, or all of them.  The router
scores all experts in float32 and keeps the top k; the gates are the softmax
of the chosen logits (``moe_router_norm``).  Pairs routed to experts held
elsewhere add nothing here; a shared expert, computed alike on every share,
is added once.  Scopes: ``model.moe`` around the whole FFN, inside it
``model.moe.router``, ``model.moe.experts`` (dispatch, grouped products,
combine) and ``model.moe.shared``.

Without a mesh (every training path here) dispatch is dropless: the
(token, expert) pairs are sorted by held expert and each expert's rows go
through one grouped product (``layers.grouped_dot``), however unevenly the
clients' data routes them.

Under a mesh the experts are sharded over the ``model`` axis with a static
capacity (design of perf iterations 1-4, EXPERIMENTS.md §Perf):

  * routing/top-k on (B,S,E) logits under GSPMD (small);
  * rank-within-expert via **argsort** — every intermediate is a (b, S·k)
    int array (the one-hot/cumsum formulation materialises (b, S·k, E):
    TBs at qwen3 scale);
  * dispatch + combine run inside **shard_map over the full (data, model)
    mesh**: each (data, model) shard scatters only the tokens routed to its
    LOCAL experts (token activations are replicated over ``model`` inside a
    data shard, so dispatch needs *zero* forward communication), the expert
    buffers emerge already (batch→data, expert→model)-sharded for the expert
    einsums, and the combine produces per-model-shard partial outputs that a
    single (b,S,D) ``psum`` over ``model`` reduces — the canonical
    expert-parallel pattern with one small collective per layer.

  History (measured on qwen3-235b train_4k, per-device roofline terms):
    v0 global flat scatter     : GSPMD replicates; 543s compute / 601s coll
    v1 batched scatter         : 5.9s compute but 137GB/layer all-reduces
    v3 shard_map(data) dispatch: 5.5s / 115s mem / 125s coll (E all-gathers)
    v4 this file               : see EXPERIMENTS.md §Perf

There, overflow beyond an expert's per-row capacity C = ceil(cf·S·k/E) is
dropped (GShard/Switch semantics, cf = 1.25): static per-shard buffers are
what expert-parallel dispatch over the mesh needs.  Where nothing overflows
the two paths agree (tests/test_moe.py).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.config import ModelConfig
from repro.models.layers import apply_mlp, project
from repro.parallel import make_param, shard
from repro.parallel.sharding import active_context, spec_for

CAPACITY_FACTOR = 1.25


def init_moe(key, cfg: ModelConfig, abstract=False):
    D, F, E = cfg.d_model, cfg.d_ff, cfg.num_experts
    ks = jax.random.split(key, 5) if key is not None else [None] * 5
    p = {
        "router": make_param(ks[0], (D, cfg.num_router_experts), ("embed", None), "float32",
                             abstract=abstract),
        "w_gate": make_param(ks[1], (E, D, F), ("experts", "expert_embed", "mlp"), cfg.param_dtype, abstract=abstract),
        "w_up": make_param(ks[2], (E, D, F), ("experts", "expert_embed", "mlp"), cfg.param_dtype, abstract=abstract),
        "w_down": make_param(ks[3], (E, F, D), ("experts", "mlp", "expert_embed"), cfg.param_dtype,
                             scale=0.02 / math.sqrt(2 * cfg.num_layers), abstract=abstract),
    }
    if cfg.shared_d_ff:
        p["shared"] = init_shared(ks[4], cfg, abstract=abstract)
    return p


def init_shared(key, cfg: ModelConfig, abstract=False):
    D, F = cfg.d_model, cfg.shared_d_ff
    ks = jax.random.split(key, 3) if key is not None else [None] * 3
    return {
        "w_gate": make_param(ks[0], (D, F), ("embed", "mlp"), cfg.param_dtype, abstract=abstract),
        "w_up": make_param(ks[1], (D, F), ("embed", "mlp"), cfg.param_dtype, abstract=abstract),
        "w_down": make_param(ks[2], (F, D), ("mlp", "embed"), cfg.param_dtype,
                             scale=0.02 / math.sqrt(2 * cfg.num_layers), abstract=abstract),
    }


def _capacity(seq_tokens: int, E: int, k: int) -> int:
    """Per-batch-row expert capacity of the mesh path."""
    c = int(math.ceil(CAPACITY_FACTOR * seq_tokens * k / E))
    c = max(c, min(seq_tokens * k, 8))
    return ((c + 7) // 8) * 8


# ---------------------------------------------------------------------------
# Per-shard dispatch / combine of the mesh path
# ---------------------------------------------------------------------------


def _rank_and_dest(top_e, E: int, C: int, k: int):
    """Argsort-based rank within expert. top_e: (b, S, k) -> dest/keep (b, Sk)."""
    b, S, _ = top_e.shape
    Sk = S * k
    flat_e = top_e.reshape(b, Sk)
    order = jnp.argsort(flat_e, axis=1, stable=True)  # groups equal experts
    se = jnp.take_along_axis(flat_e, order, axis=1)
    idx = jnp.broadcast_to(jnp.arange(Sk)[None, :], (b, Sk))
    newseg = jnp.concatenate(
        [jnp.ones((b, 1), bool), se[:, 1:] != se[:, :-1]], axis=1)
    seg_start = jax.lax.cummax(jnp.where(newseg, idx, 0), axis=1)
    rank_sorted = idx - seg_start
    inv_order = jnp.argsort(order, axis=1)
    rank = jnp.take_along_axis(rank_sorted, inv_order, axis=1)  # (b, Sk)
    keep = rank < C
    dest = jnp.where(keep, flat_e * C + rank, E * C)  # E*C = global drop slot
    return dest, keep


def _dispatch_local(x, dest, keep, *, E_local: int, C: int, k: int, e_offset):
    """Scatter the local shard's tokens into its local expert buffers.

    x: (b, S, D); dest/keep: (b, S·k) with *global* slot ids.  Only slots
    belonging to experts [e_offset, e_offset + E_local) are kept."""
    b, S, D = x.shape
    Sk = S * k
    local_dest = dest - e_offset * C
    valid = keep & (local_dest >= 0) & (local_dest < E_local * C)
    local_dest = jnp.where(valid, local_dest, E_local * C)  # drop slot
    src_token = jnp.arange(Sk) // k
    xsrc = jnp.take_along_axis(
        x, jnp.broadcast_to(src_token[None, :, None], (b, Sk, 1)), axis=1)
    buf = jnp.zeros((b, E_local * C + 1, D), dtype=x.dtype)
    bidx = jnp.broadcast_to(jnp.arange(b)[:, None], (b, Sk))
    buf = buf.at[bidx, local_dest].set(xsrc, mode="drop")
    return buf[:, : E_local * C].reshape(b, E_local, C, D)


def _combine_local(ye, dest, keep, w_flat, *, S: int, k: int, e_offset):
    """Gather this shard's expert outputs back to its tokens (partial sum —
    tokens whose (token, slot) lives on another expert shard contribute 0
    here and are completed by the psum over ``model``)."""
    b, E_local, C, D = ye.shape
    local_dest = dest - e_offset * C
    valid = keep & (local_dest >= 0) & (local_dest < E_local * C)
    safe = jnp.where(valid, local_dest, E_local * C)
    yflat = jnp.concatenate([ye.reshape(b, E_local * C, D),
                             jnp.zeros((b, 1, D), ye.dtype)], axis=1)
    contrib = jnp.take_along_axis(yflat, safe[..., None], axis=1)  # (b,Sk,D)
    w = (w_flat * valid).astype(ye.dtype)
    return jnp.sum((contrib * w[..., None]).reshape(b, S, k, D), axis=2)


# ---------------------------------------------------------------------------
# Public apply
# ---------------------------------------------------------------------------


def apply_moe(p, x, cfg: ModelConfig):
    """x: (B, S, D) -> (B, S, D), plus aux: the load-balancing loss over the
    whole router (``moe_aux_loss``) and the (token, expert) pairs each held
    expert received (``moe_loads``, (1, E_held))."""
    with jax.named_scope("model.moe"):
        with jax.named_scope("model.moe.router"):
            top_e, w_flat, aux_loss = _route(p["router"], x, cfg)
            local = top_e - cfg.expert_offset  # held experts are 0..E_held-1
            E = cfg.num_experts
            held = (local >= 0) & (local < E)
            loads = jnp.zeros((E,), jnp.float32).at[jnp.where(held, local, E)].add(
                1.0, mode="drop")
        with jax.named_scope("model.moe.experts"):
            y = _held_experts(p, x, local, w_flat, cfg)
        if "shared" in p:
            with jax.named_scope("model.moe.shared"):
                y = y + apply_mlp(p["shared"], x, cfg)
    return y, {"moe_aux_loss": aux_loss, "moe_loads": loads[None]}


def _route(router, x, cfg: ModelConfig):
    """(top-k expert ids (B, S, k), their gates (B, S·k) in x's dtype, the
    Switch-style load-balancing loss), all over the router's every expert."""
    B, S, _ = x.shape
    E, k = cfg.num_router_experts, cfg.num_experts_per_tok
    # fp32 logits; softmax over the selected k (qwen3/mixtral/granite
    # norm_topk semantics)
    logits = jnp.einsum("bsd,de->bse", x.astype(jnp.float32), router.astype(jnp.float32))
    top_l, top_e = jax.lax.top_k(logits, k)  # (B, S, k)
    if cfg.moe_router_norm:
        top_w = jax.nn.softmax(top_l, axis=-1)
    else:
        top_w = jnp.take_along_axis(jax.nn.softmax(logits, axis=-1), top_e, axis=-1)
    # load-balancing auxiliary loss (Switch-style, no (…,E) one-hots)
    me = jnp.mean(jax.nn.softmax(logits, axis=-1), axis=(0, 1))  # (E,)
    bidx_e = jnp.broadcast_to(jnp.arange(B)[:, None], (B, S * k))
    counts = jnp.zeros((B, E), jnp.float32).at[bidx_e, top_e.reshape(B, S * k)].add(1.0)
    ce = jnp.sum(counts, axis=0) / (B * S * k)
    aux_loss = E * jnp.sum(me * ce)
    return top_e, top_w.reshape(B, S * k).astype(x.dtype), aux_loss


def _held_experts(p, x, local, w_flat, cfg: ModelConfig):
    """The held experts' part of the FFN for every token: Σ gate·expert(x)
    over the token's pairs routed to a held expert (ids ``local`` in
    [0, E_held); others add nothing)."""
    B, S, D = x.shape
    E, k = cfg.num_experts, cfg.num_experts_per_tok
    mesh, rules = active_context()
    baxes, maxes = _mesh_axes(B, mesh, rules)
    if mesh is None or (baxes is None and maxes is None):
        # the dropless buffers hold T·min(k, E) rows, most of them empty
        # under even routing: recomputed in the backward pass rather than
        # kept, which costs a few gathers and the held pairs' products
        return jax.checkpoint(_dropless, static_argnums=(4, 5))(p, x, local, w_flat, E, k)
    return _sharded_capacity(p, x, local, w_flat, E, k, mesh, baxes, maxes)


def _dropless(p, x, local, w_flat, E: int, k: int):
    """Every pair routed to a held expert, none dropped: the pairs sorted by
    expert, one grouped product per projection, a scatter-add back to the
    tokens in float32.  A token holds at most min(k, E) held pairs, so the
    first T·min(k, E) sorted pairs hold them all."""
    B, S, D = x.shape
    T = B * S
    flat = local.reshape(T * k)
    key = jnp.where((flat >= 0) & (flat < E), flat, E)  # not held: sorted last
    order = jnp.argsort(key, stable=True)[: T * min(k, E)]
    tok = order // k
    sizes = jnp.zeros((E,), jnp.int32).at[key].add(1, mode="drop")
    xs = x.reshape(T, D)[tok]  # rows past the held pairs come out of each product as 0
    h = jax.nn.silu(project(xs, p["w_gate"], group_sizes=sizes))
    h = h * project(xs, p["w_up"], group_sizes=sizes)
    ys = project(h, p["w_down"], group_sizes=sizes).astype(jnp.float32)
    gate = w_flat.reshape(T * k)[order].astype(jnp.float32)
    y = jnp.zeros((T, D), jnp.float32).at[tok].add(ys * gate[:, None])
    return y.reshape(B, S, D).astype(x.dtype)


def _sharded_capacity(p, x, local, w_flat, E: int, k: int, mesh, baxes, maxes):
    """Expert-parallel over the ``model`` mesh axis with a static capacity."""
    B, S, D = x.shape
    C = _capacity(S, E, k)
    n_model = 1
    if maxes:
        for a in maxes:
            n_model *= dict(mesh.shape)[a]
    if E % n_model:
        maxes, n_model = None, 1  # awkward expert count: replicate experts
    E_local = E // n_model
    bspec = baxes if baxes is not None else None

    def sharded_moe(x_l, top_e_l, w_flat_l, w_gate, w_up, w_down):
        # runs per (data, model) shard: x_l (b_loc, S, D) replicated over model
        if maxes:
            e_idx = jax.lax.axis_index(maxes[0])
            for a in maxes[1:]:
                e_idx = e_idx * dict(mesh.shape)[a] + jax.lax.axis_index(a)
        else:
            e_idx = 0
        e_off = e_idx * E_local
        dest, keep = _rank_and_dest(top_e_l, E, C, k)
        xe = _dispatch_local(x_l, dest, keep, E_local=E_local, C=C, k=k,
                             e_offset=e_off)
        ye = _expert_ffn({"w_gate": w_gate, "w_up": w_up, "w_down": w_down}, xe)
        y = _combine_local(ye, dest, keep, w_flat_l, S=S, k=k, e_offset=e_off)
        if maxes:
            y = jax.lax.psum(y, maxes)
        return y

    # expert weights (or Adapted ones: w, a and b all lead with the expert
    # dim) enter sharded over (experts->model); other dims gathered
    wspec = P(maxes if maxes else None)
    return jax.shard_map(
        sharded_moe, mesh=mesh,
        in_specs=(P(bspec), P(bspec), P(bspec), wspec, wspec, wspec),
        out_specs=P(bspec),
        check_vma=False,
    )(x, local, w_flat, p["w_gate"], p["w_up"], p["w_down"])


def _expert_ffn(p, xe):
    """(b, E_l, C, D) -> (b, E_l, C, D) SwiGLU expert FFN (local shapes)."""
    h = jax.nn.silu(project(xe, p["w_gate"], "becd,edf->becf"))
    h = h * project(xe, p["w_up"], "becd,edf->becf")
    return project(h, p["w_down"], "becf,efd->becd")


def _mesh_axes(B: int, mesh, rules):
    """(batch mesh axes, model/expert mesh axes) honoring divisibility."""
    if mesh is None or rules is None:
        return None, None
    bspec = spec_for((B,), ("batch",), rules, mesh)
    baxes = bspec[0] if len(bspec) else None
    sizes = dict(mesh.shape)
    maxes = tuple(a for a in rules.get("experts", ()) if a in sizes)
    return baxes, (maxes if maxes else None)
