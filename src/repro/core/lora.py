"""LoRA (paper eq. (1)): w0 + Δw = w0 + B·A, with B ∈ R^{d×r}, A ∈ R^{r×k},
r << min(d, k).

The *frozen* base params stay untouched; the trainable tree mirrors the base
tree at the targeted projection leaves with {"A": (..., d_in, r),
"B": (..., r, d_out)} factor pairs (leading stacked-layer / expert dims are
preserved, so one declaration covers dense, scanned and MoE weights).

Training applies adapters unmerged: ``attach`` wraps each targeted leaf W
with its pair as ``layers.Adapted``, and every projection of the models
computes y = x·W + (α/r)·(x·A)·B through ``layers.project``.  W stays the
frozen leaf, shared by a vmapped cohort, and autodiff never forms a
W-sized gradient: dA, dB and dx come from r-wide products.

``merge`` (W' = W + (α/r)·A@B, rounded to W's dtype) is for export, where
one adapter set serves many forward-only calls, and is the merged-graph
oracle that ``split.monolithic_value_and_grad`` checks the split step against.
"""

from __future__ import annotations

from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro.config import LoRAConfig, ModelConfig
from repro.models.layers import Adapted
from repro.parallel import ParamLeaf


def _leaf_name(path) -> str:
    last = path[-1]
    return getattr(last, "key", str(last))


def is_target(path, leaf, lcfg: LoRAConfig) -> bool:
    shape = leaf.shape if hasattr(leaf, "shape") else ()
    return _leaf_name(path) in lcfg.targets and len(shape) >= 2


def init_lora(params, axes, cfg: ModelConfig, key=None, abstract: bool = False):
    """Build (lora_params, lora_axes) mirroring targeted leaves of ``params``."""
    lcfg = cfg.lora or LoRAConfig()
    flat, treedef = jax.tree_util.tree_flatten_with_path(params)
    flat_axes = jax.tree_util.tree_flatten_with_path(
        axes, is_leaf=lambda t: isinstance(t, tuple) and all(isinstance(e, (str, type(None))) for e in t)
    )[0]
    axes_by_path = {jax.tree_util.keystr(p): a for p, a in flat_axes}
    if key is None and not abstract:
        key = jax.random.PRNGKey(1)

    out_vals: dict[str, Any] = {}
    out_axes: dict[str, Any] = {}
    i = 0
    for path, leaf in flat:
        if not is_target(path, leaf, lcfg):
            continue
        pstr = jax.tree_util.keystr(path)
        w_axes = axes_by_path.get(pstr, tuple([None] * len(leaf.shape)))
        lead = tuple(leaf.shape[:-2])
        d_in, d_out = leaf.shape[-2], leaf.shape[-1]
        r = lcfg.rank
        a_shape = lead + (d_in, r)
        b_shape = lead + (r, d_out)
        a_axes = tuple(w_axes[:-1]) + (None,)
        b_axes = tuple(w_axes[:-2]) + (None, w_axes[-1])
        if abstract:
            A = jax.ShapeDtypeStruct(a_shape, jnp.dtype(cfg.param_dtype))
            B = jax.ShapeDtypeStruct(b_shape, jnp.dtype(cfg.param_dtype))
        else:
            key, sub = jax.random.split(key)
            A = (jax.random.normal(sub, a_shape, jnp.float32) / r).astype(cfg.param_dtype)
            B = jnp.zeros(b_shape, cfg.param_dtype)  # Δw = 0 at init
        out_vals[pstr] = {"A": A, "B": B}
        out_axes[pstr] = {"A": a_axes, "B": b_axes}
        i += 1
    return out_vals, out_axes


def attach(params, lora_params, cfg: ModelConfig):
    """Every targeted leaf W becomes ``Adapted(W, A, B, α/r)``, applied
    unmerged by ``layers.project``; other leaves pass through."""
    scale = (cfg.lora or LoRAConfig()).scale

    def one(path, leaf):
        ab = lora_params.get(jax.tree_util.keystr(path))
        return leaf if ab is None else Adapted(leaf, ab["A"], ab["B"], scale)

    return jax.tree_util.tree_map_with_path(one, params)


def merge(params, lora_params, cfg: ModelConfig):
    """W' = W + (α/r)·A@B at every targeted leaf; other leaves pass through.
    Traced under the scope ``lora.merge``."""
    lcfg = cfg.lora or LoRAConfig()
    scale = lcfg.scale
    flat, treedef = jax.tree_util.tree_flatten_with_path(params)
    merged = []
    with jax.named_scope("lora.merge"):
        for path, leaf in flat:
            pstr = jax.tree_util.keystr(path)
            if pstr in lora_params:
                ab = lora_params[pstr]
                delta = jnp.einsum("...ir,...ro->...io", ab["A"].astype(jnp.float32),
                                   ab["B"].astype(jnp.float32)) * scale
                merged.append((leaf.astype(jnp.float32) + delta).astype(leaf.dtype))
            else:
                merged.append(leaf)
    return jax.tree_util.tree_unflatten(treedef, merged)


def delta_norm(lora_params) -> jax.Array:
    """||Δw||² across all adapters (diagnostics / convergence tracking)."""
    sq = [jnp.sum(jnp.square(v["A"].astype(jnp.float32))) + jnp.sum(jnp.square(v["B"].astype(jnp.float32)))
          for v in lora_params.values()]
    return jnp.sqrt(sum(sq))


def lora_param_count(cfg: ModelConfig) -> int:
    """Analytic adapter parameter count (used by the delay model: |Δw|)."""
    from repro.models.transformer import init_params

    params, axes = init_params(cfg, abstract=True)
    lcfg = cfg.lora or LoRAConfig()
    total = 0
    for path, leaf in jax.tree_util.tree_flatten_with_path(params)[0]:
        if is_target(path, leaf, lcfg):
            lead = 1
            for s in leaf.shape[:-2]:
                lead *= s
            total += lead * lcfg.rank * (leaf.shape[-2] + leaf.shape[-1])
    return total


def split_client_server(lora_params, cut_group: int):
    """Partition adapters at a scanned-group boundary: leaves under 'groups'
    keyed by stacked-layer dim are sliced; embed-side and encoder leaves go
    to the client, which runs the embedding and the encoder (encdec); head/
    final-side to the server (paper: client holds the first A-fraction).
    """
    client, server = {}, {}
    for pstr, ab in lora_params.items():
        if pstr.startswith("['groups']"):
            client[pstr] = jax.tree.map(lambda x: x[:cut_group], ab)
            server[pstr] = jax.tree.map(lambda x: x[cut_group:], ab)
        elif "embed" in pstr or pstr.startswith("['enc_groups']"):
            client[pstr] = ab
        else:
            server[pstr] = ab
    return client, server


def join_client_server(client, server):
    """Inverse of split_client_server."""
    out = {}
    keys = set(client) | set(server)
    for pstr in keys:
        if pstr in client and pstr in server:
            out[pstr] = jax.tree.map(lambda c, s: jnp.concatenate([c, s], axis=0),
                                     client[pstr], server[pstr])
        elif pstr in client:
            out[pstr] = client[pstr]
        else:
            out[pstr] = server[pstr]
    return out
