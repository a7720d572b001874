"""Split-learning engine (paper Algorithm 2).

The model is cut at a layer boundary: the client executes embed + the
first ``cut`` layers of the scanned stack; the main server executes the rest
+ tail + final-norm + head + loss.  With a layer pattern of period p the
client runs groups [0, cut // p) and the first cut % p sublayers of the next
group, the server that group's other sublayers and the groups after it
(``transformer.layer_view``).  Frozen base weights live on both sides (split-fed
deployments pre-stage w0; only LoRA updates and smashed activations move).

``split_value_and_grad`` reproduces the paper's message flow exactly with
``jax.vjp``:

    client forward  ->  smashed activations A_k   (uplink, s bits)
    server fwd+bwd  ->  loss, dLoRA_s, dA_k       (downlink gradient)
    client backward ->  dLoRA_c                   (vjp closure)

and is verified (tests/test_split.py) to equal end-to-end autodiff grads.
The activation byte count is exposed for the delay model (the paper's ``s``).
"""

from __future__ import annotations

from typing import Any, NamedTuple

import jax
import jax.numpy as jnp

from repro.config import ModelConfig
from repro.core import lora as lora_lib
from repro.models import layers as L
from repro.models import transformer as T


AUX_WEIGHT = 0.01  # the expert layers' load-balancing term in the loss


class SplitParts(NamedTuple):
    client_base: Any  # params view with the first ``cut`` layers
    server_base: Any  # params view with the other layers (+ tail/final/head)


def slice_base(params, cut: int, cfg: ModelConfig) -> SplitParts:
    """The client's and the server's views of ``params`` at layer ``cut``."""
    n = cfg.num_groups * cfg.group_size
    client = dict(params)
    server = dict(params)
    client["groups"] = T.layer_view(params["groups"], cfg, 0, cut)
    server["groups"] = T.layer_view(params["groups"], cfg, cut, n)
    return SplitParts(client, server)


def client_forward(client_base, lora_c, batch, cfg: ModelConfig, *, remat=False):
    """Embed + first ``cut`` layers -> (smashed activations (B, S, D), the
    encoder output or None, the layers' aux as ``transformer._scan_groups``
    gives it).

    Traced under the scope ``fedsllm.client`` (its backward pass under
    ``transpose(jvp(fedsllm.client))``), which the benchmark's trace
    reduction reads by name."""
    with jax.named_scope("fedsllm.client"):
        adapted = lora_lib.attach(client_base, lora_c, cfg)
        enc_out = T._run_encoder(adapted, batch, cfg) if cfg.family == "encdec" else None
        x, positions = T._embed_inputs(adapted, batch, cfg)
        x, _, aux = T._scan_groups(adapted, x, cfg, positions=positions, enc_out=enc_out,
                                   remat=remat, include_tail=False)
        return x, enc_out, aux


def server_forward_loss(server_base, lora_s, acts, batch, cfg: ModelConfig, *,
                        enc_out=None, remat=False):
    """Remaining layers + tail + head + CE loss on the main server, traced
    under the scope ``fedsllm.server``: (loss with the server layers'
    load-balancing term, the layers' aux)."""
    with jax.named_scope("fedsllm.server"):
        adapted = lora_lib.attach(server_base, lora_s, cfg)
        S = acts.shape[1]
        positions = jnp.arange(S)[None, :]
        start = cfg.num_groups * cfg.group_size - T.n_layers(adapted["groups"])
        x, _, aux = T._scan_groups(adapted, acts, cfg, positions=positions, enc_out=enc_out,
                                   remat=remat, include_tail=True, start=start)
        x = L.apply_norm(adapted["final_norm"], x, cfg)
        loss = L.fused_cross_entropy(adapted["embed"], x, batch["labels"], cfg,
                                     mask=batch.get("mask"))
        return loss + AUX_WEIGHT * aux["moe_aux_loss"], aux


def split_value_and_grad(params, lora_c, lora_s, batch, cfg: ModelConfig, cut: int,
                         remat: bool = False, compressor=None):
    """Algorithm-2 message flow. Returns (loss, dlora_c, dlora_s, info).

    ``compressor`` (see ``repro.api.compressors``) is applied to the smashed
    activations on the client→server uplink, *outside* the client vjp: the
    server differentiates w.r.t. the compressed activations and the resulting
    dA_k flows straight through the codec back into the client backward pass
    (standard straight-through split learning).  ``info`` reports the exact
    per-trace compressed uplink volume for diagnostics; the delay model's
    ``s`` bits are rescaled by the codec's nominal ratio up front, in
    ``repro.api.Experiment`` (the allocator runs before any batch exists).
    """
    parts = slice_base(params, cut, cfg)

    def client_fn(lc):
        acts, enc_out, aux = client_forward(parts.client_base, lc, batch, cfg, remat=remat)
        return (acts, enc_out, aux["moe_aux_loss"]), aux.get("moe_loads")

    (acts, enc_out, aux_c), client_vjp, loads_c = jax.vjp(client_fn, lora_c, has_aux=True)
    # the client's load-balancing term joins the loss; its gradient is the
    # client's own (0 for a model without experts)
    aux_ct = jnp.asarray(AUX_WEIGHT, aux_c.dtype)
    if compressor is not None:
        acts = compressor.apply(acts)
        if enc_out is not None:  # encdec: the encoder output is uplink too
            enc_out = compressor.apply(enc_out)

    if enc_out is not None:  # encdec: encoder output is also smashed data
        def server_fn(ls, a, eo):
            return server_forward_loss(parts.server_base, ls, a, batch, cfg,
                                       enc_out=eo, remat=remat)

        (loss, aux_s), (dlora_s, dacts, denc) = jax.value_and_grad(
            server_fn, argnums=(0, 1, 2), has_aux=True)(lora_s, acts, enc_out)
        (dlora_c,) = client_vjp((dacts, denc, aux_ct))
    else:
        def server_fn(ls, a):
            return server_forward_loss(parts.server_base, ls, a, batch, cfg,
                                       enc_out=None, remat=remat)

        (loss, aux_s), (dlora_s, dacts) = jax.value_and_grad(
            server_fn, argnums=(0, 1), has_aux=True)(lora_s, acts)
        # gradient of smashed data returns to the client (the paper's dA_k)
        (dlora_c,) = client_vjp((dacts, None, aux_ct))
    if cfg.num_experts:
        loss = loss + AUX_WEIGHT * aux_c
    uplink_elems = acts.size + (enc_out.size if enc_out is not None else 0)
    smashed_bits = (uplink_elems * acts.dtype.itemsize * 8 if compressor is None
                    else compressor.bits(uplink_elems, acts.dtype.itemsize * 8))
    info = {
        "smashed_bytes": uplink_elems * acts.dtype.itemsize,
        "smashed_bits_uplink": smashed_bits,
        "grad_bytes": dacts.size * dacts.dtype.itemsize,
    }
    loads = [x for x in (loads_c, aux_s.get("moe_loads")) if x is not None]
    if loads:
        # the (token, expert) pairs each held expert received, per layer
        info["moe_loads"] = jnp.concatenate(loads, axis=0)
    return loss, dlora_c, dlora_s, info


def monolithic_value_and_grad(params, lora_c, lora_s, batch, cfg: ModelConfig, cut: int):
    """End-to-end autodiff reference — must equal split_value_and_grad.

    One graph through the merged weights W + (α/r)·A@B (``lora.merge``) and
    the same client and server math with no adapters attached: independent
    of the unmerged application that the split step uses."""

    def loss_fn(lc, ls):
        merged = lora_lib.merge(params, lora_lib.join_client_server(lc, ls), cfg)
        parts = slice_base(merged, cut, cfg)
        acts, enc_out, aux = client_forward(parts.client_base, {}, batch, cfg)
        loss, _ = server_forward_loss(parts.server_base, {}, acts, batch, cfg, enc_out=enc_out)
        return loss + AUX_WEIGHT * aux["moe_aux_loss"]

    loss, (dc, ds) = jax.value_and_grad(loss_fn, argnums=(0, 1))(lora_c, lora_s)
    return loss, dc, ds
