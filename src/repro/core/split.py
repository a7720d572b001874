"""Split-learning engine (paper Algorithm 2).

The model is cut at a scanned-group boundary: the client executes
embed + groups[:cut]; the main server executes groups[cut:] + tail +
final-norm + head + loss.  Frozen base weights live on both sides (split-fed
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


class SplitParts(NamedTuple):
    client_base: Any  # params view with groups[:cut]
    server_base: Any  # params view with groups[cut:] (+ tail/final/head)


def slice_base(params, cut: int) -> SplitParts:
    client = dict(params)
    server = dict(params)
    client["groups"] = jax.tree.map(lambda a: a[:cut], params["groups"])
    server["groups"] = jax.tree.map(lambda a: a[cut:], params["groups"])
    return SplitParts(client, server)


def client_forward(client_base, lora_c, batch, cfg: ModelConfig, *, remat=False):
    """Embed + first ``cut`` groups -> smashed activations (B, S, D).

    Traced under the scope ``fedsllm.client`` (its backward pass under
    ``transpose(jvp(fedsllm.client))``), which the benchmark's trace
    reduction reads by name."""
    with jax.named_scope("fedsllm.client"):
        merged = lora_lib.merge(client_base, lora_c, cfg)
        enc_out = T._run_encoder(merged, batch, cfg) if cfg.family == "encdec" else None
        x, positions = T._embed_inputs(merged, batch, cfg)
        x, _, _ = T._scan_groups(merged, x, cfg, positions=positions, enc_out=enc_out,
                                 remat=remat, include_tail=False)
        return x, enc_out


def server_forward_loss(server_base, lora_s, acts, batch, cfg: ModelConfig, *,
                        enc_out=None, remat=False):
    """Remaining groups + tail + head + CE loss on the main server, traced
    under the scope ``fedsllm.server``."""
    with jax.named_scope("fedsllm.server"):
        merged = lora_lib.merge(server_base, lora_s, cfg)
        S = acts.shape[1]
        positions = jnp.arange(S)[None, :]
        x, _, aux = T._scan_groups(merged, acts, cfg, positions=positions, enc_out=enc_out,
                                   remat=remat, include_tail=True)
        x = L.apply_norm(merged["final_norm"], x, cfg)
        loss = L.fused_cross_entropy(merged["embed"], x, batch["labels"], cfg,
                                     mask=batch.get("mask"))
        return loss + 0.01 * aux


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
    parts = slice_base(params, cut)

    def client_fn(lc):
        return client_forward(parts.client_base, lc, batch, cfg, remat=remat)

    (acts, enc_out), client_vjp = jax.vjp(client_fn, lora_c)
    if compressor is not None:
        acts = compressor.apply(acts)
        if enc_out is not None:  # encdec: the encoder output is uplink too
            enc_out = compressor.apply(enc_out)

    if enc_out is not None:  # encdec: encoder output is also smashed data
        def server_fn(ls, a, eo):
            return server_forward_loss(parts.server_base, ls, a, batch, cfg,
                                       enc_out=eo, remat=remat)

        loss, (dlora_s, dacts, denc) = jax.value_and_grad(server_fn, argnums=(0, 1, 2))(
            lora_s, acts, enc_out)
        (dlora_c,) = client_vjp((dacts, denc))
    else:
        def server_fn(ls, a):
            return server_forward_loss(parts.server_base, ls, a, batch, cfg,
                                       enc_out=None, remat=remat)

        loss, (dlora_s, dacts) = jax.value_and_grad(server_fn, argnums=(0, 1))(lora_s, acts)
        # gradient of smashed data returns to the client (the paper's dA_k)
        (dlora_c,) = client_vjp((dacts, None))
    uplink_elems = acts.size + (enc_out.size if enc_out is not None else 0)
    smashed_bits = (uplink_elems * acts.dtype.itemsize * 8 if compressor is None
                    else compressor.bits(uplink_elems, acts.dtype.itemsize * 8))
    info = {
        "smashed_bytes": uplink_elems * acts.dtype.itemsize,
        "smashed_bits_uplink": smashed_bits,
        "grad_bytes": dacts.size * dacts.dtype.itemsize,
    }
    return loss, dlora_c, dlora_s, info


def monolithic_value_and_grad(params, lora_c, lora_s, batch, cfg: ModelConfig, cut: int):
    """End-to-end autodiff reference — must equal split_value_and_grad."""

    def loss_fn(lc, ls):
        full = lora_lib.join_client_server(lc, ls)
        merged = lora_lib.merge(params, full, cfg)
        loss, _ = T.loss_fn(merged, batch, cfg)
        # note: T.loss_fn adds 0.01*aux internally; replicate server path
        return loss

    # simpler exact reference: run the same two-phase math in one graph
    def loss2(lc, ls):
        parts = slice_base(params, cut)
        acts, enc_out = client_forward(parts.client_base, lc, batch, cfg)
        return server_forward_loss(parts.server_base, ls, acts, batch, cfg, enc_out=enc_out)

    (loss), (dc, ds) = jax.value_and_grad(loss2, argnums=(0, 1))(lora_c, lora_s)
    return loss, dc, ds
