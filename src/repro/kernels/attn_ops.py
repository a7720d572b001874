"""Jit'd public wrapper for flash attention (padding to block multiples)."""

from __future__ import annotations

import jax.numpy as jnp

from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.attn_ref import flash_attention_ref


def flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    softcap: float = 0.0, bq: int = 128, bk: int = 128,
                    interpret: bool = False):
    """q (B,H,S,d), k/v (B,Kv,S,d). Pads seq to block multiples.

    ``interpret=True`` runs the kernel in the Pallas interpreter (the CPU
    tests); otherwise it is compiled for the TPU."""
    B, H, Sq, d = q.shape
    Skv = k.shape[2]
    bq_ = min(bq, max(8, Sq))
    bk_ = min(bk, max(8, Skv))
    Sqp = ((Sq + bq_ - 1) // bq_) * bq_
    Skp = ((Skv + bk_ - 1) // bk_) * bk_
    qp = jnp.pad(q, ((0, 0), (0, 0), (0, Sqp - Sq), (0, 0)))
    kp = jnp.pad(k, ((0, 0), (0, 0), (0, Skp - Skv), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (0, 0), (0, Skp - Skv), (0, 0)))
    if Skp > Skv and not causal:
        # the kernel masks padded keys only through the causal frontier
        raise ValueError(f"non-causal flash_attention needs kv length {Skv} to "
                         f"be a multiple of the kv block {bk_}")
    o = flash_attention_pallas(qp, kp, vp, causal=causal, window=window,
                               softcap=softcap, bq=bq_, bk=bk_, interpret=interpret)
    return o[:, :, :Sq, :]


__all__ = ["flash_attention", "flash_attention_ref"]
