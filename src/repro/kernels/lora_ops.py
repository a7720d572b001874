"""Jit'd public wrapper for the fused LoRA matmul.

Handles arbitrary leading batch dims, non-aligned shapes (zero padding to
block multiples) and dtype promotion.  ``interpret=True`` runs the kernel in
the Pallas interpreter (the CPU tests); otherwise it is compiled for the TPU."""

from __future__ import annotations

import jax.numpy as jnp

from repro.kernels.lora_matmul import lora_matmul_pallas
from repro.kernels.lora_ref import lora_matmul_ref


def _round_up(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


def lora_matmul(x, w, a, b, *, scale: float = 1.0, bm: int = 128, bn: int = 128,
                bk: int = 512, interpret: bool = False):
    """y = x·W + scale·(x·A)·B with x (..., K), w (K, N), a (K, r), b (r, N)."""
    lead = x.shape[:-1]
    K = x.shape[-1]
    N = w.shape[1]
    r = a.shape[1]
    M = 1
    for s in lead:
        M *= s
    x2 = x.reshape(M, K)

    bm_ = min(bm, _round_up(M, 8))
    bn_ = min(bn, _round_up(N, 128))
    bk_ = min(bk, _round_up(K, 128))
    Mp, Np, Kp = _round_up(M, bm_), _round_up(N, bn_), _round_up(K, bk_)
    rp = _round_up(r, 8)
    xp = jnp.pad(x2, ((0, Mp - M), (0, Kp - K)))
    wp = jnp.pad(w, ((0, Kp - K), (0, Np - N)))
    ap = jnp.pad(a, ((0, Kp - K), (0, rp - r)))
    bp = jnp.pad(b, ((0, rp - r), (0, Np - N)))
    y = lora_matmul_pallas(xp, wp, ap, bp, scale=scale, bm=bm_, bn=bn_, bk=bk_,
                           interpret=interpret)
    return y[:M, :N].reshape(*lead, N)


__all__ = ["lora_matmul", "lora_matmul_ref"]
