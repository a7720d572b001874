"""Jit'd public wrapper for the SSD scan kernel (model layout in and out)."""

from __future__ import annotations

import jax.numpy as jnp

from repro.kernels.ssd_scan import ssd_scan_pallas
from repro.kernels.ssd_ref import ssd_scan_ref


def ssd_scan(x, dt, A, Bm, Cm, *, chunk: int = 256, interpret: bool = False):
    """x: (B,S,H,P); dt: (B,S,H); A: (H,); Bm/Cm: (B,S,N) -> y (B,S,H,P).

    The kernel runs head-major; ``interpret=True`` runs it in the Pallas
    interpreter (the CPU tests), otherwise it is compiled for the TPU."""
    y = ssd_scan_pallas(jnp.swapaxes(x, 1, 2), jnp.swapaxes(dt, 1, 2), A, Bm, Cm,
                        chunk=chunk, interpret=interpret)
    return jnp.swapaxes(y, 1, 2)


__all__ = ["ssd_scan", "ssd_scan_ref"]
