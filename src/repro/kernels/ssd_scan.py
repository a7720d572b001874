"""Mamba-2 SSD chunked-scan Pallas TPU kernel.

State-space duality layout: per (batch, head) the sequence is processed in
chunks of Q; the quadratic intra-chunk term and the state in/out projections
are MXU matmuls; the (N, P) recurrent state lives in fp32 VMEM scratch and
persists across the (sequential, innermost) chunk grid dimension:

  y[c]    = tril(C_c·B_cᵀ ⊙ decay) · (dt·x)_c  +  (C_c ⊙ decay_in) · h_{c-1}
  h_c     = exp(Σ log a_c) · h_{c-1}  +  B_cᵀ · (decay_out ⊙ (dt·x)_c)

This is the TPU adaptation of the Mamba-2 GPU kernel: instead of warp-level
scans, the inter-chunk recurrence is carried in VMEM between grid steps (the
TPU grid is sequential), and all O(Q²)/O(Q·N·P) work is shaped for the MXU.

Grid = (B, H, S/Q); chunks innermost.  x (B,H,S,P) head-major so that every
block's last two dims are (Q, P)/(Q, N), dt (B,H,S) pre-scaled outside (laid
out as one (1, Q) row per chunk), A (H,) in SMEM, Bm/Cm (B,S,N) shared across
heads (groups = 1).  The in-chunk cumulative sums are triangular matmuls, so
row and column forms of the decay come out of the MXU without a transpose.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_HI = jax.lax.Precision.HIGHEST
_NT = (((1,), (1,)), ((), ()))  # contract the last dims: a · bᵀ


def _kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, y_ref, state_ref, *, Q: int):
    ic = pl.program_id(2)

    @pl.when(ic == 0)
    def _init():
        state_ref[...] = jnp.zeros_like(state_ref)

    x = x_ref[...].astype(jnp.float32)  # (Q, P)
    dt = dt_ref[...].astype(jnp.float32)  # (1, Q)
    A = a_ref[pl.program_id(1)]  # scalar (negative)
    Bm = b_ref[...].astype(jnp.float32)  # (Q, N)
    Cm = c_ref[...].astype(jnp.float32)  # (Q, N)

    row = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 1)
    mask = row >= col
    tril = mask.astype(jnp.float32)
    eye = (row == col).astype(jnp.float32)

    la = dt * A  # (1, Q) log decay per step
    cs_col = jax.lax.dot_general(tril, la, _NT, precision=_HI,
                                 preferred_element_type=jnp.float32)  # (Q, 1)
    cs_row = jax.lax.dot_general(la, tril, _NT, precision=_HI,
                                 preferred_element_type=jnp.float32)  # (1, Q)
    dt_col = jax.lax.dot_general(eye, dt, _NT, precision=_HI,
                                 preferred_element_type=jnp.float32)  # (Q, 1)
    xw = x * dt_col  # dt-weighted input

    # intra-chunk: scores[q, s] = (C_q·B_s) · exp(cs_q - cs_s) for s <= q
    L = jnp.where(mask, jnp.exp(cs_col - cs_row), 0.0)
    scores = jax.lax.dot_general(Cm, Bm, _NT,
                                 preferred_element_type=jnp.float32) * L
    y = jax.lax.dot(scores, xw, preferred_element_type=jnp.float32)  # (Q, P)

    # inter-chunk: contribution of the carried state
    decay_in = jnp.exp(cs_col)  # (Q, 1) decay from chunk start to step q
    y += jax.lax.dot(Cm * decay_in, state_ref[...],
                     preferred_element_type=jnp.float32)  # (Q,N)x(N,P)

    # state update: h = exp(sum la)·h + Bᵀ·(decay_to_end ⊙ xw)
    total = jnp.sum(la)
    decay_out = jnp.exp(total - cs_col)  # (Q, 1)
    state_ref[...] = jnp.exp(total) * state_ref[...] + jax.lax.dot(
        Bm.T, xw * decay_out, preferred_element_type=jnp.float32)  # (N, P)

    y_ref[...] = y.astype(y_ref.dtype)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_scan_pallas(x, dt, A, Bm, Cm, *, chunk: int = 256, interpret: bool = False):
    """x: (B,H,S,P); dt: (B,H,S); A: (H,); Bm/Cm: (B,S,N) -> y (B,H,S,P)."""
    B, H, S, P = x.shape
    N = Bm.shape[-1]
    Q = min(chunk, S)
    assert S % Q == 0
    nc = S // Q

    grid = (B, H, nc)
    return pl.pallas_call(
        functools.partial(_kernel, Q=Q),
        grid=grid,
        in_specs=[
            pl.BlockSpec((None, None, Q, P), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((None, None, None, 1, Q), lambda b, h, c: (b, h, c, 0, 0)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((None, Q, N), lambda b, h, c: (b, c, 0)),
            pl.BlockSpec((None, Q, N), lambda b, h, c: (b, c, 0)),
        ],
        out_specs=pl.BlockSpec((None, None, Q, P), lambda b, h, c: (b, h, c, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, S, P), x.dtype),
        scratch_shapes=[pltpu.VMEM((N, P), jnp.float32)],
        interpret=interpret,
    )(x, dt.reshape(B, H, nc, 1, Q), A.astype(jnp.float32), Bm, Cm)
