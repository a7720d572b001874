"""Per-kernel shape/dtype sweeps: Pallas (interpret mode) vs jnp oracle."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.attn_ops import flash_attention, flash_attention_ref
from repro.kernels.lora_ops import lora_matmul, lora_matmul_ref
from repro.kernels.ssd_ops import ssd_scan, ssd_scan_ref

# ---------------------------------------------------------------------------
# LoRA fused matmul
# ---------------------------------------------------------------------------

LORA_CASES = [
    # (M, K, N, r, dtype, tol)
    (128, 256, 128, 8, jnp.float32, 1e-5),
    (256, 512, 384, 16, jnp.float32, 1e-5),
    (64, 128, 256, 4, jnp.bfloat16, 5e-2),
    (100, 200, 300, 8, jnp.float32, 1e-5),  # non-aligned -> padding path
    (32, 1024, 64, 32, jnp.float32, 1e-5),
    (8, 64, 8, 2, jnp.float32, 1e-5),  # tiny
]


@pytest.mark.parametrize("M,K,N,r,dtype,tol", LORA_CASES)
def test_lora_matmul_matches_ref(M, K, N, r, dtype, tol):
    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    x = jax.random.normal(ks[0], (M, K), dtype)
    w = jax.random.normal(ks[1], (K, N), dtype) * 0.05
    a = jax.random.normal(ks[2], (K, r), dtype) * 0.05
    b = jax.random.normal(ks[3], (r, N), dtype) * 0.05
    y = lora_matmul(x, w, a, b, scale=2.0, interpret=True)
    ref = lora_matmul_ref(x, w, a, b, scale=2.0)
    np.testing.assert_allclose(np.asarray(y, np.float32), np.asarray(ref, np.float32),
                               rtol=tol, atol=tol)


def test_lora_matmul_batched_leading_dims():
    ks = jax.random.split(jax.random.PRNGKey(1), 4)
    x = jax.random.normal(ks[0], (2, 8, 64), jnp.float32)
    w = jax.random.normal(ks[1], (64, 32), jnp.float32) * 0.1
    a = jax.random.normal(ks[2], (64, 4), jnp.float32) * 0.1
    b = jax.random.normal(ks[3], (4, 32), jnp.float32) * 0.1
    y = lora_matmul(x, w, a, b, interpret=True)
    ref = lora_matmul_ref(x.reshape(16, 64), w, a, b).reshape(2, 8, 32)
    np.testing.assert_allclose(np.asarray(y), np.asarray(ref), rtol=1e-5, atol=1e-5)


def test_lora_matmul_zero_B_equals_base():
    """B = 0 (LoRA init) -> fused result == plain matmul."""
    ks = jax.random.split(jax.random.PRNGKey(2), 3)
    x = jax.random.normal(ks[0], (64, 128), jnp.float32)
    w = jax.random.normal(ks[1], (128, 64), jnp.float32) * 0.1
    a = jax.random.normal(ks[2], (128, 8), jnp.float32)
    b = jnp.zeros((8, 64), jnp.float32)
    y = lora_matmul(x, w, a, b, scale=4.0, interpret=True)
    np.testing.assert_allclose(np.asarray(y), np.asarray(x @ w), rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# Flash attention
# ---------------------------------------------------------------------------

ATTN_CASES = [
    # (B, H, Kv, S, d, window, softcap, dtype, tol)
    (2, 4, 2, 128, 64, 0, 0.0, jnp.float32, 2e-5),
    (1, 4, 4, 256, 32, 64, 0.0, jnp.float32, 2e-5),   # sliding window
    (1, 2, 1, 128, 64, 0, 50.0, jnp.float32, 2e-5),   # softcap + MQA
    (1, 8, 2, 192, 64, 0, 0.0, jnp.bfloat16, 3e-2),   # GQA bf16, ragged seq
    (2, 2, 2, 64, 128, 32, 30.0, jnp.float32, 2e-5),  # window + softcap
]


@pytest.mark.parametrize("B,H,Kv,S,d,window,softcap,dtype,tol", ATTN_CASES)
def test_flash_attention_matches_ref(B, H, Kv, S, d, window, softcap, dtype, tol):
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (B, H, S, d), dtype)
    k = jax.random.normal(ks[1], (B, Kv, S, d), dtype)
    v = jax.random.normal(ks[2], (B, Kv, S, d), dtype)
    o = flash_attention(q, k, v, window=window, softcap=softcap, bq=64, bk=64,
                        interpret=True)
    ref = flash_attention_ref(q, k, v, window=window, softcap=softcap)
    np.testing.assert_allclose(np.asarray(o, np.float32), np.asarray(ref, np.float32),
                               rtol=tol, atol=tol)


def test_flash_attention_rows_sum_to_one_property():
    """Degenerate v = ones -> output rows must be exactly ones (softmax sums)."""
    B, H, S, d = 1, 2, 128, 32
    q = jax.random.normal(jax.random.PRNGKey(0), (B, H, S, d))
    k = jax.random.normal(jax.random.PRNGKey(1), (B, H, S, d))
    v = jnp.ones((B, H, S, d))
    o = flash_attention(q, k, v, bq=64, bk=64, interpret=True)
    np.testing.assert_allclose(np.asarray(o), 1.0, rtol=1e-5)


# ---------------------------------------------------------------------------
# SSD scan
# ---------------------------------------------------------------------------

SSD_CASES = [
    # (B, S, H, P, N, chunk, dtype, rtol)
    (2, 64, 3, 16, 8, 16, jnp.float32, 1e-4),
    (1, 128, 2, 32, 16, 32, jnp.float32, 1e-4),
    (1, 64, 1, 8, 8, 64, jnp.float32, 1e-4),   # single chunk
    (2, 96, 2, 16, 8, 32, jnp.float32, 1e-4),
]


@pytest.mark.parametrize("B,S,H,P,N,chunk,dtype,rtol", SSD_CASES)
def test_ssd_scan_matches_sequential_ref(B, S, H, P, N, chunk, dtype, rtol):
    ks = jax.random.split(jax.random.PRNGKey(0), 5)
    x = jax.random.normal(ks[0], (B, S, H, P), dtype)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, S, H), dtype))
    A = -jnp.exp(jax.random.normal(ks[2], (H,), dtype) * 0.3)
    Bm = jax.random.normal(ks[3], (B, S, N), dtype) * 0.5
    Cm = jax.random.normal(ks[4], (B, S, N), dtype) * 0.5
    y = ssd_scan(x, dt, A, Bm, Cm, chunk=chunk, interpret=True)
    ref = ssd_scan_ref(x, dt, A, Bm, Cm)
    scale = float(jnp.max(jnp.abs(ref))) + 1e-6
    np.testing.assert_allclose(np.asarray(y) / scale, np.asarray(ref) / scale,
                               rtol=rtol, atol=rtol)


def test_ssd_decay_property():
    """With A -> -inf (full decay) the SSD reduces to a per-step product
    y_t = C_t·(dt_t·B_t ⊗ x_t) — no state carry-over."""
    B, S, H, P, N = 1, 32, 1, 8, 4
    ks = jax.random.split(jax.random.PRNGKey(3), 4)
    x = jax.random.normal(ks[0], (B, S, H, P))
    dt = jnp.full((B, S, H), 1.0)
    A = jnp.full((H,), -50.0)  # decay exp(-50) ≈ 0
    Bm = jax.random.normal(ks[1], (B, S, N))
    Cm = jax.random.normal(ks[2], (B, S, N))
    y = ssd_scan(x, dt, A, Bm, Cm, chunk=16, interpret=True)
    expected = jnp.einsum("bsn,bsn,bshp->bshp", Cm, Bm, x)
    np.testing.assert_allclose(np.asarray(y), np.asarray(expected), rtol=1e-4, atol=1e-4)


def test_flash_attention_ragged_non_causal_raises():
    """Padded keys are masked only through the causal frontier, so a ragged
    non-causal call is refused instead of answered by another code path."""
    q = jnp.ones((1, 2, 96, 32))
    with pytest.raises(ValueError, match="non-causal"):
        flash_attention(q, q, q, causal=False, bq=64, bk=64, interpret=True)
