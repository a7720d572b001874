"""Compiles for a described TPU v5e, with no chip attached.

The TPU compiler refuses what interpret mode lets through (block shapes off
the (8, 128) tiling, programs larger than the chip's memory), so the
kernels of the main path and the round program that ``chip_smoke.py`` runs
are compiled here at their real widths.  The topology is described inside a
fixture: only the worker that runs this file loads the TPU library.
"""

import os
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.config import get_arch
from repro.kernels.attn_ops import flash_attention
from repro.kernels.lora_ops import lora_matmul
from repro.kernels.ssd_ops import ssd_scan


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    # compiles for a described device cannot be read back from the
    # persistent cache, so keep them out of it
    from jax.experimental.compilation_cache import compilation_cache

    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _assert_kernel(compiled):
    assert "tpu_custom_call" in compiled.as_text()


def test_lora_matmul_compiles_at_fedsllm_100m_width(one_chip):
    cfg = get_arch("fedsllm-100m")
    M, D, F, r = 2048, cfg.d_model, cfg.d_ff, cfg.lora.rank
    args = [_spec(s, jnp.bfloat16, one_chip) for s in ((M, D), (D, F), (D, r), (r, F))]
    fn = partial(lora_matmul, scale=cfg.lora.scale, interpret=False)
    _assert_kernel(jax.jit(fn).lower(*args).compile())


def test_flash_attention_compiles_at_fedsllm_100m_width(one_chip):
    cfg = get_arch("fedsllm-100m")
    S = 1024
    q = _spec((1, cfg.num_heads, S, cfg.head_dim), jnp.bfloat16, one_chip)
    kv = _spec((1, cfg.num_kv_heads, S, cfg.head_dim), jnp.bfloat16, one_chip)
    fn = partial(flash_attention, causal=True, interpret=False)
    _assert_kernel(jax.jit(fn).lower(q, kv, kv).compile())


def test_ssd_scan_compiles_at_mamba2_130m_width(one_chip):
    cfg = get_arch("mamba2-130m")
    H = cfg.ssm_expand * cfg.d_model // cfg.ssm_head_dim
    B, S, P, N = 1, 1024, cfg.ssm_head_dim, cfg.ssm_state
    args = (_spec((B, S, H, P), jnp.float32, one_chip),
            _spec((B, S, H), jnp.float32, one_chip),
            _spec((H,), jnp.float32, one_chip),
            _spec((B, S, N), jnp.float32, one_chip),
            _spec((B, S, N), jnp.float32, one_chip))
    fn = partial(ssd_scan, chunk=cfg.ssm_chunk, interpret=False)
    _assert_kernel(jax.jit(fn).lower(*args).compile())


def test_smoke_round_program_fits_one_v5e(one_chip, chip_smoke):
    """The round program chip_smoke.py runs, at fedsllm-100m full width and
    the smoke's cohort, fits one chip's 16 GB with the script's headroom."""
    exp = chip_smoke.smoke_experiment(get_arch(chip_smoke.ARCH), chip_smoke.CLIENTS)
    tok = jax.ShapeDtypeStruct(
        (chip_smoke.CLIENTS, chip_smoke.BATCH, chip_smoke.SEQ), jnp.int32)
    batches = {"tokens": tok, "labels": tok,
               "mask": jax.ShapeDtypeStruct(tok.shape, jnp.float32)}
    # the arguments run_round passes, moved onto the described chip
    args = jax.tree.map(lambda a: _spec(np.shape(a), a.dtype, one_chip),
                        exp.round_args(batches))
    mem = chip_smoke.compiled_bytes(exp.round_fn.lower(*args).compile())
    assert 0 < mem["total"] <= chip_smoke.HBM_BUDGET_BYTES, mem
