"""Split-learning engine: the Algorithm-2 message flow must equal
end-to-end autodiff exactly, for every model family."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.config import LoRAConfig, get_arch, smoke_variant
from repro.core import lora as lora_lib
from repro.core import split
from repro.models import layers as L
from repro.models import transformer as T

FAMILIES = ["fedsllm-100m", "olmoe-1b-7b", "mamba2-130m", "recurrentgemma-9b",
            "whisper-base"]


def setup(arch, cut=1):
    cfg = smoke_variant(get_arch(arch)).replace(lora=LoRAConfig(rank=4, alpha=8.0))
    params, axes = T.init_params(cfg, key=jax.random.PRNGKey(0))
    lora_full, _ = lora_lib.init_lora(params, axes, cfg, key=jax.random.PRNGKey(1))
    # make B nonzero so gradients flow through both factors
    lora_full = jax.tree.map(
        lambda x: x + 0.01 * jax.random.normal(jax.random.PRNGKey(2), x.shape, x.dtype),
        lora_full)
    lc, ls = lora_lib.split_client_server(lora_full, cut)
    B, S = 2, 16
    kt, kl = jax.random.split(jax.random.PRNGKey(3))
    batch = {"tokens": jax.random.randint(kt, (B, S), 0, cfg.vocab_size),
             "labels": jax.random.randint(kl, (B, S), 0, cfg.vocab_size),
             "mask": jnp.ones((B, S), jnp.float32)}
    if cfg.family == "encdec":
        batch["frame_embeds"] = jax.random.normal(kt, (B, cfg.encoder_seq, cfg.d_model),
                                                  jnp.float32)
    if cfg.family == "vlm":
        Tv = cfg.vision_tokens
        batch["vision_embeds"] = jax.random.normal(kt, (B, Tv, 1024), jnp.float32)
        batch["tokens"] = batch["tokens"][:, : S - Tv]
    return cfg, params, lc, ls, batch


@pytest.mark.parametrize("arch", FAMILIES)
def test_split_equals_monolithic(arch):
    cfg, params, lc, ls, batch = setup(arch)
    loss_s, dc_s, ds_s, info = split.split_value_and_grad(params, lc, ls, batch, cfg, 1)
    loss_m, dc_m, ds_m = split.monolithic_value_and_grad(params, lc, ls, batch, cfg, 1)
    np.testing.assert_allclose(float(loss_s), float(loss_m), rtol=1e-6)
    for a, b in zip(jax.tree.leaves(dc_s), jax.tree.leaves(dc_m)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-6)
    for a, b in zip(jax.tree.leaves(ds_s), jax.tree.leaves(ds_m)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-6)
    assert info["smashed_bytes"] > 0


def test_split_join_roundtrip():
    cfg, params, lc, ls, batch = setup("fedsllm-100m", cut=1)
    joined = lora_lib.join_client_server(lc, ls)
    lc2, ls2 = lora_lib.split_client_server(joined, 1)
    for a, b in zip(jax.tree.leaves(lc), jax.tree.leaves(lc2)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    for a, b in zip(jax.tree.leaves(ls), jax.tree.leaves(ls2)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_smashed_bytes_scale_with_cut_position():
    """Smashed activation volume is (B, S, D) regardless of cut — the
    paper's constant s; gradient volume matches it."""
    cfg, params, lc, ls, batch = setup("fedsllm-100m", cut=1)
    _, _, _, info1 = split.split_value_and_grad(params, lc, ls, batch, cfg, 1)
    assert info1["smashed_bytes"] == info1["grad_bytes"]


@pytest.mark.parametrize("arch", FAMILIES)
def test_unmerged_adapters_match_merged_weights(arch):
    """The split step's client and server functions apply adapters unmerged;
    fed ``lora.merge``d weights and no adapters they give the same loss and
    adapter gradients."""
    cfg, params, lc, ls, batch = setup(arch)
    parts = split.slice_base(params, 1)

    def unmerged(lc, ls):
        acts, enc_out = split.client_forward(parts.client_base, lc, batch, cfg)
        return split.server_forward_loss(parts.server_base, ls, acts, batch, cfg,
                                         enc_out=enc_out)

    def merged(lc, ls):
        acts, enc_out = split.client_forward(
            lora_lib.merge(parts.client_base, lc, cfg), {}, batch, cfg)
        return split.server_forward_loss(lora_lib.merge(parts.server_base, ls, cfg), {},
                                         acts, batch, cfg, enc_out=enc_out)

    loss_u, grads_u = jax.value_and_grad(unmerged, argnums=(0, 1))(lc, ls)
    loss_m, grads_m = jax.value_and_grad(merged, argnums=(0, 1))(lc, ls)
    np.testing.assert_allclose(float(loss_u), float(loss_m), rtol=1e-5)
    for a, b in zip(jax.tree.leaves(grads_u), jax.tree.leaves(grads_m)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("spec,w_shape", [(None, (64, 48)),
                                          ("becd,edf->becf", (3, 64, 48))])
def test_project_plain_weight_is_unchanged(spec, w_shape):
    """Serving and every caller without adapters see the projection they had:
    a plain weight gives ``x @ w.astype(x.dtype)`` (or its einsum) bit for bit."""
    kx, kw = jax.random.split(jax.random.PRNGKey(4))
    x_shape = (2, 16, 64) if spec is None else (2, 3, 5, 64)
    x = jax.random.normal(kx, x_shape, jnp.float32).astype(jnp.bfloat16)
    w = jax.random.normal(kw, w_shape, jnp.float32)
    want = x @ w.astype(x.dtype) if spec is None else jnp.einsum(spec, x, w.astype(x.dtype))
    got = L.project(x, w, spec)
    assert got.dtype == want.dtype
    np.testing.assert_array_equal(np.asarray(got.astype(jnp.float32)),
                                  np.asarray(want.astype(jnp.float32)))
