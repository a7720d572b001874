"""DP aggregation, noise layer, split-learning pipelining, grad accumulation."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.config import FedsLLMConfig, LoRAConfig, TrainConfig, get_arch, smoke_variant
from repro.core import federated, privacy
from repro.core import lora as lora_lib, split
from repro.models import transformer as T
from repro.optim.grad_utils import global_norm
from repro.parallel import pipeline


# ---------------------------------------------------------------------------
# privacy
# ---------------------------------------------------------------------------


def test_clip_bounds_norm():
    t = {"w": jnp.full((10,), 100.0)}
    c = privacy.clip_tree(t, 1.0)
    np.testing.assert_allclose(float(global_norm(c)), 1.0, rtol=1e-5)
    # small updates pass through
    t2 = {"w": jnp.full((10,), 1e-3)}
    c2 = privacy.clip_tree(t2, 1.0)
    np.testing.assert_allclose(np.asarray(c2["w"]), 1e-3, rtol=1e-5)


def test_dp_fedavg_noise_scale():
    """Mean of noised stack == clean mean + N(0, (σc/K)²)."""
    K, d = 8, 4096
    rng = np.random.default_rng(0)
    stacked = {"w": jnp.asarray(rng.normal(0, 0.01, (K, d)), jnp.float32)}
    noised = privacy.clip_and_noise_updates(stacked, jax.random.PRNGKey(0),
                                            clip_norm=1.0, noise_multiplier=1.0)
    clean = federated.fedavg(stacked)
    dp = federated.fedavg(noised)
    resid = np.asarray(dp["w"] - clean["w"])
    emp_std = resid.std()
    np.testing.assert_allclose(emp_std, 1.0 / K, rtol=0.15)


def test_noise_layer_snr():
    x = jax.random.normal(jax.random.PRNGKey(0), (64, 64))
    y = privacy.noise_layer(x, jax.random.PRNGKey(1), snr_db=20.0)
    noise = np.asarray(y - x)
    snr = float(jnp.mean(x**2)) / max(noise.var(), 1e-12)
    assert 50 < snr < 200  # 20 dB = 100x


def test_privacy_cost_monotone():
    e1 = privacy.privacy_cost(1.0, rounds=10)
    e2 = privacy.privacy_cost(2.0, rounds=10)
    e3 = privacy.privacy_cost(1.0, rounds=40)
    assert e2 < e1 < e3


def test_dp_round_runs_and_stays_finite():
    from repro.core import fedsllm
    from repro.data.tokens import TokenStream, client_batches

    cfg = smoke_variant(get_arch("fedsllm-100m")).replace(lora=LoRAConfig(rank=4))
    fcfg = FedsLLMConfig(num_clients=4)
    state, _ = fedsllm.init_state(cfg, 1)
    round_fn = jax.jit(fedsllm.build_round_fn(cfg, fcfg, 1, eta=0.5,
                                             dp_clip=1.0, dp_noise=0.5))
    stream = TokenStream(2, 32, cfg.vocab_size, seed=0)
    batches = client_batches(stream, 0, 4)
    state2, metrics = round_fn(state, batches, None, jax.random.PRNGKey(7))
    for leaf in jax.tree.leaves(state2.lora_c):
        assert bool(jnp.all(jnp.isfinite(leaf)))


# ---------------------------------------------------------------------------
# pipelining
# ---------------------------------------------------------------------------


def test_pipelined_split_grads_exact():
    """Microbatched split step == full-batch split step exactly."""
    cfg = smoke_variant(get_arch("fedsllm-100m")).replace(lora=LoRAConfig(rank=4))
    params, axes = T.init_params(cfg, key=jax.random.PRNGKey(0))
    lora, _ = lora_lib.init_lora(params, axes, cfg, key=jax.random.PRNGKey(1))
    lora = jax.tree.map(lambda x: x + 0.01, lora)
    lc, ls = lora_lib.split_client_server(lora, 1, cfg)
    B, S = 4, 16
    kt, kl = jax.random.split(jax.random.PRNGKey(2))
    batch = {"tokens": jax.random.randint(kt, (B, S), 0, cfg.vocab_size),
             "labels": jax.random.randint(kl, (B, S), 0, cfg.vocab_size),
             "mask": jnp.ones((B, S), jnp.float32)}
    loss_f, dc_f, ds_f, _ = split.split_value_and_grad(params, lc, ls, batch, cfg, 1)
    loss_p, dc_p, ds_p = pipeline.pipelined_split_grads(params, lc, ls, batch, cfg, 1, 4)
    np.testing.assert_allclose(float(loss_p), float(loss_f), rtol=1e-5)
    for a, b in zip(jax.tree.leaves(dc_p), jax.tree.leaves(dc_f)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b, np.float32),
                                   rtol=1e-4, atol=5e-6)
    for a, b in zip(jax.tree.leaves(ds_p), jax.tree.leaves(ds_f)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b, np.float32),
                                   rtol=1e-4, atol=5e-6)


def test_pipeline_latency_model():
    stages = dict(client_fwd=1.0, uplink=0.5, server=2.0, downlink=0.1,
                  client_bwd=1.0)
    seq = pipeline.pipeline_round_time(stages, 1)
    pipe = pipeline.pipeline_round_time(stages, 8)
    assert np.isclose(seq["sequential_s"], 4.6)
    # M→∞ limit is the bottleneck stage (2.0)
    assert pipe["pipelined_s"] < seq["sequential_s"]
    assert pipe["pipelined_s"] >= 2.0 * (8 - 1) / 8
    assert pipe["speedup"] > 1.5


def test_pipeline_stage_times_integrate_with_allocator():
    from repro.core import delay_model as dm
    from repro.core import resource_alloc as ra

    fcfg = FedsLLMConfig(num_clients=5)
    net = dm.sample_network(fcfg, seed=0)
    a = ra.solve_fixed_eta_exact(fcfg, net, 0.1)
    stages = pipeline.split_stage_times(fcfg, net, 0.1, a.A, a)
    out = pipeline.pipeline_round_time(stages, 4)
    assert np.all(out["speedup"] >= 1.0)
    assert np.all(out["pipelined_s"] <= out["sequential_s"] + 1e-9)


# ---------------------------------------------------------------------------
# gradient accumulation
# ---------------------------------------------------------------------------


def test_grad_accumulation_matches_full_batch():
    from repro.launch.steps import make_train_step

    cfg = smoke_variant(get_arch("fedsllm-100m")).replace(vocab_size=64)
    params, _ = T.init_params(cfg, key=jax.random.PRNGKey(0))
    kt, kl = jax.random.split(jax.random.PRNGKey(1))
    B, S = 8, 16
    batch = {"tokens": jax.random.randint(kt, (B, S), 0, cfg.vocab_size),
             "labels": jax.random.randint(kl, (B, S), 0, cfg.vocab_size),
             "mask": jnp.ones((B, S), jnp.float32)}

    outs = {}
    for m in (0, 4):
        tcfg = TrainConfig(learning_rate=1e-2, remat="none", microbatch=m,
                           optimizer="sgd")
        step_fn, opt = make_train_step(cfg, tcfg)
        p, o, s, metrics = jax.jit(step_fn)(params, opt.init(params),
                                            jnp.zeros((), jnp.int32), batch)
        outs[m] = (p, float(metrics["loss"]))
    np.testing.assert_allclose(outs[0][1], outs[4][1], rtol=1e-5)
    for a, b in zip(jax.tree.leaves(outs[0][0]), jax.tree.leaves(outs[4][0])):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32), atol=2e-5)
