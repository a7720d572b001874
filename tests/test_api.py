"""Unified `Experiment` API: registries, config-driven wiring, shim parity."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.api import (Experiment, aggregators, allocators, compressors,
                       get_compressor)
from repro.config import (FedsLLMConfig, LoRAConfig, RunConfig, SHAPES,
                          get_arch, smoke_variant)
from repro.core import federated, fedsllm
from repro.data.tokens import TokenStream, client_batches

CLIENTS = 4


@pytest.fixture(scope="module")
def run_cfg():
    cfg = smoke_variant(get_arch("fedsllm-100m")).replace(
        lora=LoRAConfig(rank=4, alpha=8.0))
    return RunConfig(model=cfg, shape=SHAPES["train_4k"],
                     fedsllm=FedsLLMConfig(num_clients=CLIENTS))


@pytest.fixture(scope="module")
def batches(run_cfg):
    stream = TokenStream(2, 32, run_cfg.model.vocab_size, seed=0)
    return client_batches(stream, 0, CLIENTS)


# ---------------------------------------------------------------------------
# Registries
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("registry,expect", [
    (aggregators, {"fedavg", "weighted", "median", "trimmed_mean"}),
    (allocators, {"proposed", "EB", "FE", "BA"}),
    (compressors, {"none", "int8", "randk", "topk"}),
])
def test_registry_contents(registry, expect):
    assert expect <= set(registry.names())


@pytest.mark.parametrize("registry", [aggregators, allocators, compressors])
def test_unknown_strategy_lists_known_names(registry):
    """Mirror `get_arch`: unknown names raise KeyError naming the knowns."""
    with pytest.raises(KeyError) as exc:
        registry.get("definitely-not-registered")
    msg = str(exc.value)
    for name in registry.names():
        assert name in msg


@pytest.mark.parametrize("axis,registry", [
    ("aggregator", aggregators),
    ("allocator", allocators),
    ("compressor", compressors),
])
def test_unknown_strategy_in_experiment(run_cfg, axis, registry):
    """Every strategy axis fails fast at construction, naming the knowns."""
    with pytest.raises(KeyError, match=f"unknown {axis}") as exc:
        Experiment.from_config(run_cfg, **{axis: "nope"})
    for name in registry.names():
        assert name in str(exc.value)


# ---------------------------------------------------------------------------
# Experiment: config -> two rounds
# ---------------------------------------------------------------------------


def test_experiment_two_rounds(run_cfg, batches):
    exp = Experiment.from_config(run_cfg, allocator="EB", eta=0.5)
    assert exp.cohort == CLIENTS
    r1 = exp.run_round(batches)
    r2 = exp.run_round(batches)  # same data: local loss must keep descending
    assert np.isfinite(float(r1.metrics["loss_round_start"]))
    assert float(r2.metrics["loss_round_start"]) < float(r1.metrics["loss_round_start"])
    # co-computed simulated wireless timing, one entry per simulated user
    K = run_cfg.fedsllm.num_clients
    assert r1.timing.total.shape == (K,)
    assert np.all(r1.timing.total > 0) and r1.wall_clock > 0
    # the dead-metric fix: client update norm must be a real, nonzero value
    assert float(r2.metrics["h_c_norm"]) > 0


def test_build_round_fn_contract(run_cfg, batches):
    """build_round_fn (the engine) == Experiment.run_round, bit-exact.

    The former ``make_round_fn`` shim is gone; this pins the contract the
    shim-equivalence test used to enforce directly on the engine: a
    hand-built round function with default aggregation and no codec must
    reproduce the Experiment's round exactly (the Experiment's D_k weights
    are uniform on the even paper split, so weighted == unweighted)."""
    exp = Experiment.from_config(run_cfg, allocator="EB")
    res = exp.run_round(batches)

    state0, _ = fedsllm.init_state(exp.cfg, exp.cut, key=jax.random.PRNGKey(0))
    engine = jax.jit(fedsllm.build_round_fn(exp.cfg, exp.fcfg, exp.cut, exp.eta))
    state1, metrics1 = engine(state0, batches)

    for a, b in zip(jax.tree.leaves((res.state.lora_c, res.state.lora_s)),
                    jax.tree.leaves((state1.lora_c, state1.lora_s))):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    np.testing.assert_array_equal(
        np.asarray(res.metrics["loss_round_start"]),
        np.asarray(metrics1["loss_round_start"]))
    assert not hasattr(fedsllm, "make_round_fn")  # deprecation completed


@pytest.mark.parametrize("local_algo", ["gd", "scaffold"])
def test_round_args_are_what_run_round_passes(run_cfg, batches, local_algo):
    """``round_fn(*round_args(b))`` is ``run_round(b)``, on one trace: the
    program a caller lowers from ``round_args`` is the one rounds run."""
    exp = Experiment.from_config(run_cfg, allocator="EB", local_algo=local_algo)
    ids = np.arange(CLIENTS)[::-1]
    out = exp.round_fn(*exp.round_args(batches, client_ids=ids))
    res = exp.run_round(batches, client_ids=ids)
    assert exp.trace_count == 1
    for a, b in zip(jax.tree.leaves((out[0], out[2:])),
                    jax.tree.leaves((res.state, exp.algo_state))):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_weighted_aggregation_matters(run_cfg, batches):
    """Non-uniform D_k weights must change the aggregated update."""
    exp = Experiment.from_config(run_cfg, allocator="EB")
    skew = np.zeros(CLIENTS)
    skew[0] = 1.0
    exp.net.D_k[:] = CLIENTS * skew + 1e-9  # all mass on client 0
    res_skew = exp.run_round(batches)

    uni = Experiment.from_config(run_cfg, allocator="EB")
    res_uni = uni.run_round(batches)
    diffs = [float(jnp.max(jnp.abs(a - b))) for a, b in zip(
        jax.tree.leaves(res_skew.state.lora_s), jax.tree.leaves(res_uni.state.lora_s))]
    assert max(diffs) > 0


# ---------------------------------------------------------------------------
# Aggregator strategies
# ---------------------------------------------------------------------------


def _stacked(rows):
    return {"w": jnp.asarray(rows, jnp.float32)}


def test_coordinate_median_ignores_outlier():
    tree = _stacked([[1.0, 1.0], [1.1, 0.9], [0.9, 1.1], [1e6, -1e6]])
    med = aggregators.get("median")(tree)
    np.testing.assert_allclose(np.asarray(med["w"]), [1.0, 1.0], atol=0.11)


def test_trimmed_mean_ignores_outlier():
    tree = _stacked([[1.0, 1.0], [1.1, 0.9], [0.9, 1.1], [1e6, -1e6]])
    tm = aggregators.get("trimmed_mean")(tree)
    assert np.all(np.abs(np.asarray(tm["w"])) < 2.0)


def test_robust_aggregators_respect_mask():
    """A masked-out straggler must not influence the order statistics."""
    tree = _stacked([[1.0], [2.0], [3.0], [1e9]])
    mask = jnp.array([1.0, 1.0, 1.0, 0.0])
    med = aggregators.get("median")(tree, mask=mask)
    np.testing.assert_allclose(np.asarray(med["w"]), [2.0])
    tm = aggregators.get("trimmed_mean")(tree, mask=mask)
    assert float(np.abs(np.asarray(tm["w"]))[0]) < 10.0


def test_fedavg_weighted_matches_manual():
    tree = _stacked([[2.0], [4.0], [6.0], [8.0]])
    w = jnp.array([1.0, 1.0, 2.0, 0.0])
    out = federated.fedavg(tree, weights=w)
    np.testing.assert_allclose(np.asarray(out["w"]), [(2 + 4 + 12) / 4.0])


# ---------------------------------------------------------------------------
# Compressors
# ---------------------------------------------------------------------------


def test_compressor_bits_accounting():
    none, int8 = get_compressor("none"), get_compressor("int8")
    topk = get_compressor("topk", fraction=0.1)
    n = 1 << 16
    assert none.bits(n) == n * 32
    assert int8.bits(n) == n * 8 + 32
    assert topk.bits(n) < 0.2 * n * 32
    assert none.ratio == 1.0 and int8.ratio == 0.25


def test_compressor_rescales_delay_model(run_cfg):
    full = Experiment.from_config(run_cfg, allocator="EB")
    comp = Experiment.from_config(run_cfg, allocator="EB", compressor="int8")
    assert comp.fcfg.s_bits == pytest.approx(0.25 * full.fcfg.s_bits)
    # cheaper uplink -> no-worse optimised latency
    assert comp.alloc.T <= full.alloc.T * (1 + 1e-9)


def test_int8_roundtrip_error_small():
    x = jax.random.normal(jax.random.PRNGKey(0), (64, 64))
    y = get_compressor("int8").apply(x)
    assert float(jnp.max(jnp.abs(x - y))) <= float(jnp.max(jnp.abs(x))) / 127.0 + 1e-6


def test_split_reports_codec_uplink_bits(run_cfg, batches):
    """split_value_and_grad's info reflects the codec's exact uplink volume."""
    from repro.core import lora as lora_lib, split
    from repro.models import transformer as T

    cfg = run_cfg.model
    params, axes = T.init_params(cfg, key=jax.random.PRNGKey(0))
    lora, _ = lora_lib.init_lora(params, axes, cfg, key=jax.random.PRNGKey(1))
    lc, ls = lora_lib.split_client_server(lora, 1)
    batch = jax.tree.map(lambda x: x[0], batches)
    _, _, _, dense = split.split_value_and_grad(params, lc, ls, batch, cfg, 1)
    _, _, _, comp = split.split_value_and_grad(params, lc, ls, batch, cfg, 1,
                                              compressor=get_compressor("int8"))
    assert dense["smashed_bits_uplink"] == dense["smashed_bytes"] * 8
    # 8 bits/elem (f32 payload = 4 bytes/elem) + one f32 scale
    assert comp["smashed_bits_uplink"] == dense["smashed_bytes"] * 2 + 32


def test_timing_priced_at_training_eta(run_cfg):
    """RoundResult timing must reflect the η the rounds actually run with."""
    slow = Experiment.from_config(run_cfg, allocator="EB", eta=0.2)
    fast = Experiment.from_config(run_cfg, allocator="EB", eta=0.8)
    # fewer local iterations at larger η -> cheaper simulated round
    assert fast.wall_clock_per_round < slow.wall_clock_per_round


def test_compressed_training_round_stays_finite(run_cfg, batches):
    for codec in ("int8", "randk"):
        exp = Experiment.from_config(run_cfg, allocator="EB", compressor=codec)
        res = exp.run_round(batches)
        assert np.isfinite(float(res.metrics["loss_local_final"]))
        for leaf in jax.tree.leaves(res.state.lora_c):
            assert bool(jnp.all(jnp.isfinite(leaf)))
