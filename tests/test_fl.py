"""Local-update algorithm registry (7th axis) + data-heterogeneity
workloads: registry contracts, the gd bit-compat golden, FedProx/SCAFFOLD
semantics (μ=0 degeneracy, variate updates, straggler mask-invariance),
single-jit-trace bounds, scaffold checkpoint/resume identity, workload
purity in (seed, client), and the local-algo sweep dimension."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _golden import GOLDEN_LOSSES, GOLDEN_ROUND_TIMES, GOLDEN_TOTAL_TIME

from repro.api import (Experiment, get_local_algo, get_workload, local_algos,
                       workloads)
from repro.config import (FedsLLMConfig, LoRAConfig, RunConfig, SHAPES,
                          get_arch, smoke_variant)
from repro.core import delay_model as dm
from repro.core import federated, fedsllm, split
from repro.core import lora as lora_lib
from repro.data.tokens import TokenStream
from repro.fl.local_algos import FedProxLocal, GDLocal, ScaffoldLocal
from repro.fl.workloads import (DirichletDomainWorkload, IIDWorkload,
                                LengthSkewWorkload, QuantitySkewWorkload)
from repro.sim.campaign import stream_batcher
from repro.sim.sweep import run_sweep

K = 6
COHORT = 4


@pytest.fixture(scope="module")
def run_cfg():
    cfg = smoke_variant(get_arch("fedsllm-100m")).replace(
        lora=LoRAConfig(rank=4, alpha=8.0))
    return RunConfig(model=cfg, shape=SHAPES["train_4k"],
                     fedsllm=FedsLLMConfig(num_clients=K))


@pytest.fixture(scope="module")
def stream(run_cfg):
    return TokenStream(2, 32, run_cfg.model.vocab_size, seed=0)


def _fresh(run_cfg, **kw):
    kw.setdefault("allocator", "EB")
    kw.setdefault("eta", 0.5)
    return Experiment.from_config(run_cfg, **kw)


def _campaign(exp, stream, rounds=3):
    deadline = float(np.quantile(exp.timing.total, 0.7))
    return exp.run(num_rounds=rounds, stream=stream, cohort=COHORT,
                   deadline=deadline, resample_channel=True)


@pytest.fixture(scope="module")
def gd_run(run_cfg, stream):
    exp = _fresh(run_cfg)
    return exp, _campaign(exp, stream)


@pytest.fixture(scope="module")
def scaffold_run(run_cfg, stream):
    exp = _fresh(run_cfg, local_algo="scaffold")
    return exp, _campaign(exp, stream)


def _lora_leaves(state):
    return jax.tree.leaves((state.lora_c, state.lora_s))


# ---------------------------------------------------------------------------
# Registry contract (the seventh axis mirrors the other six)
# ---------------------------------------------------------------------------


def test_local_algo_registry_contents():
    assert {"gd", "fedprox", "scaffold"} <= set(local_algos.names())


def test_workload_registry_contents():
    assert {"iid", "quantity-skew", "length-skew",
            "dirichlet"} <= set(workloads.names())


def test_unknown_names_list_known_names():
    with pytest.raises(KeyError) as exc:
        get_local_algo("definitely-not-registered")
    for name in local_algos.names():
        assert name in str(exc.value)
    with pytest.raises(KeyError) as exc:
        get_workload("definitely-not-registered")
    for name in workloads.names():
        assert name in str(exc.value)


def test_unknown_axes_in_experiment(run_cfg):
    with pytest.raises(KeyError, match="unknown local_algo"):
        Experiment.from_config(run_cfg, local_algo="nope")
    with pytest.raises(KeyError, match="unknown workload"):
        Experiment.from_config(run_cfg, workload="nope")


def test_getters_accept_instances_and_kwargs():
    prox = FedProxLocal(mu=0.3)
    assert get_local_algo(prox) is prox
    assert get_local_algo("fedprox", mu=0.7).mu == 0.7
    assert isinstance(get_local_algo(ScaffoldLocal), ScaffoldLocal)
    wl = QuantitySkewWorkload(alpha=0.1)
    assert get_workload(wl) is wl
    assert get_workload("dirichlet", alpha=0.2).alpha == 0.2
    with pytest.raises(TypeError):
        get_local_algo(prox, mu=0.5)


def test_params_feed_checkpoint_identity():
    assert GDLocal().params() == {}
    assert FedProxLocal(mu=0.25).params() == {"mu": 0.25}
    assert IIDWorkload().params() == {}
    assert "alpha" in DirichletDomainWorkload().params()


# ---------------------------------------------------------------------------
# Lemma 2 dedupe (satellite): fedsllm delegates to delay_model
# ---------------------------------------------------------------------------


def test_local_iteration_count_consistent_with_delay_model():
    import math
    for fcfg in (FedsLLMConfig(), FedsLLMConfig(num_clients=K, L_smooth=1.5)):
        for eta in (0.05, 0.1, 0.3, 0.5, 0.7, 0.9, 0.95):
            got = fedsllm.local_iteration_count(fcfg, eta)
            assert got == max(1, math.ceil(dm.local_iters(fcfg, eta)))
            # the pre-dedupe closed form, for the avoidance of drift
            assert got == max(1, math.ceil(dm.lemma_v(fcfg)
                                           * math.log2(1.0 / eta)))


# ---------------------------------------------------------------------------
# gd bit-compat golden (tests/_golden.py, shared with tests/test_topology.py)
# ---------------------------------------------------------------------------


def test_gd_campaign_matches_pre_registry_golden(gd_run):
    """The default local algorithm IS the legacy inner loop — the pre-PR
    star/blockfade trajectory reproduces exactly."""
    exp, res = gd_run
    assert exp.local_algo.name == "gd" and exp.workload.name == "iid"
    assert exp.algo_state is None
    np.testing.assert_allclose([r.round_time for r in res.records],
                               GOLDEN_ROUND_TIMES, rtol=1e-12)
    np.testing.assert_allclose(res.total_time, GOLDEN_TOTAL_TIME, rtol=1e-12)
    np.testing.assert_allclose(res.history("loss_round_start"),
                               GOLDEN_LOSSES, rtol=1e-5)
    assert exp.trace_count == 1


def test_fedprox_mu0_is_gd_bit_exact(run_cfg, stream, gd_run):
    """μ = 0 removes the proximal pull: the trajectory must be bit-identical
    to gd (x + 0·h == x in IEEE arithmetic)."""
    exp = _fresh(run_cfg, local_algo=FedProxLocal(mu=0.0))
    res = _campaign(exp, stream)
    for a, b in zip(_lora_leaves(res.state), _lora_leaves(gd_run[1].state)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_scaffold_round0_equals_gd(scaffold_run, gd_run):
    """Variates start at zero, so scaffold's first round is gd's first round
    exactly; corrections only alter the trajectory from round 1 on."""
    _, s_res = scaffold_run
    _, g_res = gd_run
    for k, v in s_res.records[0].metrics.items():
        np.testing.assert_array_equal(np.asarray(v),
                                      np.asarray(g_res.records[0].metrics[k]))
    np.testing.assert_array_equal(
        np.asarray(s_res.records[1].metrics["loss_round_start"]),
        np.asarray(g_res.records[1].metrics["loss_round_start"]))


def test_scaffold_single_trace_and_variate_shape(scaffold_run):
    exp, _ = scaffold_run
    assert exp.trace_count == 1
    leaves = jax.tree.leaves(exp.algo_state)
    assert all(x.shape[0] == K for x in leaves)
    # three rounds of cohort-4 participation left *some* variate nonzero
    assert any(float(jnp.max(jnp.abs(x))) > 0 for x in leaves)


def test_fedprox_single_trace(run_cfg, stream):
    exp = _fresh(run_cfg, local_algo="fedprox")
    _campaign(exp, stream)
    assert exp.trace_count == 1 and exp.algo_state is None


# ---------------------------------------------------------------------------
# SCAFFOLD variate semantics
# ---------------------------------------------------------------------------


def _round_batches(stream, ids):
    per = [stream.batch_at(int(k)) for k in ids]
    return jax.tree.map(lambda *xs: jnp.stack(xs, axis=0), *per)


def test_scaffold_mask_invariance_of_variates(run_cfg, stream):
    """Dropped clients' control variates must not update: a straggler that
    missed the round learned nothing, and clients outside the cohort were
    never asked."""
    exp = _fresh(run_cfg, local_algo="scaffold")
    ids = np.array([0, 1, 2, 3])
    mask = jnp.asarray([1.0, 1.0, 0.0, 1.0])
    exp.run_round(_round_batches(stream, ids), mask=mask, client_ids=ids)
    rows = {k: [np.asarray(x[k]) for x in jax.tree.leaves(exp.algo_state)]
            for k in range(K)}
    for k in (0, 1, 3):  # participated and survived: variates moved off 0
        assert any(np.max(np.abs(r)) > 0 for r in rows[k])
    for k in (2, 4, 5):  # masked straggler + out-of-cohort: untouched
        for r in rows[k]:
            np.testing.assert_array_equal(r, np.zeros_like(r))
    # a second round with the roles flipped updates exactly the newcomers
    before = [np.asarray(x) for x in jax.tree.leaves(exp.algo_state)]
    exp.run_round(_round_batches(stream, ids),
                  mask=jnp.asarray([0.0, 1.0, 1.0, 1.0]), client_ids=ids)
    after = [np.asarray(x) for x in jax.tree.leaves(exp.algo_state)]
    for b, a in zip(before, after):
        np.testing.assert_array_equal(b[0], a[0])  # masked this time: frozen
    assert any(np.max(np.abs(x[2])) > 0 for x in after)  # client 2 now moved
    assert exp.trace_count == 1  # masks and ids are value-only


def test_scaffold_option2_update_rule():
    """c_k⁺ = c_k − c̄ − h/(I_loc·δ), with the mask blending old and new."""
    algo = ScaffoldLocal()
    ctrl = ({"w": jnp.asarray([[1.0], [2.0]])},)
    cbar = ({"w": jnp.asarray([0.5])},)
    h = ({"w": jnp.asarray([[4.0], [8.0]])},)
    upd = algo.update_variates(ctrl, cbar, h, None, I_loc=4, delta=0.5)
    np.testing.assert_allclose(np.asarray(upd[0]["w"]),
                               [[1.0 - 0.5 - 2.0], [2.0 - 0.5 - 4.0]])
    masked = algo.update_variates(ctrl, cbar, h, jnp.asarray([1.0, 0.0]),
                                  I_loc=4, delta=0.5)
    np.testing.assert_allclose(np.asarray(masked[0]["w"]), [[-1.5], [2.0]])


def test_scaffold_checkpoint_resume_bit_identical(run_cfg, stream, tmp_path):
    """The acceptance bar: an interrupted scaffold campaign resumes with the
    exact variates and replays the remaining rounds bit-identically."""
    kw = dict(stream=stream, cohort=COHORT, resample_channel=True)
    mk = lambda: _fresh(run_cfg, local_algo="scaffold")  # noqa: E731

    full = mk()
    res_full = full.run(num_rounds=4, **kw)

    ck = str(tmp_path / "scaffold_ck")
    part = mk()
    part.run(num_rounds=2, checkpoint_dir=ck, checkpoint_every=2, **kw)
    resumed = mk()
    res_res = resumed.run(num_rounds=4, checkpoint_dir=ck, resume=True, **kw)

    for a, b in zip(_lora_leaves(res_full.state), _lora_leaves(res_res.state)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    for a, b in zip(jax.tree.leaves(full.algo_state),
                    jax.tree.leaves(resumed.algo_state)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert [r.round for r in res_res.records] == [2, 3]
    np.testing.assert_allclose(res_res.total_time, res_full.total_time,
                               rtol=1e-12)

    # a different local algorithm refuses the checkpoint, like a different
    # schedule or scenario would
    with pytest.raises(ValueError, match="different campaign"):
        _fresh(run_cfg).run(num_rounds=4, checkpoint_dir=ck, resume=True, **kw)
    # ... and so do different hyper-parameters of the same algorithm
    with pytest.raises(ValueError, match="different campaign"):
        _fresh(run_cfg, local_algo=FedProxLocal(mu=0.0)).run(
            num_rounds=4, checkpoint_dir=ck, resume=True, **kw)


# ---------------------------------------------------------------------------
# The round's first local step, taken from ḡ (no split pass at h = 0)
# ---------------------------------------------------------------------------

CUT = 1


def _eta_for(fcfg, I_loc):
    """A training η whose Lemma 2 count is ``I_loc``."""
    eta = 2.0 ** (-(I_loc - 0.5) / dm.lemma_v(fcfg))
    assert fedsllm.local_iteration_count(fcfg, eta) == I_loc
    return eta


def _unpeeled_round(cfg, fcfg, eta, algo, compressor=None):
    """The round as it ran before its first local step was peeled: the
    round-start pass, then I_loc local steps from h = 0, each a split pass
    (the first recomputing the round-start pass).  Default aggregation, the
    first C population rows as the cohort."""
    I_loc = fedsllm.local_iteration_count(fcfg, eta)
    xi, delta = fcfg.xi, fcfg.delta
    add = lambda a, b: jax.tree.map(jnp.add, a, b)  # noqa: E731

    def vg(base, lc, ls, batch):
        loss, dc, ds, _ = split.split_value_and_grad(base, lc, ls, batch, cfg, CUT,
                                                     compressor=compressor)
        return loss, (dc, ds)

    def round_fn(state, batches, mask, algo_state):
        C = jax.tree.leaves(batches)[0].shape[0]
        loss0, g0 = jax.vmap(lambda b: vg(state.base, state.lora_c, state.lora_s, b))(
            batches)
        gbar = federated.fedavg(g0, mask=mask)
        ctrl = ctrl_bar = None
        if algo.stateful:
            ctrl_bar = jax.tree.map(lambda x: jnp.mean(x, axis=0), algo_state)
            ctrl = jax.tree.map(lambda x: x[:C], algo_state)

        def client(gk, batch, ck):
            def body(h, _):
                loss, g = vg(state.base, add(state.lora_c, h[0]), add(state.lora_s, h[1]),
                             batch)
                g = jax.tree.map(lambda a, b, c: a - b + xi * c, g, gk, gbar)
                g = algo.correct(g, h, ck, ctrl_bar)
                return jax.tree.map(lambda x, gx: x - delta * gx, h, g), loss

            h0 = jax.tree.map(jnp.zeros_like, (state.lora_c, state.lora_s))
            h, losses = jax.lax.scan(body, h0, None, length=I_loc)
            return h, losses[-1]

        (h_c, h_s), last = jax.vmap(client)(g0, batches, ctrl)
        new_state = state._replace(
            lora_c=federated.apply_update(state.lora_c, federated.fedavg(h_c, mask=mask)),
            lora_s=federated.apply_update(state.lora_s, federated.fedavg(h_s, mask=mask)),
            round=state.round + 1)
        metrics = {"loss_round_start": jnp.mean(loss0),
                   "loss_local_final": jnp.mean(last),
                   "h_c_norm": lora_lib.delta_norm(h_c)}
        variates = None
        if algo.stateful:
            upd = algo.update_variates(ctrl, ctrl_bar, (h_c, h_s), mask, I_loc, delta)
            variates = jax.tree.map(lambda full, u: full.at[:C].set(u), algo_state, upd)
        return new_state, metrics, variates

    return round_fn


def _perturbed(tree, key, scale):
    leaves, treedef = jax.tree.flatten(tree)
    keys = jax.random.split(key, len(leaves))
    return treedef.unflatten([x + scale * jax.random.normal(k, x.shape, x.dtype)
                              for x, k in zip(leaves, keys)])


def _rel_gap(new, old):
    new, old = np.asarray(new, np.float64), np.asarray(old, np.float64)
    return float(np.linalg.norm(new - old) / max(np.linalg.norm(old), 1e-30))


PEEL_VARIANTS = {
    "gd": dict(algo="gd"),
    "fedprox": dict(algo=FedProxLocal(mu=0.1)),
    "scaffold": dict(algo="scaffold"),
    "int8": dict(algo="gd", compressor="int8"),
    "mask": dict(algo="gd", mask=[1.0, 1.0, 0.0, 1.0]),
}


@pytest.mark.parametrize("I_loc", [1, 2, 3])
@pytest.mark.parametrize("variant", list(PEEL_VARIANTS))
def test_peeled_round_matches_unpeeled(run_cfg, stream, variant, I_loc):
    """Taking the first local step from ḡ gives the round the unpeeled one
    gave — state, metrics and variates — up to the float32 noise of the old
    first step's ∇F_k(Δw) − ∇F_k(Δw), two separately fused passes."""
    from repro.api.compressors import get_compressor

    spec = PEEL_VARIANTS[variant]
    cfg, fcfg = run_cfg.model, run_cfg.fedsllm
    algo = get_local_algo(spec["algo"])
    compressor = get_compressor(spec["compressor"]) if "compressor" in spec else None
    eta = _eta_for(fcfg, I_loc)
    state, _ = fedsllm.init_state(cfg, CUT, key=jax.random.PRNGKey(0))
    # off the zero-B start, so both adapter factors get gradients
    state = state._replace(
        lora_c=_perturbed(state.lora_c, jax.random.PRNGKey(1), 0.05),
        lora_s=_perturbed(state.lora_s, jax.random.PRNGKey(2), 0.05))
    algo_state = (_perturbed(algo.init_variates((state.lora_c, state.lora_s), K),
                             jax.random.PRNGKey(3), 0.01) if algo.stateful else None)
    batches = _round_batches(stream, np.arange(COHORT))
    mask = jnp.asarray(spec["mask"]) if "mask" in spec else None

    peeled = fedsllm.build_round_fn(cfg, fcfg, CUT, eta, compressor=compressor,
                                    local_algo=algo)
    assert peeled.split_passes == I_loc
    if algo.stateful:
        new = jax.jit(peeled)(state, batches, mask, None, None, None, None, algo_state)
    else:
        new = jax.jit(peeled)(state, batches, mask) + (None,)
    old = jax.jit(_unpeeled_round(cfg, fcfg, eta, algo, compressor))(
        state, batches, mask, algo_state)

    for tree in (lambda o: (o[0].lora_c, o[0].lora_s), lambda o: o[2]):
        for a, b in zip(jax.tree.leaves(tree(new)), jax.tree.leaves(tree(old)), strict=True):
            assert _rel_gap(a, b) < 1e-4
    assert new[1].keys() == old[1].keys()
    for k in old[1]:
        assert _rel_gap(new[1][k], old[1][k]) < 1e-4, k
    if I_loc == 1:
        np.testing.assert_array_equal(np.asarray(new[1]["loss_local_final"]),
                                      np.asarray(new[1]["loss_round_start"]))


@pytest.mark.parametrize("I_loc", [1, 3])
def test_round_runs_cohort_times_I_loc_split_passes(run_cfg, stream, monkeypatch, I_loc):
    """A round runs cohort × I_loc split passes (the round-start pass gives
    the first local step), counted as they run on the device."""
    ran = []
    vg = split.split_value_and_grad

    def counted(*a, **kw):
        out = vg(*a, **kw)
        jax.debug.callback(lambda _: ran.append(1), out[0])  # one per client's pass
        return out

    monkeypatch.setattr(split, "split_value_and_grad", counted)
    cfg, fcfg = run_cfg.model, run_cfg.fedsllm
    round_fn = fedsllm.build_round_fn(cfg, fcfg, CUT, _eta_for(fcfg, I_loc))
    assert round_fn.split_passes == I_loc
    state, _ = fedsllm.init_state(cfg, CUT, key=jax.random.PRNGKey(0))
    out = jax.jit(round_fn)(state, _round_batches(stream, np.arange(COHORT)))
    jax.block_until_ready(out)
    jax.effects_barrier()
    assert len(ran) == COHORT * I_loc


# ---------------------------------------------------------------------------
# Workloads: purity in (seed, client), iid bit-compat, skew semantics
# ---------------------------------------------------------------------------


def test_iid_workload_matches_legacy_stream_batcher(stream):
    legacy = stream_batcher(stream, K)
    wl = IIDWorkload().batcher(stream, K)
    ids = np.array([0, 3, 5])
    for r in (0, 2):
        for a, b in zip(jax.tree.leaves(legacy(r, ids)),
                        jax.tree.leaves(wl(r, ids))):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("name,kw", [
    ("iid", {}),
    ("quantity-skew", {}),
    ("length-skew", {}),
    ("dirichlet", {"domain_pool": 8}),
])
def test_workload_pure_in_seed_and_client(stream, name, kw):
    """Client k's round-r batch never depends on who else was sampled, and
    rebuilding the batcher from the same (stream, K) replays it exactly."""
    wl = get_workload(name, **kw)
    fn_a = wl.batcher(stream, K)
    fn_b = get_workload(name, **kw).batcher(stream, K)
    full = np.arange(K)
    sub = np.array([1, 4])
    for r in (0, 3):
        batch_full = fn_a(r, full)
        batch_sub = fn_a(r, sub)
        for i, k in enumerate(sub):
            for a, b in zip(jax.tree.leaves(batch_sub),
                            jax.tree.leaves(batch_full)):
                np.testing.assert_array_equal(np.asarray(a[i]),
                                              np.asarray(b[k]))
        for a, b in zip(jax.tree.leaves(batch_full),
                        jax.tree.leaves(fn_b(r, full))):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_quantity_skew_pools_cycle(stream):
    wl = QuantitySkewWorkload(alpha=0.3, pool_rounds=4)
    sizes = wl.pool_sizes(stream.seed, K)
    assert sizes.min() >= 1 and len(sizes) == K
    fn = wl.batcher(stream, K)
    k = int(np.argmin(sizes))
    n = int(sizes[k])
    a = fn(0, np.array([k]))
    b = fn(n, np.array([k]))  # one full cycle later: same batch again
    np.testing.assert_array_equal(np.asarray(a["tokens"]),
                                  np.asarray(b["tokens"]))
    # skewed draws give unequal pools on this seed
    assert sizes.max() > sizes.min()


def test_length_skew_truncates_loss_mask(stream):
    wl = LengthSkewWorkload(min_frac=0.25)
    fn = wl.batcher(stream, K)
    iid = IIDWorkload().batcher(stream, K)
    ids = np.arange(K)
    got, ref = fn(1, ids), iid(1, ids)
    np.testing.assert_array_equal(np.asarray(got["tokens"]),
                                  np.asarray(ref["tokens"]))
    fracs = wl.length_fracs(stream.seed, K)
    lengths = np.maximum(1, np.ceil(fracs * stream.seq)).astype(int)
    mask = np.asarray(got["mask"])
    for k in range(K):
        assert (mask[k].sum(axis=-1) == lengths[k]).all()
    assert len(set(lengths.tolist())) > 1  # genuinely heterogeneous


def test_dirichlet_workload_partitions_domains(stream):
    wl = DirichletDomainWorkload(alpha=0.3, num_domains=4, domain_pool=8)
    shards = wl.client_shards(stream.seed, K)
    allidx = np.sort(np.concatenate(shards))
    np.testing.assert_array_equal(allidx, np.arange(4 * 8))
    assert min(len(s) for s in shards) >= 1
    streams = wl.domain_streams(stream)
    assert len({s.seed for s in streams}) == 4
    assert len({s.structure for s in streams}) == 4
    # different stream seeds give different partitions (purity in seed)
    other = wl.client_shards(stream.seed + 1, K)
    assert any(not np.array_equal(a, b) for a, b in zip(shards, other))


def test_non_iid_workload_requires_stream(run_cfg, stream):
    exp = _fresh(run_cfg, workload="dirichlet")
    fixed = _round_batches(stream, np.arange(COHORT))
    with pytest.raises(ValueError, match="workload"):
        exp.run(num_rounds=1, batches=fixed)


def test_describe_names_the_new_axes(run_cfg):
    exp = _fresh(run_cfg, local_algo="fedprox", workload="length-skew")
    assert "algo=fedprox" in exp.describe()
    assert "workload=length-skew" in exp.describe()


# ---------------------------------------------------------------------------
# Sweep dimension
# ---------------------------------------------------------------------------


def test_sweep_local_algo_axis(run_cfg, stream):
    res = run_sweep(run_cfg, 2, scenarios=("blockfade",), allocators=("EB",),
                    local_algos=("gd", "fedprox"), stream=stream,
                    cohort=COHORT, exp_overrides={"eta": 0.5})
    assert {r["local_algo"] for r in res.records} == {"gd", "fedprox"}
    assert all(r["workload"] == "iid" for r in res.records)
    rows = res.cell("blockfade", "EB", local_algo="fedprox")
    assert [r["round"] for r in rows] == [0, 1]
    with pytest.raises(ValueError, match="local_algo"):
        res.cell("blockfade", "EB")
    gain = res.local_algo_gain()
    assert set(gain) == {"blockfade/iid/fedprox"}
    assert len(res.summary()) == 2
    for row in res.summary():
        assert row["trace_count"] == 1


def test_sweep_non_iid_without_stream_raises(run_cfg):
    with pytest.raises(ValueError, match="non-iid"):
        run_sweep(run_cfg, 1, workloads=("dirichlet",), batches={})
