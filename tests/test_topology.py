"""Hierarchical topology subsystem: registry contract, attachment/
localization invariants, per-hop delay composition, per-edge-cell
allocation, two-tier aggregation inside the single-jit-trace contract,
checkpoint topology guards, the star bit-compat golden, and the
topology-dimension sweep."""

import dataclasses

import jax
import numpy as np
import pytest
from _golden import (GOLDEN_DEADLINE, GOLDEN_LOSSES, GOLDEN_ROUND_TIMES,
                     GOLDEN_TOTAL_TIME)

from repro.api import Experiment, get_topology, topologies
from repro.config import (FedsLLMConfig, LoRAConfig, RunConfig, SHAPES,
                          get_arch, smoke_variant)
from repro.api.allocators import get_allocator
from repro.core import delay_model as dm
from repro.core import fedsllm
from repro.core.resource_alloc import Allocation
from repro.net import allocation
from repro.net.allocation import cell_latency, solve_wait_aware, subnetwork
from repro.net.topology import (EdgeAggTopology, EdgeCloudTopology,
                                HierTopology, RelayTopology, Topology)
from repro.sim import events
from repro.sim.scenario import DriftScenario, get_scenario
from repro.sim.sweep import run_sweep

K = 6
COHORT = 4


@pytest.fixture(scope="module")
def fcfg():
    return FedsLLMConfig(num_clients=K)


@pytest.fixture(scope="module")
def run_cfg():
    cfg = smoke_variant(get_arch("fedsllm-100m")).replace(
        lora=LoRAConfig(rank=4, alpha=8.0))
    return RunConfig(model=cfg, shape=SHAPES["train_4k"],
                     fedsllm=FedsLLMConfig(num_clients=K))


@pytest.fixture(scope="module")
def stream(run_cfg):
    from repro.data.tokens import TokenStream

    return TokenStream(2, 32, run_cfg.model.vocab_size, seed=0)


def _fresh(run_cfg, **kw):
    kw.setdefault("allocator", "EB")
    kw.setdefault("eta", 0.5)
    return Experiment.from_config(run_cfg, **kw)


# ---------------------------------------------------------------------------
# Registry contract (the fifth axis mirrors the other four)
# ---------------------------------------------------------------------------


def test_topology_registry_contents():
    assert {"star", "edge-cloud", "edge-agg", "relay"} <= set(topologies.names())


def test_unknown_topology_lists_known_names():
    with pytest.raises(KeyError) as exc:
        get_topology("definitely-not-registered")
    for name in topologies.names():
        assert name in str(exc.value)


def test_unknown_topology_in_experiment(run_cfg):
    with pytest.raises(KeyError, match="unknown topology"):
        Experiment.from_config(run_cfg, topology="nope")


def test_get_topology_accepts_instances():
    topo = EdgeCloudTopology(num_edges=4)
    assert get_topology(topo) is topo
    assert isinstance(get_topology("edge-cloud"), EdgeCloudTopology)


def test_topology_parameter_validation():
    with pytest.raises(ValueError, match="num_edges"):
        EdgeCloudTopology(num_edges=0)
    with pytest.raises(ValueError, match="backhaul_bps"):
        RelayTopology(backhaul_bps=0.0)


# ---------------------------------------------------------------------------
# Attachment + localization
# ---------------------------------------------------------------------------


def test_edge_positions_deterministic_ring(fcfg):
    topo = EdgeCloudTopology(num_edges=3)
    exy = topo.edge_xy(fcfg)
    assert exy.shape == (3, 2)
    np.testing.assert_allclose(np.linalg.norm(exy, axis=1), fcfg.area_m / 4.0)
    np.testing.assert_array_equal(exy, topo.edge_xy(fcfg))


def test_attach_picks_nearest_edge(fcfg):
    topo = EdgeCloudTopology(num_edges=3)
    net = get_scenario("geo-blockfade").round_network(fcfg, 0, 0)
    assign = topo.attach(fcfg, net)
    assert assign.shape == (K,)
    d = np.linalg.norm(net.xy[:, None, :] - topo.edge_xy(fcfg)[None], axis=2)
    np.testing.assert_array_equal(assign, np.argmin(d, axis=1))


def test_localize_swaps_distance_term_keeps_shadowing(fcfg):
    """g' = g·10^((pl_bs − pl_edge)/10): the round's shadowing realisation
    survives localization, only the deterministic path loss moves."""
    topo = EdgeCloudTopology(num_edges=2)
    net = get_scenario("geo-blockfade").round_network(fcfg, 0, 1)
    loc, assign = topo.localize(fcfg, net)
    ratio = dm.db_to_lin(net.pl_db - loc.pl_db)
    np.testing.assert_allclose(loc.g_c, net.g_c * ratio, rtol=1e-12)
    np.testing.assert_allclose(loc.g_s, net.g_s * ratio, rtol=1e-12)
    np.testing.assert_array_equal(loc.xy, net.xy)  # geometry untouched
    # edge path loss is the path loss to the attached edge
    exy = topo.edge_xy(fcfg)[assign]
    d_km = np.maximum(np.linalg.norm(net.xy - exy, axis=1), 1.0) / 1000.0
    np.testing.assert_allclose(
        loc.pl_db, fcfg.pathloss_const_db + fcfg.pathloss_exp * np.log10(d_km))


def test_hier_topology_refuses_geometry_free_scenarios(run_cfg):
    """The legacy blockfade/frozen draws carry no positions — attaching to
    an edge is meaningless and must fail loudly."""
    for scenario in ("blockfade", "frozen"):
        with pytest.raises(ValueError, match="geometry"):
            Experiment.from_config(run_cfg, topology="edge-cloud",
                                   scenario=scenario)


def test_drift_reattaches_clients_as_they_move(fcfg):
    """Under mobility the per-round attachment is recomputed from that
    round's geometry — clients hop cells."""
    topo = EdgeCloudTopology(num_edges=3)
    sc = DriftScenario(step_m=150.0)
    assigns = []
    for r in range(6):
        net, assign = events.localized_round_network(
            fcfg, 0, r, scenario=sc, topology=topo)
        assigns.append(assign)
    assert any(not np.array_equal(assigns[0], a) for a in assigns[1:])


def test_localized_round_network_without_topology(fcfg):
    net, assign = events.localized_round_network(
        fcfg, 0, 0, scenario=get_scenario("geo-blockfade"))
    assert assign is None and net.xy is not None


# ---------------------------------------------------------------------------
# Per-hop delay composition
# ---------------------------------------------------------------------------


def test_edge_cloud_timing_adds_cell_backhaul(run_cfg):
    exp = _fresh(run_cfg, topology="edge-cloud", scenario="geo-blockfade")
    topo, assign = exp.topology, exp.assign
    wireless = (exp.timing.total - exp.timing.backhaul)
    counts = np.bincount(assign, minlength=topo.num_edges)
    expect = (counts * exp.fcfg.s_c_bits / topo.backhaul_bps)[assign]
    np.testing.assert_allclose(exp.timing.backhaul, expect, rtol=1e-12)
    np.testing.assert_allclose(
        wireless,
        exp.timing.compute + exp.timing.uplink_fed + exp.timing.uplink_main,
        rtol=1e-12)
    np.testing.assert_array_equal(exp.timing.edge_of, assign)


def test_edge_agg_backhaul_is_one_payload_per_edge(fcfg):
    """Pre-aggregation makes the backhaul load independent of cell size."""
    agg = EdgeAggTopology(num_edges=2, backhaul_bps=1e6)
    cloud = EdgeCloudTopology(num_edges=2, backhaul_bps=1e6)
    assign = np.array([0, 0, 0, 0, 1, 1])
    np.testing.assert_allclose(agg.backhaul_seconds(fcfg, assign, 0.5),
                               np.full(K, fcfg.s_c_bits / 1e6))
    expect = np.where(assign == 0, 4 * fcfg.s_c_bits, 2 * fcfg.s_c_bits) / 1e6
    np.testing.assert_allclose(cloud.backhaul_seconds(fcfg, assign, 0.5),
                               expect)


def test_relay_backhaul_scales_with_local_iterations(fcfg):
    """The relay forwards every local iteration's smashed activations, so
    its hop couples into η through Lemma 2's V(η)."""
    relay = RelayTopology(num_edges=1, backhaul_bps=1e6)
    assign = np.zeros(K, int)
    for eta in (0.3, 0.6):
        V = dm.local_iters(fcfg, eta)
        expect = K * (fcfg.s_c_bits + V * fcfg.s_bits) / 1e6
        np.testing.assert_allclose(relay.backhaul_seconds(fcfg, assign, eta),
                                   np.full(K, expect), rtol=1e-12)
    # more aggressive η (fewer local iters) shrinks the relay hop
    assert (relay.backhaul_seconds(fcfg, assign, 0.6)[0]
            < relay.backhaul_seconds(fcfg, assign, 0.3)[0])


def test_infinite_backhaul_degenerates_to_wireless_only(run_cfg):
    topo = EdgeCloudTopology(num_edges=2, backhaul_bps=np.inf)
    exp = _fresh(run_cfg, topology=topo, scenario="geo-blockfade")
    np.testing.assert_allclose(
        exp.timing.total,
        exp.timing.compute + exp.timing.uplink_fed + exp.timing.uplink_main)
    np.testing.assert_array_equal(exp.timing.backhaul, np.zeros(K))


# ---------------------------------------------------------------------------
# Per-edge-cell allocation
# ---------------------------------------------------------------------------


def test_subnetwork_keeps_full_bandwidth_pool(fcfg):
    net = get_scenario("geo-blockfade").round_network(fcfg, 0, 0)
    sub = subnetwork(net, np.array([1, 3]))
    assert sub.K == 2 and sub.B_c == net.B_c and sub.B_s == net.B_s
    np.testing.assert_array_equal(sub.g_c, net.g_c[[1, 3]])
    np.testing.assert_array_equal(sub.D_k, net.D_k[[1, 3]])


def test_cell_allocation_respects_per_cell_budgets(run_cfg):
    """Each edge owns an independent bandwidth pool: the solved bandwidths
    must fit the budget per cell (not just globally)."""
    exp = _fresh(run_cfg, eta=None, topology="edge-cloud",
                 scenario="geo-blockfade")
    for m in range(exp.topology.num_edges):
        members = exp.assign == m
        if not np.any(members):
            continue
        assert np.sum(exp.alloc.b_c[members]) <= exp.net.B_c * (1 + 1e-6)
        assert np.sum(exp.alloc.b_s[members]) <= exp.net.B_s * (1 + 1e-6)
    assert np.isfinite(exp.alloc.T) and exp.alloc.feasible


def test_proposed_beats_ba_in_every_cell(run_cfg):
    """The paper's 47.63%-style comparison, per edge cell: the per-cell
    Lemma-3 solve + topology-level η sweep must beat the unoptimised BA
    baseline in every non-empty cell."""
    kw = dict(eta=None, topology="edge-cloud", scenario="geo-blockfade")
    prop = _fresh(run_cfg, allocator="proposed", **kw)
    ba = _fresh(run_cfg, allocator="BA", **kw)
    np.testing.assert_array_equal(prop.assign, ba.assign)
    fcfg, topo = prop.fcfg, prop.topology
    T_prop = cell_latency(fcfg, prop.net, prop.alloc, prop.assign, topo,
                          prop.alloc.eta)
    T_ba = cell_latency(fcfg, ba.net, ba.alloc, ba.assign, topo,
                        ba.alloc.eta)
    for m in range(topo.num_edges):
        if np.isnan(T_prop[m]):
            continue
        assert T_prop[m] < T_ba[m], (m, T_prop, T_ba)
    assert prop.alloc.T < ba.alloc.T


# ---------------------------------------------------------------------------
# Two-tier aggregation inside the single-trace contract
# ---------------------------------------------------------------------------


def test_edge_agg_round_matches_flat_weighted_fedavg(run_cfg, stream):
    """Per-edge then cross-edge weighted fedavg == the flat reduction (up to
    float associativity) when weights are the D_k sizes — so edge-side
    pre-aggregation changes the traffic pattern, not the training math."""
    from repro.data.tokens import client_batches

    batches = client_batches(stream, 0, K)
    flat = _fresh(run_cfg, scenario="geo-blockfade")
    tiered = _fresh(run_cfg, scenario="geo-blockfade", topology="edge-agg")
    res_a = flat.run_round(batches)
    res_b = tiered.run_round(batches)
    np.testing.assert_allclose(
        float(res_a.metrics["loss_round_start"]),
        float(res_b.metrics["loss_round_start"]), rtol=1e-6)
    for a, b in zip(jax.tree.leaves((res_a.state.lora_c, res_a.state.lora_s)),
                    jax.tree.leaves((res_b.state.lora_c, res_b.state.lora_s))):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-6)


def test_edge_agg_campaign_single_trace_under_reattachment(run_cfg, stream):
    """The one-hot assignment matrix is a value-only argument: per-round
    re-attachment under mobility must never retrace the round function."""
    exp = _fresh(run_cfg, topology=EdgeAggTopology(num_edges=3),
                 scenario=DriftScenario(step_m=150.0))
    assigns = []
    res = exp.run(num_rounds=3, stream=stream, cohort=COHORT,
                  resample_channel=True,
                  on_round=lambda rec: assigns.append(exp.assign.copy()))
    assert res.num_rounds == 3
    assert exp.trace_count == 1  # the acceptance bar
    assert any(not np.array_equal(assigns[0], a) for a in assigns[1:])


# ---------------------------------------------------------------------------
# star: bit-identical to the pre-topology engine
# ---------------------------------------------------------------------------

# Golden trajectory (tests/_golden.py, shared with tests/test_fl.py).


def test_star_campaign_matches_pre_topology_golden(run_cfg, stream):
    """The default topology IS the legacy engine: simulator quantities
    reproduce the pre-topology trajectory exactly (the times were captured
    before repro.net existed), training losses to float tolerance."""
    exp = _fresh(run_cfg)
    assert exp.topology.name == "star" and exp.assign is None
    deadline = float(np.quantile(exp.timing.total, 0.7))
    np.testing.assert_allclose(deadline, GOLDEN_DEADLINE, rtol=1e-12)
    res = exp.run(num_rounds=3, stream=stream, cohort=COHORT,
                  deadline=deadline, resample_channel=True)
    np.testing.assert_allclose([r.round_time for r in res.records],
                               GOLDEN_ROUND_TIMES, rtol=1e-12)
    np.testing.assert_allclose(res.total_time, GOLDEN_TOTAL_TIME, rtol=1e-12)
    np.testing.assert_allclose(res.history("loss_round_start"),
                               GOLDEN_LOSSES, rtol=1e-5)
    assert res.topology == "star" and exp.trace_count == 1


def test_star_explicit_equals_default(run_cfg, stream):
    """Experiment() == Experiment(topology="star"), bit-exact."""
    kw = dict(stream=stream, cohort=COHORT, resample_channel=True)
    res_a = _fresh(run_cfg).run(num_rounds=2, **kw)
    res_b = _fresh(run_cfg, topology="star").run(num_rounds=2, **kw)
    assert res_a.total_time == res_b.total_time
    for ra_, rb in zip(res_a.records, res_b.records):
        assert ra_.metrics == rb.metrics
    for a, b in zip(jax.tree.leaves((res_a.state.lora_c, res_a.state.lora_s)),
                    jax.tree.leaves((res_b.state.lora_c, res_b.state.lora_s))):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ---------------------------------------------------------------------------
# Joint reallocation + checkpoints on hierarchical graphs
# ---------------------------------------------------------------------------


def test_edge_cloud_realloc_bounded_traces_and_resume(run_cfg, stream,
                                                      tmp_path):
    """The acceptance bar: an edge-cloud campaign with reallocate=True runs
    N rounds with trace_count ≤ len(eta_buckets), and checkpoint-resume is
    bit-identical (per-round re-attachment and per-cell re-solves replay
    exactly)."""
    kw = dict(stream=stream, cohort=COHORT, resample_channel=True,
              reallocate=True)
    mk = lambda: _fresh(run_cfg, eta=0.2, topology="edge-cloud",  # noqa: E731
                        scenario="geo-blockfade")
    exp = mk()
    full = exp.run(num_rounds=4, **kw)
    assert full.num_rounds == 4
    assert exp.trace_count <= len(exp.eta_buckets)
    for rec in full.records:
        assert rec.eta in exp.eta_buckets

    ckpt = str(tmp_path / "camp")
    mk().run(num_rounds=2, checkpoint_dir=ckpt, checkpoint_every=2, **kw)
    rest = mk().run(num_rounds=4, checkpoint_dir=ckpt, resume=True, **kw)
    assert [r.round for r in rest.records] == [2, 3]
    for ra_, rb in zip(full.records[2:], rest.records):
        assert ra_.metrics == rb.metrics and ra_.eta == rb.eta
    for a, b in zip(jax.tree.leaves((full.state.lora_c, full.state.lora_s)),
                    jax.tree.leaves((rest.state.lora_c, rest.state.lora_s))):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_resume_refuses_different_topology(run_cfg, stream, tmp_path):
    ckpt = str(tmp_path / "camp")
    kw = dict(stream=stream, cohort=COHORT, resample_channel=True)
    _fresh(run_cfg, topology="edge-cloud", scenario="geo-blockfade").run(
        num_rounds=2, checkpoint_dir=ckpt, checkpoint_every=2, **kw)
    with pytest.raises(ValueError, match="topology"):
        _fresh(run_cfg, topology="star", scenario="geo-blockfade").run(
            num_rounds=4, checkpoint_dir=ckpt, resume=True, **kw)
    # the same topology resumes fine
    res = _fresh(run_cfg, topology="edge-cloud", scenario="geo-blockfade").run(
        num_rounds=4, checkpoint_dir=ckpt, resume=True, **kw)
    assert [r.round for r in res.records] == [2, 3]


def test_resume_refuses_different_attachment_digest(run_cfg, stream,
                                                    tmp_path):
    """Same topology name, different graph (edge count) — the attachment
    digest catches what the name cannot."""
    ckpt = str(tmp_path / "camp")
    kw = dict(stream=stream, cohort=COHORT, resample_channel=True)
    _fresh(run_cfg, topology=EdgeCloudTopology(num_edges=2),
           scenario="geo-blockfade").run(
        num_rounds=2, checkpoint_dir=ckpt, checkpoint_every=2, **kw)
    with pytest.raises(ValueError, match="topo_digest"):
        _fresh(run_cfg, topology=EdgeCloudTopology(num_edges=3),
               scenario="geo-blockfade").run(
            num_rounds=4, checkpoint_dir=ckpt, resume=True, **kw)


def test_topology_digest_covers_params(run_cfg, fcfg):
    sc = get_scenario("geo-blockfade")
    assert (EdgeCloudTopology(num_edges=2).digest(fcfg, sc, 0)
            != EdgeCloudTopology(num_edges=3).digest(fcfg, sc, 0))
    assert (EdgeCloudTopology(backhaul_bps=1e6).digest(fcfg, sc, 0)
            != EdgeCloudTopology(backhaul_bps=1e9).digest(fcfg, sc, 0))
    # star's digest is parameter-free and never touches the scenario
    assert (Topology().digest(fcfg, sc, 0)
            == get_topology("star").digest(fcfg, sc, 1))


# ---------------------------------------------------------------------------
# Topology-dimension sweep
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def hier_sweep(run_cfg, stream):
    return run_sweep(run_cfg, 2, topologies=("star", "edge-cloud"),
                     scenarios=("geo-blockfade",), allocators=("EB", "BA"),
                     stream=stream, cohort=COHORT, exp_overrides={"cut": 1})


def test_sweep_per_topology_rows(hier_sweep):
    assert len(hier_sweep.records) == 2 * 1 * 2 * 2  # topo × scen × alloc × r
    for row in hier_sweep.records:
        assert row["topology"] in ("star", "edge-cloud")
    summary = hier_sweep.summary()
    assert {(r["topology"], r["allocator"]) for r in summary} == {
        ("star", "EB"), ("star", "BA"),
        ("edge-cloud", "EB"), ("edge-cloud", "BA")}
    for row in summary:
        assert row["rounds"] == 2 and row["total_time"] > 0


def test_sweep_delay_reduction_per_topology(hier_sweep):
    """The paper's comparison, reported per topology: the optimised
    allocator beats BA on the flat graph AND in the hierarchical split."""
    red = hier_sweep.delay_reduction(allocator="EB", baseline="BA")
    assert set(red) == {"star/geo-blockfade", "edge-cloud/geo-blockfade"}
    for pct in red.values():
        assert 0 < pct < 100


def test_sweep_json_records_topologies(hier_sweep, tmp_path):
    import json

    with open(hier_sweep.to_json(str(tmp_path / "hier.json"))) as f:
        payload = json.load(f)
    assert payload["topologies"] == ["star", "edge-cloud"]
    assert set(payload["delay_reduction"]["pct_by_scenario"]) == {
        "star/geo-blockfade", "edge-cloud/geo-blockfade"}


# ---------------------------------------------------------------------------
# Optimised edge placement (kmeans facility location)
# ---------------------------------------------------------------------------


def test_kmeans_placement_is_pure_and_tightens_geometry(fcfg):
    """kmeans places edges at the user geometry's facility-location optimum
    (Lloyd from the ring): a pure function of the drawn geometry, and the
    mean client→edge distance strictly tightens vs the ring."""
    net = get_scenario("geo-blockfade").initial_network(fcfg, seed=0)
    ring = EdgeCloudTopology(num_edges=3, placement="ring")
    km = EdgeCloudTopology(num_edges=3, placement="kmeans")
    exy_a = km.edge_xy(fcfg, net)
    exy_b = km.edge_xy(fcfg, net)
    np.testing.assert_array_equal(exy_a, exy_b)  # deterministic, no RNG

    def mean_dist(topo):
        assign = topo.attach(fcfg, net)
        exy = topo.edge_xy(fcfg, net)[assign]
        return float(np.mean(np.linalg.norm(net.xy - exy, axis=1)))

    assert mean_dist(km) < mean_dist(ring)


def test_kmeans_placement_critical_path_not_worse_than_ring(run_cfg):
    """The per-cell allocation under kmeans placement yields an end-to-end
    critical path (and worst-cell latency) no worse than the deterministic
    ring on geo-blockfade — the whole point of facility location."""
    ring = _fresh(run_cfg, scenario="geo-blockfade",
                  topology=EdgeCloudTopology(num_edges=2, placement="ring"))
    km = _fresh(run_cfg, scenario="geo-blockfade",
                topology=EdgeCloudTopology(num_edges=2, placement="kmeans"))
    assert float(np.max(km.timing.total)) <= float(np.max(ring.timing.total))
    cells_ring = cell_latency(ring.fcfg, ring.net, ring.alloc, ring.assign,
                              ring.topology, ring.eta)
    cells_km = cell_latency(km.fcfg, km.net, km.alloc, km.assign,
                            km.topology, km.eta)
    assert np.nanmax(cells_km) <= np.nanmax(cells_ring)


def test_kmeans_requires_geometry(run_cfg):
    with pytest.raises(ValueError):
        _fresh(run_cfg, scenario="blockfade",
               topology=EdgeCloudTopology(placement="kmeans"))


def test_placement_validation_and_digest(fcfg):
    with pytest.raises(ValueError):
        EdgeCloudTopology(placement="steiner")
    sc = get_scenario("geo-blockfade")
    ring = EdgeCloudTopology(num_edges=2, placement="ring")
    km = EdgeCloudTopology(num_edges=2, placement="kmeans")
    assert ring.digest(fcfg, sc, 0) != km.digest(fcfg, sc, 0)


# ---------------------------------------------------------------------------
# Queueing backhaul (shared metro FIFO / processor sharing) + downlink
# ---------------------------------------------------------------------------


def test_backhaul_model_validation():
    with pytest.raises(ValueError):
        EdgeCloudTopology(backhaul_model="token-ring")


@pytest.mark.parametrize("model", ["fifo", "ps"])
def test_queued_backhaul_composes_nonnegative_hops(fcfg, model):
    """fifo/ps replace the serial pipe: per-client hops are their own
    wait+service in the SHARED metro queue — non-negative, and the composed
    total is wireless + hop exactly."""
    from repro.core import resource_alloc as ra

    sc = get_scenario("geo-blockfade")
    net0 = sc.initial_network(fcfg, seed=0)
    for cls in (EdgeCloudTopology, EdgeAggTopology, RelayTopology):
        topo = cls(num_edges=2, backhaul_model=model, backhaul_bps=2e6)
        net, assign = topo.localize(fcfg, net0)
        alloc = topo.allocate(
            fcfg, net, assign,
            lambda f, n, **kw: ra.optimize(f, n, strategy="EB", **kw),
            strategy="EB", eta_search="coarse")
        t = topo.round_timing(fcfg, net, alloc, 0.5, assign)
        assert np.all(np.asarray(t.backhaul) >= -1e-9)
        wireless = fedsllm.simulate_round_time(fcfg, net, alloc, 0.5)
        np.testing.assert_allclose(t.total, wireless.total + t.backhaul)


def test_fifo_backhaul_contends_across_cells(fcfg):
    """Two cells' bursts share ONE metro pipe: tightening the capacity
    must grow someone's queueing wait beyond their own service time —
    contention the serial per-cell pipe cannot represent."""
    sc = get_scenario("geo-blockfade")
    net0 = sc.initial_network(fcfg, seed=0)
    topo = EdgeCloudTopology(num_edges=2, backhaul_model="fifo",
                             backhaul_bps=1e3)  # deliberately tight
    net, assign = topo.localize(fcfg, net0)
    totals = np.linspace(1.0, 1.01, fcfg.num_clients)  # near-simultaneous
    hop = topo._queued_backhaul(fcfg, assign, 0.5, totals)
    service = fcfg.s_c_bits / 1e3
    assert float(np.max(hop)) > 1.5 * service  # someone queued behind others


def test_serial_backhaul_stays_default_and_bit_identical(fcfg):
    sc = get_scenario("geo-blockfade")
    net0 = sc.initial_network(fcfg, seed=0)
    default = EdgeCloudTopology(num_edges=2)
    assert default.backhaul_model == "serial" and default.downlink_bps == 0.0
    net, assign = default.localize(fcfg, net0)
    legacy = (default._cell_bits(fcfg, assign, 0.5)
              / default.backhaul_bps)[assign]
    np.testing.assert_array_equal(
        default.backhaul_seconds(fcfg, assign, 0.5), legacy)


def test_downlink_broadcast_adds_one_multicast_per_cell(fcfg):
    """downlink_bps > 0 adds ONE broadcast cost — identical for every
    member of a cell — on top of the otherwise-unchanged composition."""
    from repro.core import resource_alloc as ra

    sc = get_scenario("geo-blockfade")
    net0 = sc.initial_network(fcfg, seed=0)
    base = EdgeCloudTopology(num_edges=2)
    dl = EdgeCloudTopology(num_edges=2, downlink_bps=1e6)
    net, assign = base.localize(fcfg, net0)
    alloc = ra.optimize(fcfg, net, strategy="EB")
    t_base = base.round_timing(fcfg, net, alloc, 0.5, assign)
    t_dl = dl.round_timing(fcfg, net, alloc, 0.5, assign)
    cost = fcfg.s_c_bits / 1e6
    assert t_base.downlink is None
    np.testing.assert_allclose(t_dl.downlink, cost)
    np.testing.assert_allclose(np.asarray(t_dl.total),
                               np.asarray(t_base.total) + cost)


# ---------------------------------------------------------------------------
# Wait-aware allocation: the allocator↔queueing loop under contended backhaul
# ---------------------------------------------------------------------------

CONTENDED_BPS = 2e3  # two cells' bursts sharing one deliberately thin pipe


def _contended(fcfg, model, **kw):
    sc = get_scenario("geo-blockfade")
    net0 = sc.initial_network(fcfg, seed=0)
    topo = EdgeCloudTopology(num_edges=2, backhaul_model=model,
                             backhaul_bps=CONTENDED_BPS, **kw)
    net, assign = topo.localize(fcfg, net0)
    return topo, net, assign


def _blind_solve(fcfg, net, assign, topo, alloc_fn, eta):
    """The wait-blind per-cell solve at one η, priced through the TRUE
    queued round_timing — the pre-loop allocator's answer."""
    cells = [np.where(assign == m)[0] for m in range(topo.num_edges)]
    solved = [(idx, alloc_fn(fcfg, subnetwork(net, idx),
                             eta_grid=np.array([eta])))
              for idx in cells if len(idx)]
    return allocation._combine(fcfg, net, assign, topo, solved, eta,
                               "proposed")


@pytest.mark.parametrize("model", ["fifo", "ps"])
def test_wait_aware_beats_wait_blind_under_contention(fcfg, model):
    """The tentpole acceptance: on a contended fixture (two cells, one thin
    metro pipe) the wait-aware fixed point must return a strictly faster
    end-to-end T than the wait-blind per-cell solve at the same η — both
    priced through the true queued round_timing."""
    topo, net, assign = _contended(fcfg, model)
    alloc_fn = get_allocator("proposed")
    eta = 0.3
    aware, info = solve_wait_aware(fcfg, net, assign, topo, alloc_fn, eta)
    blind = _blind_solve(fcfg, net, assign, topo, alloc_fn, eta)
    assert info.converged and info.iters <= topo.wait_iters
    assert aware is not None and blind is not None
    assert aware.T < blind.T, (aware.T, blind.T)
    # the reported T is exactly the true-queue critical path
    timing = topo.round_timing(fcfg, net, aware, eta, assign)
    I0 = dm.global_rounds(fcfg, eta)
    assert aware.T == pytest.approx(I0 * float(np.max(timing.total)))


def test_wait_aware_allocate_beats_baselines_per_cell():
    """End-to-end through the η sweep: the wait-aware proposed allocate is
    never worse than the wait-blind proposed allocate on the same grid, and
    beats EB/FE/BA in every non-empty cell under the queued pipe.

    The fixture is transmission-bound (small wireless pools) with a
    moderately loaded metro queue: on a compute-bound draw the bandwidth
    split is irrelevant and EB — which sweeps the same η grid — ties the
    exact solver to within queue-arrival epsilon, so per-cell strictness
    would test the channel draw, not the allocator."""
    fcfg = FedsLLMConfig(num_clients=K, bandwidth_total_hz=2e5)
    sc = get_scenario("geo-blockfade")
    net0 = sc.initial_network(fcfg, seed=1)
    topo = EdgeCloudTopology(num_edges=2, backhaul_model="fifo",
                             backhaul_bps=2e5, wait_iters=2)
    blind_topo = EdgeCloudTopology(num_edges=2, backhaul_model="fifo",
                                   backhaul_bps=2e5, wait_aware=False)
    net, assign = topo.localize(fcfg, net0)
    prop_fn = get_allocator("proposed")
    kw = dict(strategy="proposed", eta_search="warm", eta0=0.3)
    aware = topo.allocate(fcfg, net, assign, prop_fn, **kw)
    blind = blind_topo.allocate(fcfg, net, assign, prop_fn, **kw)
    assert aware.feasible and blind.feasible
    assert aware.T <= blind.T
    T_aware = cell_latency(fcfg, net, aware, assign, topo, aware.eta)
    for strat in ("EB", "FE", "BA"):
        base = topo.allocate(fcfg, net, assign, get_allocator(strat),
                             strategy=strat, eta_search="warm", eta0=0.3)
        T_base = cell_latency(fcfg, net, base, assign, topo, base.eta)
        for m in range(topo.num_edges):
            if not np.isnan(T_aware[m]):
                assert T_aware[m] < T_base[m], (strat, m, T_aware, T_base)


def test_wait_aware_flag_is_inert_on_serial_backhaul(fcfg):
    """backhaul_model="serial" keeps the legacy allocator bit-identical:
    the loop never engages (no wait_diag) and the flag changes nothing."""
    sc = get_scenario("geo-blockfade")
    net0 = sc.initial_network(fcfg, seed=0)
    prop_fn = get_allocator("proposed")
    allocs = []
    for flag in (True, False):
        topo = EdgeCloudTopology(num_edges=2, wait_aware=flag)
        net, assign = topo.localize(fcfg, net0)
        a = topo.allocate(fcfg, net, assign, prop_fn, strategy="proposed",
                          eta_search="warm", eta0=0.02)
        assert not hasattr(topo, "wait_diag")
        allocs.append(a)
    a, b = allocs
    assert a.T == b.T and a.eta == b.eta
    np.testing.assert_array_equal(a.b_c, b.b_c)
    np.testing.assert_array_equal(a.b_s, b.b_s)
    np.testing.assert_array_equal(a.t_c, b.t_c)
    np.testing.assert_array_equal(a.t_s, b.t_s)


def test_edge_agg_queued_outage_keeps_cell_backhaul_finite(fcfg):
    """Regression (edge-agg × queued × outage): a +inf member must not
    poison its cell's pre-aggregated job — the edge forwards once its
    FINITE members are in; only a fully-dead cell never reaches the
    queue."""
    topo = EdgeAggTopology(num_edges=2, backhaul_model="fifo",
                           backhaul_bps=2e6)
    assign = np.array([0, 0, 0, 1, 1, 1])
    totals = np.array([1.0, 2.0, np.inf, 1.5, 2.5, 3.0])
    arrivals, bits, job_of = topo._backhaul_jobs(fcfg, assign, 0.5, totals)
    np.testing.assert_allclose(arrivals, [2.0, 3.0])  # finite-max per cell
    hop = topo._queued_backhaul(fcfg, assign, 0.5, totals)
    assert np.all(np.isfinite(hop[np.isfinite(totals)]))
    assert hop[2] == 0.0  # the outage'd client never reaches the queue
    # a fully-dead cell never arrives, and doesn't block the live one
    dead = np.array([1.0, 2.0, 3.0, np.inf, np.inf, np.inf])
    arr2, _, _ = topo._backhaul_jobs(fcfg, assign, 0.5, dead)
    np.testing.assert_allclose(arr2, [3.0, np.inf])
    hop2 = topo._queued_backhaul(fcfg, assign, 0.5, dead)
    assert np.all(np.isfinite(hop2[:3])) and np.all(hop2[3:] == 0.0)


def test_combine_prices_critical_path_over_finite_clients(fcfg):
    """Regression (degenerate η sweep under outage): one +inf client must
    not turn every η candidate into T=+inf — the sweep prices the
    deadline-surviving critical path, +inf only when nobody is finite."""
    topo = EdgeCloudTopology(num_edges=2)
    sc = get_scenario("geo-blockfade")
    net, assign = topo.localize(fcfg, sc.initial_network(fcfg, seed=0))

    def cell_alloc(idx, dead=()):
        n = len(idx)
        t_c = np.where(np.isin(idx, list(dead)), np.inf, 1.0)
        return Allocation(1.0, 0.3, 0.5, t_c, np.ones(n),
                          np.full(n, 1e6), np.full(n, 1e6), True, "proposed")

    cells = [np.where(assign == m)[0] for m in range(2)]
    one_dead = [(idx, cell_alloc(idx, dead={int(cells[0][0])}))
                for idx in cells]
    combined = allocation._combine(fcfg, net, assign, topo, one_dead, 0.3,
                                   "proposed")
    assert np.isfinite(combined.T)
    all_dead = [(idx, cell_alloc(idx, dead=set(map(int, idx))))
                for idx in cells]
    degenerate = allocation._combine(fcfg, net, assign, topo, all_dead, 0.3,
                                     "proposed")
    assert np.isinf(degenerate.T)


def test_infeasible_allocation_carries_nan_eta(fcfg):
    bad = allocation._infeasible(fcfg, "proposed")
    assert not bad.feasible and np.isinf(bad.T) and np.isnan(bad.eta)


def test_set_eta_refuses_non_finite(run_cfg):
    exp = _fresh(run_cfg)
    with pytest.raises(ValueError, match="non-finite"):
        exp.set_eta(float("nan"))


def test_realloc_round_refuses_infeasible_solve(run_cfg, monkeypatch):
    """A reallocating round whose solve comes back infeasible must raise
    with the round index instead of adopting a fabricated η."""
    exp = _fresh(run_cfg, topology=EdgeCloudTopology(num_edges=2),
                 scenario="geo-blockfade")
    monkeypatch.setattr(exp.topology, "allocate",
                        lambda *a, **k: allocation._infeasible(exp.fcfg, "EB"))
    with pytest.raises(ValueError, match="round 3"):
        events.round_state(exp, 0, 3, reallocate=True)


HIER_TOPOS = ("edge-cloud", "edge-agg", "relay")
GEO_SCENARIOS = ("geo-blockfade", "drift", "hetero", "outage", "shadowing")


def test_wait_aware_fixed_point_deterministic_on_every_hier_cell(fcfg):
    """Property: on every registered hierarchical topology × geometry
    scenario the wait-aware fixed point at one η converges within its
    deterministic cap and repeat calls are bit-identical — so campaigns
    that re-solve per round stay pure functions of (RunConfig, seed)."""
    prop_fn = get_allocator("proposed")
    eta = 0.3
    for tname in HIER_TOPOS:
        for sname in GEO_SCENARIOS:
            topo = type(get_topology(tname))(num_edges=2,
                                             backhaul_model="fifo")
            net, assign = topo.localize(
                fcfg, get_scenario(sname).round_network(fcfg, 0, 1))
            a1, i1 = solve_wait_aware(fcfg, net, assign, topo, prop_fn, eta)
            a2, i2 = solve_wait_aware(fcfg, net, assign, topo, prop_fn, eta)
            key = (tname, sname)
            assert i1.converged and i1.iters <= topo.wait_iters, (key, i1)
            assert (i1.iters, i1.max_delta) == (i2.iters, i2.max_delta), key
            assert a1 is not None and a1.T == a2.T, key
            np.testing.assert_array_equal(a1.b_c, a2.b_c, err_msg=str(key))
            np.testing.assert_array_equal(a1.t_c, a2.t_c, err_msg=str(key))


def test_wait_aware_realloc_campaign_bounded_traces(run_cfg, stream):
    """A wait-aware reallocating campaign keeps the jit cache η-bucket
    bounded and engages the fixed point every round (diag converged)."""
    exp = _fresh(run_cfg, eta=0.2, allocator="proposed",
                 topology=EdgeCloudTopology(num_edges=2,
                                            backhaul_model="fifo"),
                 scenario="geo-blockfade")
    res = exp.run(num_rounds=2, stream=stream, cohort=COHORT,
                  resample_channel=True, reallocate=True)
    assert res.num_rounds == 2
    assert exp.trace_count <= len(exp.eta_buckets)
    for rec in res.records:
        assert rec.eta in exp.eta_buckets
    diag = exp.topology.wait_diag
    assert diag and all(d.converged for d in diag)


def test_queued_realloc_checkpoint_resume_bit_identical(run_cfg, stream,
                                                        tmp_path):
    """Checkpoint/resume replays a queued-backhaul reallocating campaign
    bit-identically (the queued pricing and the new topology params ride
    the digest)."""
    mk = lambda: _fresh(run_cfg, eta=0.2,  # noqa: E731
                        topology=EdgeCloudTopology(num_edges=2,
                                                   backhaul_model="fifo"),
                        scenario="geo-blockfade")
    kw = dict(stream=stream, cohort=COHORT, resample_channel=True,
              reallocate=True)
    exp = mk()
    full = exp.run(num_rounds=4, **kw)
    assert exp.trace_count <= len(exp.eta_buckets)
    ckpt = str(tmp_path / "camp")
    mk().run(num_rounds=2, checkpoint_dir=ckpt, checkpoint_every=2, **kw)
    rest = mk().run(num_rounds=4, checkpoint_dir=ckpt, resume=True, **kw)
    assert [r.round for r in rest.records] == [2, 3]
    for ra_, rb in zip(full.records[2:], rest.records):
        assert ra_.metrics == rb.metrics and ra_.eta == rb.eta
    for a, b in zip(jax.tree.leaves((full.state.lora_c, full.state.lora_s)),
                    jax.tree.leaves((rest.state.lora_c, rest.state.lora_s))):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
