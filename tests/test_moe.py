"""The expert layer and the Granite-4.0-H family (``ssm-moe``) against the
benchmark's plain float32 reference (``bench/reference/ssm-moe.py``), on
seeded random weights at a small size.

Tolerances: the program runs in float32 here, the reference in float32 at
``highest`` precision, so they differ by summation order alone; 1e-5 of a
loss (nats) and relative gaps of 1e-4 of an update leave two orders of room
over what float32 reorderings give (~1e-7) and sit far under what a missing
term gives (a dropped expert, the shared expert twice, a lost token: 1e-2
and up)."""

import dataclasses
import importlib.util
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.config import LoRAConfig, get_arch, smoke_variant
from repro.core import lora as lora_lib
from repro.core import split
from repro.models import moe as MOE
from repro.models import transformer as T

BENCH = pathlib.Path(__file__).resolve().parents[1] / "bench"


@pytest.fixture(scope="module")
def reference():
    spec = importlib.util.spec_from_file_location("ref_ssm_moe", BENCH / "reference" / "ssm-moe.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def tiny(**kw):
    """granite-4.0-h-small's smoke variant cut to a period of three layers
    (Mamba-2, attention, Mamba-2), each ending in the MoE."""
    cfg = smoke_variant(get_arch("granite-4.0-h-small"))
    return cfg.replace(**{"layer_pattern": "MGM", "num_layers": 3,
                          "lora": LoRAConfig(rank=4, alpha=8.0), **kw})


def ref_cfg(cfg) -> dict:
    """The model configuration as the reference reads it (a cell's file)."""
    d = {k: v for k, v in dataclasses.asdict(cfg).items() if k != "lora"}
    d.update(lora_rank=cfg.lora.rank, lora_alpha=cfg.lora.alpha,
             router_experts=cfg.num_router_experts)
    return d


def moe_input(cfg, key=0, B=2, S=16):
    return jax.random.normal(jax.random.PRNGKey(key), (B, S, cfg.d_model), jnp.float32)


def _init_moe(reference, cfg, key=1):
    """One expert layer with every one of the router's experts, drawn as
    the benchmark draws it (by fan-in, so each part is of the output's order)."""
    from repro.parallel import split_param_tree

    whole = cfg.replace(num_experts=cfg.num_router_experts, router_experts=0, expert_offset=0)
    vals, _ = split_param_tree(MOE.init_moe(jax.random.PRNGKey(key), whole))
    flat, treedef = jax.tree_util.tree_flatten_with_path(vals)
    return jax.tree_util.tree_unflatten(treedef, [
        reference.init_leaf(tuple(str(k.key) for k in path), v.shape, jax.random.PRNGKey(i))
        for i, (path, v) in enumerate(flat)])


def _share(p, e0: int, E: int):
    return dict(p, **{n: p[n][e0: e0 + E] for n in ("w_gate", "w_up", "w_down")})


def _ref_moe(reference, cfg, p, x):
    """The reference's expert layer, no adapters."""
    rc = ref_cfg(cfg)
    zero = {f"{blk}.{n}": {"A": jnp.zeros(w.shape[:-1] + (1,)), "B": jnp.zeros((*w.shape[:-2], 1, w.shape[-1]))}
            for blk, q in (("moe", p), ("shared", p["shared"])) for n, w in q.items()
            if n in ("w_gate", "w_up", "w_down")}
    rc.update(lora_rank=1, lora_alpha=1.0)
    with jax.default_matmul_precision("highest"):
        y, _ = reference._moe(x, p, zero, rc, lambda t: t)
    return y


def test_expert_shares_sum_to_the_whole_layer(reference):
    """Eight chips' shares of a 16-expert layer, each told which 2 experts it
    holds: their routed parts summed, with the shared expert (which every
    chip computes alike) counted once, are the uncut reference's layer."""
    cfg = tiny(num_experts=2, router_experts=16, num_experts_per_tok=4)
    p = _init_moe(reference, cfg)
    x = moe_input(cfg)
    shared = T.L.apply_mlp(p["shared"], x, cfg)
    parts = []
    for j in range(8):
        y, aux = MOE.apply_moe(_share(p, 2 * j, 2), x, cfg.replace(expert_offset=2 * j))
        parts.append(y - shared)
    whole = _ref_moe(reference, cfg.replace(num_experts=16, router_experts=16), p, x)
    np.testing.assert_allclose(np.asarray(sum(parts) + shared), np.asarray(whole),
                               rtol=1e-4, atol=1e-5)
    # and a share is the reference's share: the routed pairs held elsewhere add nothing
    y3, _ = MOE.apply_moe(_share(p, 6, 2), x, cfg.replace(expert_offset=6))
    np.testing.assert_allclose(np.asarray(y3), np.asarray(
        _ref_moe(reference, cfg.replace(expert_offset=6), _share(p, 6, 2), x)), rtol=1e-4, atol=1e-5)


def test_routing_onto_one_held_expert_drops_no_token(reference):
    """Every token routes its top pair to held expert 0: far above any
    capacity a static dispatch would give it, and none is dropped."""
    cfg = tiny(num_experts=2, router_experts=16, num_experts_per_tok=4, expert_offset=4)
    p = _init_moe(reference, cfg)
    x = jnp.abs(moe_input(cfg))  # positive rows: the router's column 4 wins for each
    p = dict(p, router=p["router"].at[:, 4].set(50.0))
    y, aux = MOE.apply_moe(_share(p, 4, 2), x, cfg)
    T_ = x.shape[0] * x.shape[1]
    assert float(aux["moe_loads"][0, 0]) == T_
    np.testing.assert_allclose(np.asarray(y), np.asarray(
        _ref_moe(reference, cfg, _share(p, 4, 2), x)), rtol=1e-4, atol=1e-5)


MESH_SCRIPT = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
os.environ["JAX_PLATFORMS"] = "cpu"
import jax, jax.numpy as jnp
from repro.config import get_arch, smoke_variant
from repro.launch.mesh import make_mesh
from repro.models import moe as MOE
from repro.models.layers import Adapted
from repro.parallel import RULESETS, sharding_context, split_param_tree

cfg = smoke_variant(get_arch("granite-4.0-h-small")).replace(
    num_experts=4, router_experts=8, expert_offset=2, num_experts_per_tok=2)
p, _ = split_param_tree(MOE.init_moe(jax.random.PRNGKey(1), cfg))
ks = jax.random.split(jax.random.PRNGKey(2), 6)
for i, n in enumerate(("w_gate", "w_up", "w_down")):
    w = p[n]
    p[n] = Adapted(w, 0.1 * jax.random.normal(ks[2 * i], w.shape[:-1] + (4,)),
                   0.1 * jax.random.normal(ks[2 * i + 1], (w.shape[0], 4, w.shape[-1])), 2.0)
x = jax.random.normal(jax.random.PRNGKey(3), (4, 4, cfg.d_model))
calls = []
sharded = MOE._sharded_capacity
MOE._sharded_capacity = lambda *a: calls.append(1) or sharded(*a)
y = jax.jit(lambda p, x: MOE.apply_moe(p, x, cfg)[0])(p, x)
assert not calls
with sharding_context(make_mesh((2, 2), ("data", "model")), RULESETS["train"]):
    ym = jax.jit(lambda p, x: MOE.apply_moe(p, x, cfg)[0])(p, x)
assert calls
print(float(jnp.max(jnp.abs(y - ym)) / jnp.max(jnp.abs(y))))
"""


def test_mesh_path_equals_dropless_path_without_overflow():
    """Under a (data 2, model 2) mesh of four CPU devices the expert layer
    takes its static-capacity ``shard_map`` path, each model shard holding
    2 of the 4 held experts (offset 2 in a router of 8), adapters attached.
    Four tokens a row at top-2 fill at most 8 slots of an expert, under the
    capacity floor of 8, so nothing overflows and the mesh path equals the
    local dropless one to float32 summation order (relative 1e-5)."""
    import os
    import subprocess
    import sys

    root = pathlib.Path(__file__).resolve().parents[1]
    env = dict(os.environ, PYTHONPATH=str(root / "src"))
    out = subprocess.run([sys.executable, "-c", MESH_SCRIPT], env=env, capture_output=True,
                         text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    assert float(out.stdout.split()[-1]) < 1e-5


def _split_setup(cfg, cut):
    params, axes = T.init_params(cfg, key=jax.random.PRNGKey(0))
    lora_full, _ = lora_lib.init_lora(params, axes, cfg, key=jax.random.PRNGKey(1))
    lora_full = jax.tree.map(
        lambda x: x + 0.01 * jax.random.normal(jax.random.PRNGKey(2), x.shape, x.dtype), lora_full)
    lc, ls = lora_lib.split_client_server(lora_full, cut, cfg)
    kt, kl = jax.random.split(jax.random.PRNGKey(3))
    batch = {"tokens": jax.random.randint(kt, (2, 16), 0, cfg.vocab_size),
             "labels": jax.random.randint(kl, (2, 16), 0, cfg.vocab_size),
             "mask": jnp.ones((2, 16), jnp.float32)}
    return params, lc, ls, batch


@pytest.mark.parametrize("cut", [1, 2, 4])
def test_split_at_every_layer_of_a_period_equals_monolithic(cut):
    """A period-3 hybrid of 6 layers cut inside its first group (1, 2) and
    inside its second (4): the client holds the first ``cut`` layers and
    the split step equals end-to-end autodiff through the merged weights."""
    cfg = tiny(num_layers=6)
    params, lc, ls, batch = _split_setup(cfg, cut)
    mixers = [v["A"].shape[0] for k, v in lc.items()
              if k.endswith("['mamba']['in_proj']") or k.endswith("['attn']['wq']")]
    assert sum(mixers) == cut
    vg = jax.jit(split.split_value_and_grad, static_argnums=(4, 5))
    mono = jax.jit(split.monolithic_value_and_grad, static_argnums=(4, 5))
    loss_s, dc_s, ds_s, info = vg(params, lc, ls, batch, cfg, cut)
    loss_m, dc_m, ds_m = mono(params, lc, ls, batch, cfg, cut)
    np.testing.assert_allclose(float(loss_s), float(loss_m), rtol=1e-6)
    for a, b in zip(jax.tree.leaves((dc_s, ds_s)), jax.tree.leaves((dc_m, ds_m))):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-6)
    assert info["moe_loads"].shape == (cfg.num_layers, cfg.num_experts)


@pytest.mark.parametrize("bounds", [(0, 2, 4, 6), (0, 1, 2, 4, 6), (0, 4, 5, 6)])
def test_pipeline_stages_of_layer_views_equal_the_whole_stack(bounds):
    """A period-3 hybrid of 6 layers run as stages [bounds[j], bounds[j+1]),
    each a ``layer_view`` told its first layer: a stage that starts and ends
    inside groups ([2, 4): layer 2, then layer 3), one inside a single group
    ([1, 2)), and one that leaves a group early ([0, 4)) give the whole
    stack's output and aux."""
    cfg = tiny(num_layers=6)
    params, _ = T.init_params(cfg, key=jax.random.PRNGKey(0))
    x0 = moe_input(cfg, S=8)
    kw = dict(positions=jnp.arange(8)[None, :], include_tail=False)
    whole, _, aux = T._scan_groups(params, x0, cfg, **kw)
    x, loss, loads = x0, 0.0, []
    for a, b in zip(bounds, bounds[1:]):
        view = dict(params, groups=T.layer_view(params["groups"], cfg, a, b))
        assert T.n_layers(view["groups"]) == b - a
        x, _, sa = T._scan_groups(view, x, cfg, start=a, **kw)
        loss, loads = loss + sa["moe_aux_loss"], loads + [sa["moe_loads"]]
    np.testing.assert_allclose(np.asarray(x), np.asarray(whole), rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(float(loss), float(aux["moe_aux_loss"]), rtol=1e-6)
    np.testing.assert_array_equal(np.concatenate(loads), np.asarray(aux["moe_loads"]))


def test_rows_the_grouped_product_leaves_unwritten_reach_no_sum(monkeypatch):
    """The TPU's grouped product leaves the rows past the last group (and an
    empty group's block of the weight gradient) unwritten.  Played here as
    NaN, they change neither the loss nor any adapter gradient."""
    cfg = tiny(num_experts=4, router_experts=16, expert_offset=4, num_experts_per_tok=4)
    params, lc, ls, batch = _split_setup(cfg, 1)
    clean = split.split_value_and_grad(params, lc, ls, batch, cfg, 1)[:3]

    ragged, general = jax.lax.ragged_dot, jax.lax.ragged_dot_general

    def unwritten_rows(x, w, gs, **kw):
        y = ragged(x, w, gs, **kw)
        return jnp.where(jnp.arange(x.shape[0])[:, None] < jnp.sum(gs), y, jnp.nan)

    def unwritten_groups(x, ct, gs, dims, **kw):
        y = general(x, ct, gs, dims, **kw)
        return jnp.where((gs > 0)[:, None, None], y, jnp.nan)

    monkeypatch.setattr(jax.lax, "ragged_dot", unwritten_rows)
    monkeypatch.setattr(jax.lax, "ragged_dot_general", unwritten_groups)
    jax.clear_caches()  # no product traced before the patch may be reused
    dirty = split.split_value_and_grad(params, lc, ls, batch, cfg, 1)[:3]
    for a, b in zip(jax.tree.leaves(clean), jax.tree.leaves(dirty)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_program_round_matches_the_reference():
    """Two federated split rounds of the program (a period of Mamba-2,
    attention and Mamba-2 layers, cut after the second, 3 of 12 experts
    held, top-4, float32) against the benchmark's reference rounds from the
    same weights and inputs: the round-start losses and every adapter
    leaf's update agree to float32 summation order."""
    import json
    import sys

    if str(BENCH) not in sys.path:
        sys.path.append(str(BENCH))
    from harness import session, spec

    cfg = json.loads((BENCH / "configs" / "granite-4.0-h-small.json").read_text())
    cfg.update(name="tiny-ssm-moe", num_layers=3, layer_pattern="MGM", d_model=64, num_heads=4,
               num_kv_heads=2, head_dim=16, d_ff=32, shared_d_ff=48, vocab_size=256,
               num_experts=3, router_experts=12, expert_offset=3, num_experts_per_tok=4,
               ssm_state=16, ssm_head_dim=16, ssm_chunk=16, dtype="float32",
               param_dtype="float32", lora_rank=4, lora_alpha=8.0)
    traffic = json.loads((BENCH / "traffic" / "sync-moe.json").read_text())
    traffic.update(population=6, cohort=2, batch=1, seq=32, cut=2)
    cell = spec.Cell("tiny", 1, cfg, traffic, {}, [], [])
    sess = session.build(cell, 3500000101)
    session.warm(sess)
    lora0, losses, snaps = session.program_side(sess)
    gaps, _ = session.compare(sess, lora0, losses, snaps, session.follow(sess))
    assert gaps["loss_gap"] < 1e-5, gaps
    for k in ("update1_gap", "update2_gap", "client_update1_gap", "client_update2_gap"):
        assert gaps[k] < 1e-4, gaps
