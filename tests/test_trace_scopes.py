"""The program's own trace names: device scopes in the compiled round
function's op metadata, and host spans of a campaign on the profiler's
clock.  The benchmark's trace reduction finds both by these literal names."""

import glob
import os
import re

import jax
import numpy as np
import pytest

from repro.api import Experiment
from repro.config import FedsLLMConfig, LoRAConfig, RunConfig, SHAPES, get_arch, smoke_variant
from repro.core import fedsllm
from repro.core import lora as lora_lib
from repro.data.tokens import TokenStream, client_batches

ARCH = {"dense": "fedsllm-100m", "ssm": "mamba2-130m"}
SPLIT_SCOPES = ["fedsllm.client", "transpose(jvp(fedsllm.client))", "fedsllm.server",
                "lora.adapter", "fedsllm.aggregate"]
MODEL_SCOPE = {"dense": "model.attention", "ssm": "model.ssd"}
SCOPE_CASES = ([(f, s) for f in ARCH for s in SPLIT_SCOPES]
               + [(f, MODEL_SCOPE[f]) for f in ARCH])
MOE_SCOPES = ["model.moe", "model.moe.router", "model.moe.experts", "model.moe.shared",
              "model.ssd", "model.attention"]
COUNTERS = ["moe_held_share", "moe_max_load"]
ROUND_STEPS = ["fedsllm.plan", "fedsllm.batches", "fedsllm.dispatch", "fedsllm.read"]
K, COHORT, ROUNDS = 4, 2, 2


def _experiment(family: str) -> Experiment:
    if family == "ssm-moe":
        # granite-4.0-h-small's smoke variant at one period of three layers,
        # holding experts 4..7 of the router's 16, cut after its first layer
        cfg = smoke_variant(get_arch("granite-4.0-h-small")).replace(
            layer_pattern="MGM", num_layers=3, num_experts=4, router_experts=16,
            expert_offset=4, num_experts_per_tok=4)
        kw = {"cut": 1}
    else:
        cfg, kw = smoke_variant(get_arch(ARCH[family])), {}
    run_cfg = RunConfig(model=cfg.replace(lora=LoRAConfig(rank=4, alpha=8.0)),
                        shape=SHAPES["train_4k"], fedsllm=FedsLLMConfig(num_clients=K))
    return Experiment.from_config(run_cfg, allocator="EB", eta=0.5, **kw)


def _stream(exp: Experiment) -> TokenStream:
    # 3 rows of 24 tokens: neither 24, 48 (query heads per kv head x seq)
    # nor 72 (tokens a client) is a projection width of the smoke models,
    # so a dot whose trailing dims are a weight's (d_in, d_out) is weight-sized
    return TokenStream(3, 24, exp.cfg.vocab_size, seed=0)


@pytest.fixture(scope="module")
def compiled_program():
    """family -> (Experiment, its compiled round program)."""
    cache = {}

    def get(family):
        if family not in cache:
            exp = _experiment(family)
            batches = client_batches(_stream(exp), 0, COHORT)
            cache[family] = exp, exp.round_fn.lower(*exp.round_args(batches)).compile()
        return cache[family]

    return get


@pytest.fixture(scope="module")
def compiled_round(compiled_program):
    """family -> (Experiment, HLO text of its compiled round function)."""
    cache = {}

    def get(family):
        if family not in cache:
            exp, compiled = compiled_program(family)
            cache[family] = exp, compiled.as_text()
        return cache[family]

    return get


@pytest.fixture(scope="module")
def op_names(compiled_round):
    """family -> the op_name metadata of the compiled round function."""
    cache = {}

    def get(family):
        if family not in cache:
            cache[family] = set(re.findall(r'op_name="([^"]*)"', compiled_round(family)[1]))
        return cache[family]

    return get


def _in_path(scope: str, op_name: str) -> bool:
    """``scope`` is a component of the op's scope path, bare or wrapped by a
    transform as in ``transpose(jvp(<scope>))``."""
    return re.search(rf"(^|[/(]){re.escape(scope)}($|[/)])", op_name) is not None


@pytest.mark.parametrize("family,scope", SCOPE_CASES)
def test_scope_in_compiled_round(op_names, family, scope):
    names = op_names(family)
    assert any(_in_path(scope, n) for n in names), f"{scope!r} in no op_name of {family}"


@pytest.mark.parametrize("family", list(ARCH))
def test_round_never_forms_a_weight_sized_array(compiled_round, op_names, family):
    """Adapters are applied unmerged: no dot of the round (merge, or the
    frozen weight's gradient) yields a targeted weight's (d_in, d_out)."""
    exp, text = compiled_round(family)
    lcfg = exp.cfg.lora
    weights = {tuple(leaf.shape[-2:]) for path, leaf in
               jax.tree_util.tree_flatten_with_path(exp.state.base)[0]
               if lora_lib.is_target(path, leaf, lcfg)}
    dots = [tuple(int(d) for d in dims.split(",") if d)
            for dims in re.findall(r"= \w+\[([\d,]*)\]\S* dot\(", text)]
    assert dots
    assert not [d for d in dots if d[-2:] in weights]
    assert not any(_in_path("lora.merge", n) for n in op_names(family))


@pytest.mark.parametrize("family", list(ARCH))
def test_other_family_model_scope_absent(op_names, family):
    other = next(MODEL_SCOPE[f] for f in ARCH if f != family)
    assert not any(_in_path(other, n) for n in op_names(family))


@pytest.mark.parametrize("scope", MOE_SCOPES)
def test_moe_scope_in_compiled_round(op_names, scope):
    names = op_names("ssm-moe")
    assert any(_in_path(scope, n) for n in names), f"{scope!r} in no op_name of ssm-moe"


@pytest.mark.parametrize("family", ["dense", "ssm", "ssm-moe"])
def test_routing_counters_only_with_experts(compiled_program, family):
    """The round returns the two routing counters for a model with experts
    alone, so the dense and SSM rounds read nothing more on the host."""
    metrics = compiled_program(family)[1].out_info[1]
    assert all((c in metrics) == (family == "ssm-moe") for c in COUNTERS), sorted(metrics)


@pytest.mark.parametrize("family", list(ARCH))
def test_round_program_returns_no_copy_of_the_base(compiled_program, family):
    """The compiled round's output (adapters, round counter, metrics) is
    smaller than its arguments by at least the frozen base's bytes: the
    base goes in and is not copied out."""
    exp, compiled = compiled_program(family)
    mem = compiled.memory_analysis()
    base = sum(a.nbytes for a in jax.tree.leaves(exp.state.base))
    assert mem.argument_size_in_bytes - mem.output_size_in_bytes >= base


def _host_spans(trace_dir: str) -> list:
    from jax.profiler import ProfileData

    path = max(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True),
               key=os.path.getmtime)
    spans = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                spans.extend((e.start_ns, e.start_ns + e.duration_ns, e.name, dict(e.stats))
                             for e in line.events if e.name.startswith("fedsllm."))
    return sorted(spans, key=lambda s: (s[0], -s[1]))


def test_campaign_spans_on_the_profiler_clock(tmp_path):
    exp = _experiment("dense")
    stream = _stream(exp)
    exp.run(num_rounds=1, stream=stream, cohort=COHORT)  # compile outside the trace
    with jax.profiler.trace(str(tmp_path)):
        exp.run(num_rounds=1 + ROUNDS, stream=stream, cohort=COHORT)
    spans = _host_spans(str(tmp_path))

    campaigns = [s for s in spans if s[2] == "fedsllm.campaign"]
    rounds = [s for s in spans if s[2] == "fedsllm.round"]
    assert len(campaigns) == 1
    assert [s[3]["round"] for s in rounds] == list(range(1, 1 + ROUNDS))
    I_loc = fedsllm.local_iteration_count(exp.fcfg, exp.eta)
    for s0, e0, _, stats in rounds:
        assert campaigns[0][0] <= s0 and e0 <= campaigns[0][1]
        assert stats["cohort"] == COHORT and stats["I_loc"] == I_loc
        inside = [s for s in spans if s0 <= s[0] and s[1] <= e0 and s[2] in ROUND_STEPS]
        assert [s[2] for s in inside] == ROUND_STEPS
        ends = [s[1] for s in inside]
        starts = [s[0] for s in inside]
        assert all(e <= s for e, s in zip(ends, starts[1:])), "steps overlap"
    assert np.all(np.diff([s[0] for s in rounds]) > 0)
