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
from repro.data.tokens import TokenStream, client_batches

ARCH = {"dense": "fedsllm-100m", "ssm": "mamba2-130m"}
SPLIT_SCOPES = ["fedsllm.client", "transpose(jvp(fedsllm.client))", "fedsllm.server",
                "lora.merge", "fedsllm.aggregate"]
MODEL_SCOPE = {"dense": "model.attention", "ssm": "model.ssd"}
SCOPE_CASES = ([(f, s) for f in ARCH for s in SPLIT_SCOPES]
               + [(f, MODEL_SCOPE[f]) for f in ARCH])
ROUND_STEPS = ["fedsllm.plan", "fedsllm.batches", "fedsllm.dispatch", "fedsllm.read"]
K, COHORT, ROUNDS = 4, 2, 2


def _experiment(family: str) -> Experiment:
    cfg = smoke_variant(get_arch(ARCH[family])).replace(lora=LoRAConfig(rank=4, alpha=8.0))
    run_cfg = RunConfig(model=cfg, shape=SHAPES["train_4k"],
                        fedsllm=FedsLLMConfig(num_clients=K))
    return Experiment.from_config(run_cfg, allocator="EB", eta=0.5)


def _stream(exp: Experiment) -> TokenStream:
    return TokenStream(2, 32, exp.cfg.vocab_size, seed=0)


@pytest.fixture(scope="module")
def op_names():
    """family -> the op_name metadata of the compiled round function."""
    cache = {}

    def get(family):
        if family not in cache:
            exp = _experiment(family)
            batches = client_batches(_stream(exp), 0, COHORT)
            text = exp.round_fn.lower(*exp.round_args(batches)).compile().as_text()
            cache[family] = set(re.findall(r'op_name="([^"]*)"', text))
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
def test_other_family_model_scope_absent(op_names, family):
    other = next(MODEL_SCOPE[f] for f in ARCH if f != family)
    assert not any(_in_path(other, n) for n in op_names(family))


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
