"""Tests of the benchmark's own code, run by path on the CPU:

    JAX_PLATFORMS=cpu python -m pytest -q bench/tests

They are not part of the repository's tier-1 suite (``testpaths`` there is
``tests``)."""

import json
import os
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)
sys.path.insert(0, os.path.join(ROOT, "src"))
os.environ.setdefault("JAX_PLATFORMS", "cpu")

TINY = {
    "dense": dict(name="tiny-dense", family="dense", num_layers=2, d_model=64, num_heads=4,
                  num_kv_heads=2, head_dim=16, d_ff=128, vocab_size=256,
                  mlp_activation="swiglu", norm_type="rmsnorm", use_rope=True,
                  rope_theta=10000.0, layer_pattern="G", tie_embeddings=False,
                  dtype="float32", param_dtype="float32", lora_rank=4, lora_alpha=8.0),
    "ssm": dict(name="tiny-ssm", family="ssm", num_layers=2, d_model=64, num_heads=4,
                num_kv_heads=4, head_dim=16, d_ff=0, vocab_size=256, norm_type="rmsnorm",
                use_rope=False, layer_pattern="M", ssm_state=16, ssm_head_dim=16,
                ssm_expand=2, ssm_chunk=16, ssm_conv_width=4, tie_embeddings=True,
                dtype="float32", param_dtype="float32", lora_rank=4, lora_alpha=8.0),
}


def tiny_cell(cell_name: str, traffic_name: str, family: str, **traffic):
    """A cell's traffic file and limits around a tiny float32 model of
    ``family``, at a size a CPU test holds, with BENCHMARK.json's metrics."""
    from harness import spec

    tr = json.loads((spec.BENCH / "traffic" / f"{traffic_name}.json").read_text())
    limits = json.loads((spec.BENCH / "limits" / f"{cell_name}.json").read_text())["limits"]
    bench = spec.benchmark()
    return spec.Cell(f"tiny-{cell_name}", 1, TINY[family], dict(tr, **traffic), limits,
                     bench["end_to_end"], bench["per_layer"])


@pytest.fixture
def dense_cell():
    return tiny_cell("fedsllm-100m.sync-iid", "sync-iid", "dense",
                     population=6, cohort=4, batch=2, seq=32)


@pytest.fixture
def ssm_cell():
    return tiny_cell("mamba2-130m.sync-long", "sync-long", "ssm",
                     population=6, cohort=2, batch=1, seq=64)
