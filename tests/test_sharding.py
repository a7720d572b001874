"""Sharding rules: divisibility fallback, axis exclusivity, spec shapes."""

import jax
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.parallel import RULESETS, spec_for
from repro.launch.mesh import make_abstract_mesh, make_mesh


@pytest.fixture(scope="module")
def mesh():
    # 1 real device but spec_for math only needs the mesh SHAPE semantics
    return make_abstract_mesh((16, 16), ("data", "model"))


def test_divisible_dims_get_sharded(mesh):
    spec = spec_for((4096, 18432), ("embed", "mlp"), RULESETS["train"], mesh)
    assert spec == P("data", "model")


def test_fused_projection_dim_shards_even_with_awkward_head_count(mesh):
    # starcoder2: 36 heads % 16 != 0, but the fused (D, H·hd) weight dim
    # 4608 % 16 == 0 -> the weight still shards (TP on the flattened dim)
    spec = spec_for((4608, 36 * 128), ("embed", "heads"), RULESETS["train"], mesh)
    assert spec == P("data", "model")


def test_non_divisible_activation_head_axis_dropped(mesh):
    # the unflattened activation (B, S, 36, 128) cannot shard 36 heads 16-way
    spec = spec_for((16, 128, 36, 128), ("batch", "seq", "heads", None),
                    RULESETS["train"], mesh)
    assert spec[0] == "data"
    assert len(spec) <= 2 or spec[2] is None


def test_axis_never_reused_across_dims(mesh):
    spec = spec_for((256, 256, 256), ("embed", "embed", "embed"),
                    RULESETS["train"], mesh)
    used = [s for s in spec if s is not None]
    assert len(used) == len(set(used)) == 1  # data used once


def test_pod_axis_dropped_on_single_pod(mesh):
    spec = spec_for((256, 4096), ("batch", "seq"), RULESETS["train"], mesh)
    assert spec[0] == "data"  # ("pod","data") -> data only


def test_multi_pod_batch_uses_both():
    mesh3 = make_abstract_mesh((2, 16, 16), ("pod", "data", "model"))
    spec = spec_for((256, 4096), ("batch", "seq"), RULESETS["train"], mesh3)
    assert spec[0] == ("pod", "data")


def test_decode_rules_shard_kv_seq():
    mesh = make_abstract_mesh((16, 16), ("data", "model"))
    spec = spec_for((128, 32768, 8, 128), ("batch", "kv_seq", "kv_heads", None),
                    RULESETS["decode"], mesh)
    assert spec[0] == "data"
    assert spec[1] == "model"  # cache length sharded for flash-decode


def test_spec_never_exceeds_rank(mesh):
    spec = spec_for((8,), ("embed",), RULESETS["train"], mesh)
    assert len(spec) <= 1
