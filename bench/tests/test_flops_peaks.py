"""FLOP counts against hand counts, and the peaks table."""

import subprocess
import sys

import pytest

from conftest import BENCH, ROOT


def _flops(family):
    from harness import spec

    return spec.module("flops", family).flops_per_token


def test_dense_flops_match_a_hand_count():
    cfg = dict(num_layers=2, d_model=8, num_heads=2, num_kv_heads=1, head_dim=4, d_ff=16,
               vocab_size=32, lora_rank=2)
    # projections (d_in, d_out): q 8x8, k 8x4, v 8x4, o 8x8, gate/up 8x16, down 16x8
    frozen = 64 + 32 + 32 + 64 + 128 + 128 + 128  # 576 weights a layer
    adapters = 6 * 2 * (16 + 12 + 12 + 16 + 24 + 24 + 24)  # 6 r (d_in + d_out)
    attention = 3 * 4 * 10 * 2 * 4  # seq 10, H 2, hd 4
    head = 4 * 8 * 32
    assert _flops("dense")(cfg, 10) == 2 * (4 * frozen + adapters + attention) + head


def test_ssm_flops_match_a_hand_count():
    cfg = dict(num_layers=3, d_model=8, vocab_size=32, lora_rank=2, ssm_state=4,
               ssm_head_dim=4, ssm_expand=2, ssm_chunk=8, ssm_conv_width=4)
    # d_inner 16, H 4; in_proj 8 x (32 + 8 + 4) = 8x44, out_proj 16x8
    frozen = 8 * 44 + 16 * 8
    adapters = 6 * 2 * (52 + 24)
    conv = 3 * 2 * 4 * 24
    Q = 5  # min(chunk 8, seq 5)
    scan = 3 * (2 * Q * 4 + 2 * 4 * Q * 4 + 4 * 4 * 4 * 4)
    head = 4 * 8 * 32
    assert _flops("ssm")(cfg, 5) == 3 * (4 * frozen + adapters + conv + scan) + head


def test_peaks_know_the_v5e_and_refuse_other_devices():
    from harness import peaks

    assert peaks.lookup("TPU v5 lite")["bf16_flops"] == 197e12
    with pytest.raises(KeyError):
        peaks.lookup("TPU v4")


def test_run_exits_nonzero_without_a_tpu():
    p = subprocess.run([sys.executable, f"{BENCH}/run.py", "--workload",
                        "fedsllm-100m.sync-iid", "--seed", "1", "--seconds", "1",
                        "--trace", "0"], cwd=ROOT, capture_output=True, text=True,
                       env={"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin"})
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr
