"""chip_smoke.py on the CPU: its phases pass at a tiny size with the kernels
interpreted, and its entry point refuses to report a result without a TPU."""

import jax

from repro.config import get_arch, smoke_variant


def test_phases_pass_at_smoke_size(chip_smoke):
    failed = chip_smoke.run_phases(
        smoke_variant(get_arch(chip_smoke.ARCH)),
        smoke_variant(get_arch(chip_smoke.SSD_ARCH)),
        clients=4, batch=2, seq=32, rounds=3, kernel_seq=64, interpret=True)
    assert failed == []


def test_main_fails_without_a_tpu(chip_smoke, capsys):
    assert jax.devices()[0].platform != "tpu"
    assert chip_smoke.main() == 1
    out = capsys.readouterr().out
    assert "phase a device: FAIL" in out
    assert '"ok"' not in out
