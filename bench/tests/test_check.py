"""The output check: a sound run passes, and the faults a training cell can
have, planted under the timed path (on the client's side of the cut too),
and the float8 control all fail it.

Each test drives the rest of ``run.py``'s measurement (set-up, a short
window, the reference) on the CPU with a tiny float32 model and the real
cell's traffic shape and limits; the harness's look for a chip is skipped.
"""

import types

import jax
import jax.numpy as jnp
import pytest

import run
from harness import session


def _measure(cell, seed=7):
    args = types.SimpleNamespace(seed=seed, seconds=0.3, trace=0)
    return run.measure(cell, args, {"bf16_flops": 197e12}, jax.devices()[:1])


def _break_round(monkeypatch, breaker):
    """Plant ``breaker(state, new_state, metrics, batches)`` under every
    round function the Experiment builds."""
    from repro.core import fedsllm

    build = fedsllm.build_round_fn

    def broken_build(*a, **kw):
        fn = build(*a, **kw)

        def round_fn(state, batches, *rest, **kws):
            new, metrics = fn(state, batches, *rest, **kws)
            return breaker(state, new, metrics, batches, fn, rest, kws)

        return round_fn

    monkeypatch.setattr(fedsllm, "build_round_fn", broken_build)


def test_sound_runs_pass(dense_cell, ssm_cell):
    for cell in (dense_cell, ssm_cell):
        out = _measure(cell)
        assert out["correct"], out["checks"]
        assert out["attempted"] > 0 and out["failed"] == 0


def test_state_returned_unchanged_fails(monkeypatch, dense_cell):
    _break_round(monkeypatch, lambda st, new, m, *_: (new._replace(
        lora_c=st.lora_c, lora_s=st.lora_s), m))
    out = _measure(dense_cell)
    assert not out["correct"]
    assert out["checks"]["update1_gap"]["value"] > 0.9


def test_half_of_the_batch_left_out_fails(monkeypatch, dense_cell):
    def half(st, new, m, batches, fn, rest, kws):
        rows = jax.tree.leaves(batches)[0].shape[1]
        return fn(st, jax.tree.map(lambda x: x[:, : rows // 2], batches), *rest, **kws)

    _break_round(monkeypatch, half)
    out = _measure(dense_cell)
    assert not out["correct"], out["checks"]


def test_an_altered_update_fails(monkeypatch, dense_cell):
    def altered(st, new, m, *_):  # the round's update applied twice
        twice = lambda a, b: (2 * a.astype(jnp.float32) - b).astype(a.dtype)  # noqa: E731
        return new._replace(lora_c=jax.tree.map(twice, new.lora_c, st.lora_c),
                            lora_s=jax.tree.map(twice, new.lora_s, st.lora_s)), m

    _break_round(monkeypatch, altered)
    out = _measure(dense_cell)
    assert not out["correct"], out["checks"]


def test_client_update_left_out_fails(monkeypatch, dense_cell):
    _break_round(monkeypatch, lambda st, new, m, *_: (new._replace(lora_c=st.lora_c), m))
    out = _measure(dense_cell)
    assert not out["correct"]
    assert out["checks"]["client_update1_gap"]["value"] > 0.9


def test_cut_gradient_halved_fails(monkeypatch, dense_cell):
    from repro.core import split

    vg = split.split_value_and_grad

    def halved(*a, **kw):  # the smashed-activation gradient dA_k at half size
        loss, dc, ds, info = vg(*a, **kw)
        return loss, jax.tree.map(lambda g: g / 2, dc), ds, info

    monkeypatch.setattr(split, "split_value_and_grad", halved)
    out = _measure(dense_cell)
    assert not out["correct"], out["checks"]
    assert out["checks"]["client_update1_gap"]["value"] > 0.3


@pytest.mark.parametrize("family", ["dense", "ssm"])
def test_float8_control_fails(family, dense_cell, ssm_cell):
    cell = {"dense": dense_cell, "ssm": ssm_cell}[family]
    sess = session.build(cell, 11)
    session.warm(sess)
    lora0, losses, snaps = session.program_side(sess)
    session.drop_program(sess)
    ref = session.follow(sess)
    sound, _ = session.compare(sess, lora0, losses, snaps, ref)
    ctl = session.follow(sess, "fp8")
    control, _ = session.compare(sess, lora0, ctl[0], ctl[1], ref)
    from harness.check import judge

    assert judge(sound, cell.limits), sound
    assert not judge(control, cell.limits), control
