#!/usr/bin/env python3
"""Smoke run of the federated split round on one TPU chip.

Drives the main path once, the way ``python -m repro.launch.train --fedsllm``
does: an ``Experiment`` built from ``fedsllm-100m`` at its full width (bf16,
12 layers, d 768, vocab 32k, LoRA rank 16, cut 1, η 0.5, ξ 1, EB allocator)
runs a 3-round campaign with random weights made from a seed.  Then, on the same
chip, it checks one client's split gradients against the monolithic graph
and runs the compiled Pallas kernels against their jnp references.

    python3 chip_smoke.py

Phases, in order, all in this one process:
  (a) the device: exits non-zero unless JAX finds a TPU;
  (b) the campaign: one trace, finite losses, round-0 loss near ln(vocab),
      a global update that changed the adapters and lowered the loss on
      round 0's batches, and the last round's model below the first there;
  (c) split vs monolithic loss and LoRA gradients;
  (d) lora_matmul, flash_attention (fedsllm-100m widths) and ssd_scan
      (mamba2-130m widths), compiled, against their references.
A failed phase prints its traceback and the script goes on to the next, then
exits 1.  Only when every phase passed does it print, as its last line,
``{"ok": true, "device": {...}}``.  Lines starting ``info:`` are one-off
readings, not benchmark numbers.

The persistent compilation cache goes where ``JAX_COMPILATION_CACHE_DIR``
says, else to ``.jax_cache`` in the checkout.
"""

from __future__ import annotations

import json
import math
import os
import sys
import time
import traceback

SRC = os.path.join(os.path.dirname(os.path.abspath(__file__)), "src")

ARCH = "fedsllm-100m"
SSD_ARCH = "mamba2-130m"
SEED = 0
ETA = 0.5
CUT = 1
# ξ weighs the global gradient in each client's local problem (4):
# ∇G_k(h) = ∇F_k(Δw+h) − ∇F_k(Δw) + ξ·∇F(Δw).  At the paper's ξ = 0.1 and
# fedsllm-100m width the clients' averaged update is an ascent direction
# (<∇F, h̄> > 0): each client cancels its own gradient and follows its own
# curvature, and the average of those moves raises the loss on the very
# batches the round trained on.  ξ = 1 keeps the full global gradient.  On a
# v5e, round 0 here gave <∇F, h̄> = +7.05 at ξ = 0.1 and −1.14 at ξ = 1.
XI = 1.0
ROUNDS = 3
# Sized from memory_analysis() of the round program compiled for a v5e:
# about 4.3 GB of temporaries plus 0.5 GB of arguments and outputs.  The
# CLI's default 8 clients x 8x256 tokens needs about 16 GB and does not fit.
CLIENTS = 4
BATCH = 4
SEQ = 256
KERNEL_SEQ = 1024  # tokens per sequence in the kernel phase
# 16 GB of HBM on one v5e, less 4 GB of headroom for what the process holds
# outside the round program: the next round's batches, the split check and
# the kernel phase.
HBM_BUDGET_BYTES = 12e9

# bf16 tolerances: the loss is ~10 nats, where one bf16 ulp is 0.0625; the
# gradients and kernel outputs carry bf16 rounding of about 2^-8 per
# operand, accumulated in f32.
LOSS_ATOL = 0.0625
GRAD_REL_L2 = 2e-2
KERNEL_REL_MAX = 2e-2


class SmokeFailure(RuntimeError):
    pass


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def smoke_experiment(cfg, clients: int, batch: int = BATCH, seq: int = SEQ):
    """The experiment ``launch/train.py --fedsllm --allocator EB`` builds."""
    from repro.api import Experiment
    from repro.config import FedsLLMConfig, RunConfig, ShapeConfig

    run_cfg = RunConfig(model=cfg, shape=ShapeConfig("chip-smoke", "train", seq, batch),
                        fedsllm=FedsLLMConfig(num_clients=clients, xi=XI))
    return Experiment.from_config(run_cfg, eta=ETA, cut=CUT, allocator="EB")


def mean_loss(state, batches, *, cfg, cut):
    """Mean over clients of the split model's loss on each client's batch:
    ``loss_round_start`` of a round that would start from ``state``, by a
    forward pass alone."""
    import jax
    import jax.numpy as jnp

    from repro.core import split

    parts = split.slice_base(state.base, cut)

    def one(batch):
        acts, enc_out = split.client_forward(parts.client_base, state.lora_c, batch, cfg)
        return split.server_forward_loss(parts.server_base, state.lora_s, acts, batch,
                                         cfg, enc_out=enc_out)

    return jnp.mean(jax.vmap(one)(batches))


def compiled_bytes(compiled) -> dict:
    m = compiled.memory_analysis()
    out = {"argument": m.argument_size_in_bytes, "output": m.output_size_in_bytes,
           "temp": m.temp_size_in_bytes, "alias": m.alias_size_in_bytes}
    out["total"] = out["argument"] + out["output"] + out["temp"] - out["alias"]
    return out


def phase_campaign(exp, stream, *, rounds):
    import jax
    import numpy as np

    cfg, clients = exp.cfg, exp.cohort
    ids = np.arange(clients)
    batches0 = exp.workload.batcher(stream, clients)(0, ids)

    t0 = time.perf_counter()
    compiled = exp.round_fn.lower(*exp.round_args(batches0, client_ids=ids)).compile()
    compile_s = time.perf_counter() - t0
    mem = compiled_bytes(compiled)
    print(f"info: sizes clients={clients} batch={stream.batch} seq={stream.seq} "
          f"rounds={rounds} eta={exp.eta} xi={exp.fcfg.xi} cut={exp.cut} "
          f"dtype={cfg.dtype} lora_rank={cfg.lora.rank}; round program bytes {mem}; "
          f"compile {compile_s:.3f}s", flush=True)
    check(mem["total"] <= HBM_BUDGET_BYTES,
          f"round program needs {mem['total']} B > budget {HBM_BUDGET_BYTES:.0f} B")

    states = [exp.state]
    last = [time.perf_counter()]

    def on_round(rec):
        jax.block_until_ready(exp.state)
        now = time.perf_counter()
        states.append(exp.state)
        print(f"info: round {rec.round} loss_round_start "
              f"{rec.metrics['loss_round_start']:.6f} loss_local_final "
              f"{rec.metrics['loss_local_final']:.6f} h_c_norm "
              f"{rec.metrics['h_c_norm']:.6f} wall {now - last[0]:.3f}s "
              f"(one-off smoke reading)", flush=True)
        last[0] = now

    res = exp.run(num_rounds=rounds, stream=stream, on_round=on_round)
    stats = jax.devices()[0].memory_stats() or {}
    if "peak_bytes_in_use" in stats:
        print(f"info: peak_bytes_in_use {stats['peak_bytes_in_use']}", flush=True)

    start = res.history("loss_round_start")
    h_norm = res.history("h_c_norm")
    check(exp.trace_count == 1, f"trace_count {exp.trace_count} != 1")
    check(len(start) == rounds, f"{len(start)} rounds ran, wanted {rounds}")
    check(bool(np.all(np.isfinite(start)) and np.all(np.isfinite(h_norm))),
          f"non-finite round metrics: loss {start} h_c_norm {h_norm}")
    ln_v = math.log(cfg.vocab_size)
    check(abs(start[0] - ln_v) <= 1.0,
          f"round-0 loss {start[0]:.4f} not within 1 nat of ln(V)={ln_v:.4f}")
    # the aggregated update reached the global adapters ...
    check(bool(np.all(h_norm > 0.0)), f"a client update was zero: h_c_norm {h_norm}")
    adapters = lambda st: jax.tree.leaves((st.lora_c, st.lora_s))  # noqa: E731
    moved = [not np.array_equal(np.asarray(a), np.asarray(b))
             for a, b in zip(adapters(states[0]), adapters(states[1]))]
    check(all(moved), f"round 0 left {moved.count(False)} of {len(moved)} global "
          f"adapter leaves unchanged")
    # ... and lowered the loss on the batches it was trained on
    score = jax.jit(lambda st, b: mean_loss(st, b, cfg=cfg, cut=exp.cut))
    after0 = float(score(states[1], batches0))
    final0 = float(score(states[-1], batches0))
    print(f"info: loss on round 0's batches: {start[0]:.6f} at the start, "
          f"{after0:.6f} after round 0, {final0:.6f} after round {rounds - 1}", flush=True)
    check(after0 < start[0], f"round 0's global update raised the loss on its own "
          f"batches: {start[0]:.6f} -> {after0:.6f}")
    # The last round's model against the first, on the same batches.  The
    # rounds' own loss_round_start are not compared: each round reads fresh
    # batches, whose spread outweighs what three rounds teach.
    check(final0 < start[0], f"the last round's model scores {final0:.6f} on round "
          f"0's batches, not below the first's {start[0]:.6f}")

    # one-off split of a round's wall-clock: host batch generation, then the
    # warm round program alone (its result is discarded)
    t0 = time.perf_counter()
    jax.block_until_ready(exp.workload.batcher(stream, clients)(rounds, ids))
    t1 = time.perf_counter()
    jax.block_until_ready(exp.round_fn(*exp.round_args(batches0, client_ids=ids)))
    t2 = time.perf_counter()
    print(f"info: batch generation {t1 - t0:.3f}s, round program {t2 - t1:.3f}s "
          f"(one-off smoke reading)", flush=True)


def phase_split(exp, stream):
    import jax
    import numpy as np

    from repro.core import split

    one = stream.batch_at(0)
    st, cfg, cut = exp.state, exp.cfg, exp.cut
    split_fn = jax.jit(lambda p, lc, ls, b: split.split_value_and_grad(
        p, lc, ls, b, cfg, cut)[:3])
    mono_fn = jax.jit(lambda p, lc, ls, b: split.monolithic_value_and_grad(
        p, lc, ls, b, cfg, cut))
    ls_, dc_s, ds_s = split_fn(st.base, st.lora_c, st.lora_s, one)
    lm_, dc_m, ds_m = mono_fn(st.base, st.lora_c, st.lora_s, one)
    dloss = abs(float(ls_) - float(lm_))
    worst, nonzero = 0.0, 0
    for a, b in zip(jax.tree.leaves((dc_s, ds_s)), jax.tree.leaves((dc_m, ds_m))):
        a = np.asarray(a, np.float32)
        b = np.asarray(b, np.float32)
        check(bool(np.all(np.isfinite(a)) and np.all(np.isfinite(b))),
              "non-finite LoRA gradient")
        ref = float(np.linalg.norm(b))
        err = float(np.linalg.norm(a - b))
        if ref > 0.0:
            nonzero += 1
            worst = max(worst, err / ref)
        else:
            check(err == 0.0, "split gradient nonzero where monolithic is zero")
    print(f"info: split vs monolithic: loss {float(ls_):.6f} vs {float(lm_):.6f} "
          f"(|d| {dloss:.3g}, tol {LOSS_ATOL}); worst grad rel-L2 {worst:.3g} "
          f"over {nonzero} nonzero leaves (tol {GRAD_REL_L2})", flush=True)
    check(dloss <= LOSS_ATOL, f"loss differs by {dloss}")
    check(nonzero > 0, "every LoRA gradient is zero")
    check(worst <= GRAD_REL_L2, f"gradient rel-L2 {worst} > {GRAD_REL_L2}")


def _rel_max(out, ref) -> float:
    import numpy as np

    out = np.asarray(out, np.float32)
    ref = np.asarray(ref, np.float32)
    check(bool(np.all(np.isfinite(out))), "non-finite kernel output")
    return float(np.max(np.abs(out - ref)) / (np.max(np.abs(ref)) + 1e-30))


def phase_kernels(cfg, ssd_cfg, *, batch, seq, kernel_seq, interpret):
    import jax
    import jax.numpy as jnp

    from repro.kernels.attn_ops import flash_attention, flash_attention_ref
    from repro.kernels.lora_ops import lora_matmul, lora_matmul_ref
    from repro.kernels.ssd_ops import ssd_scan, ssd_scan_ref

    dt = jnp.dtype(cfg.dtype)
    ks = jax.random.split(jax.random.PRNGKey(SEED), 12)
    errs = {}

    # lora_matmul at the MLP up-projection: (batch·seq, d) x (d, d_ff), rank r
    M, D, F, r = batch * seq, cfg.d_model, cfg.d_ff, cfg.lora.rank
    x = jax.random.normal(ks[0], (M, D), dt)
    w = (jax.random.normal(ks[1], (D, F)) * D ** -0.5).astype(dt)
    a = (jax.random.normal(ks[2], (D, r)) * D ** -0.5).astype(dt)
    b = (jax.random.normal(ks[3], (r, F)) * r ** -0.5).astype(dt)
    y = lora_matmul(x, w, a, b, scale=cfg.lora.scale, interpret=interpret)
    with jax.default_matmul_precision("highest"):
        y_ref = lora_matmul_ref(*(t.astype(jnp.float32) for t in (x, w, a, b)),
                                scale=cfg.lora.scale)
    errs[f"lora_matmul {M}x{D}->{F} r{r}"] = _rel_max(y, y_ref)

    # flash_attention, causal GQA at the model's heads
    H, Kv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    q = jax.random.normal(ks[4], (1, H, kernel_seq, hd), dt)
    k = jax.random.normal(ks[5], (1, Kv, kernel_seq, hd), dt)
    v = jax.random.normal(ks[6], (1, Kv, kernel_seq, hd), dt)
    o = flash_attention(q, k, v, causal=True, interpret=interpret)
    with jax.default_matmul_precision("highest"):
        o_ref = flash_attention_ref(*(t.astype(jnp.float32) for t in (q, k, v)),
                                    causal=True)
    errs[f"flash_attention H{H}/{Kv} S{kernel_seq} d{hd}"] = _rel_max(o, o_ref)

    # ssd_scan at the Mamba-2 SSD widths (float32 in, float32 state)
    Hs = ssd_cfg.ssm_expand * ssd_cfg.d_model // ssd_cfg.ssm_head_dim
    P, N = ssd_cfg.ssm_head_dim, ssd_cfg.ssm_state
    xs = jax.random.normal(ks[7], (1, kernel_seq, Hs, P), jnp.float32)
    dts = jax.nn.softplus(jax.random.normal(ks[8], (1, kernel_seq, Hs)) - 2.0)
    A = -jnp.exp(jax.random.normal(ks[9], (Hs,)) * 0.3)
    Bm = jax.random.normal(ks[10], (1, kernel_seq, N)) * N ** -0.5
    Cm = jax.random.normal(ks[11], (1, kernel_seq, N)) * N ** -0.5
    ys = ssd_scan(xs, dts, A, Bm, Cm, chunk=ssd_cfg.ssm_chunk, interpret=interpret)
    with jax.default_matmul_precision("highest"):
        ys_ref = ssd_scan_ref(xs, dts, A, Bm, Cm)
    errs[f"ssd_scan H{Hs} P{P} N{N} S{kernel_seq} chunk{ssd_cfg.ssm_chunk}"] = \
        _rel_max(ys, ys_ref)

    for name, e in errs.items():
        print(f"info: kernel {name}: max|err|/max|ref| {e:.3g} "
              f"(tol {KERNEL_REL_MAX})", flush=True)
    bad = {n: e for n, e in errs.items() if not e <= KERNEL_REL_MAX}
    check(not bad, f"kernels off their references: {bad}")


def run_phases(cfg, ssd_cfg, *, clients, batch, seq, rounds, kernel_seq,
               interpret) -> list[str]:
    """Run phases (b)-(d); returns the names of the phases that failed."""
    from repro.data.tokens import TokenStream

    exp = smoke_experiment(cfg, clients, batch, seq)
    print(f"info: {exp.describe()}", flush=True)
    stream = TokenStream(batch, seq, cfg.vocab_size, seed=SEED)
    phases = [
        ("b campaign", lambda: phase_campaign(exp, stream, rounds=rounds)),
        ("c split-vs-monolithic", lambda: phase_split(exp, stream)),
        ("d kernels", lambda: phase_kernels(cfg, ssd_cfg, batch=batch, seq=seq,
                                            kernel_seq=kernel_seq,
                                            interpret=interpret)),
    ]
    failed = []
    for name, fn in phases:
        t0 = time.perf_counter()
        try:
            fn()
        except Exception:  # report every phase; any failure fails the run
            traceback.print_exc()
            failed.append(name)
            print(f"phase {name}: FAIL ({time.perf_counter() - t0:.1f}s)", flush=True)
        else:
            print(f"phase {name}: PASS ({time.perf_counter() - t0:.1f}s)", flush=True)
    return failed


def main() -> int:
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax

    devices = jax.devices()
    d0 = devices[0]
    print(f"info: devices {devices}", flush=True)
    print(f"info: platform={d0.platform} kind={d0.device_kind} count={len(devices)}",
          flush=True)
    if d0.platform != "tpu":
        print(f"phase a device: FAIL (platform {d0.platform!r}, no TPU)", flush=True)
        return 1
    print("phase a device: PASS", flush=True)

    sys.path.insert(0, SRC)
    from repro.config import get_arch
    from repro.launch.compile_cache import use_compile_cache

    print(f"info: compile cache {use_compile_cache()}", flush=True)
    compiles: list[float] = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda event, secs, **kw: compiles.append(secs)
        if event == "/jax/core/compile/backend_compile_duration" else None)

    failed = run_phases(get_arch(ARCH), get_arch(SSD_ARCH), clients=CLIENTS,
                        batch=BATCH, seq=SEQ, rounds=ROUNDS, kernel_seq=KERNEL_SEQ,
                        interpret=False)
    print(f"info: backend compiles {len(compiles)}, {sum(compiles):.3f}s in all, "
          f"largest {max(compiles, default=0.0):.3f}s", flush=True)
    if failed:
        print(f"FAILED phases: {failed}", flush=True)
        return 1
    print(json.dumps({"ok": True, "device": {"platform": d0.platform,
                                             "kind": d0.device_kind,
                                             "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
