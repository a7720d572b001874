"""Kernel micro-benchmarks.

These time the jnp references, not the Pallas kernels, on whatever backend
JAX picked (the kernels run compiled only on a TPU: ``chip_smoke.py``); what
we report per kernel is
  * the jnp-reference wall time (a host-side baseline, not a chip number),
  * the analytic FLOPs and HBM bytes of the kernel's workload,
  * arithmetic intensity + the projected TPU-v5e roofline time
    max(flops/197e12, bytes/819e9) for the default production tile shapes —
    the number the §Perf iteration tracks.

Timing harness: every bench reports the MEDIAN of ``KERNEL_REPEATS``
back-to-back calls (median, not mean — one GC pause or scheduler hiccup
must not move the reported number), after a warm-up call that also absorbs
compilation.  ``python benchmarks/kernel_bench.py --variance`` runs each
bench ``--trials`` times and prints the relative spread of the medians —
the measurement that sized the per-entry ``"threshold"`` gates these
benches carry in ``benchmarks/BENCH_baseline.json`` (see
``benchmarks/compare.py``).
"""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

PEAK_FLOPS = 197e12
HBM_BW = 819e9
# repeats per reported median; raised from 5 after the CI-variance
# measurement (see --variance) so the kernel benches are stable enough to
# gate — the ms-scale CPU references swing far less at the median of 15
# than at a single call
KERNEL_REPEATS = 15


def _time(fn, *args, repeats=None):
    """Median wall-clock of ``repeats`` calls (compile+warm excluded)."""
    repeats = KERNEL_REPEATS if repeats is None else repeats
    jax.block_until_ready(fn(*args))  # compile + warm
    ts = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        ts.append(time.perf_counter() - t0)
    return float(np.median(ts))


def bench_lora(M=256, K=4096, N=4096, r=16, dtype=jnp.bfloat16, verbose=True):
    # default M=256: the fine-tuning microbatch / decode regime where the
    # matmul is HBM-bound and fusing the low-rank path saves real bytes
    # (at M>=2048 the op is MXU-bound and fusion is time-neutral)
    from repro.kernels.lora_ref import lora_matmul_ref

    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    x = jax.random.normal(ks[0], (M, K), dtype)
    w = jax.random.normal(ks[1], (K, N), dtype)
    a = jax.random.normal(ks[2], (K, r), dtype)
    b = jax.random.normal(ks[3], (r, N), dtype)
    ref = jax.jit(lora_matmul_ref)
    t = _time(ref, x, w, a, b)
    flops = 2 * M * K * N + 2 * M * K * r + 2 * M * r * N
    # fused kernel reads x once; unfused reads x twice + (M, r) roundtrip
    bytes_fused = (M * K + K * N + K * r + r * N + M * N) * 2
    bytes_unfused = bytes_fused + (M * K + 2 * M * r) * 2
    tpu_fused = max(flops / PEAK_FLOPS, bytes_fused / HBM_BW)
    tpu_unfused = max(flops / PEAK_FLOPS, bytes_unfused / HBM_BW)
    if verbose:
        print(f"lora_matmul M{M}xK{K}xN{N} r{r}: cpu_ref {t*1e3:.1f}ms | "
              f"AI={flops/bytes_fused:.0f} | v5e fused {tpu_fused*1e6:.1f}us vs "
              f"unfused {tpu_unfused*1e6:.1f}us ({100*(tpu_unfused/tpu_fused-1):.1f}% saved)")
    return dict(name="lora_matmul", cpu_ref_us=t * 1e6, tpu_roofline_us=tpu_fused * 1e6,
                tpu_unfused_us=tpu_unfused * 1e6)


def bench_attention(B=1, H=8, S=2048, d=128, verbose=True):
    from repro.kernels.attn_ref import flash_attention_ref

    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (B, H, S, d), jnp.bfloat16)
    k = jax.random.normal(ks[1], (B, H, S, d), jnp.bfloat16)
    v = jax.random.normal(ks[2], (B, H, S, d), jnp.bfloat16)
    ref = jax.jit(lambda *a: flash_attention_ref(*a))
    t = _time(ref, q, k, v)
    flops = 4 * B * H * S * S * d  # qk + pv (causal halves it; keep upper bound)
    bytes_flash = (3 * B * H * S * d + B * H * S * d) * 2
    bytes_naive = bytes_flash + 2 * B * H * S * S * 4  # logits roundtrip fp32
    tpu_flash = max(flops / PEAK_FLOPS, bytes_flash / HBM_BW)
    tpu_naive = max(flops / PEAK_FLOPS, bytes_naive / HBM_BW)
    if verbose:
        print(f"flash_attention B{B} H{H} S{S} d{d}: cpu_ref {t*1e3:.1f}ms | "
              f"v5e flash {tpu_flash*1e6:.1f}us vs naive {tpu_naive*1e6:.1f}us "
              f"({tpu_naive/tpu_flash:.1f}x)")
    return dict(name="flash_attention", cpu_ref_us=t * 1e6,
                tpu_roofline_us=tpu_flash * 1e6, tpu_naive_us=tpu_naive * 1e6)


def bench_ssd(B=2, S=2048, H=24, P=64, N=128, verbose=True):
    from repro.models.mamba2 import ssd_chunked

    ks = jax.random.split(jax.random.PRNGKey(0), 5)
    x = jax.random.normal(ks[0], (B, S, H, P), jnp.float32)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, S, H)))
    A = -jnp.exp(jax.random.normal(ks[2], (H,)) * 0.3)
    Bm = jax.random.normal(ks[3], (B, S, N)) * 0.4
    Cm = jax.random.normal(ks[4], (B, S, N)) * 0.4
    fn = jax.jit(lambda *a: ssd_chunked(*a, chunk=256)[0])
    t = _time(fn, x, dt, A, Bm, Cm)
    Q = 256
    flops = B * H * (S * Q * N * 2 * 2 + S * Q * P * 2 + S * N * P * 4)
    if verbose:
        print(f"ssd_scan B{B} S{S} H{H} P{P} N{N}: cpu chunked {t*1e3:.1f}ms "
              f"({flops/1e9:.1f} GFLOP)")
    return dict(name="ssd_scan", cpu_ref_us=t * 1e6)


def measure_variance(trials: int = 4, repeats: int = None) -> dict[str, dict]:
    """Run every kernel bench ``trials`` times; report the medians' spread.

    The number that decides whether a bench is gateable: ``rel_spread`` =
    (max − min) / min over the trial medians.  A per-entry gate threshold
    should comfortably exceed it (we sized the committed thresholds at
    ≳3× the spread measured on the CI container class — re-run this after
    a runner change before chasing phantom regressions)."""
    global KERNEL_REPEATS
    if repeats is not None:
        KERNEL_REPEATS = repeats
    out = {}
    for fn, key in ((bench_lora, "cpu_ref_us"),
                    (bench_attention, "cpu_ref_us"),
                    (bench_ssd, "cpu_ref_us")):
        meds = [fn(verbose=False)[key] for _ in range(trials)]
        name = fn.__name__.removeprefix("bench_")
        out[name] = {
            "medians_us": [round(m, 1) for m in meds],
            "min_us": round(min(meds), 1), "max_us": round(max(meds), 1),
            "rel_spread": round((max(meds) - min(meds)) / min(meds), 4),
        }
        print(f"{name}: medians {out[name]['medians_us']} us, "
              f"spread {100*out[name]['rel_spread']:.1f}%", flush=True)
    return out


if __name__ == "__main__":
    import argparse

    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--variance", action="store_true",
                    help="measure run-to-run spread of each bench median")
    ap.add_argument("--trials", type=int, default=4)
    ap.add_argument("--repeats", type=int, default=None,
                    help=f"calls per median (default {KERNEL_REPEATS})")
    args = ap.parse_args()
    if args.variance:
        measure_variance(trials=args.trials, repeats=args.repeats)
    else:
        if args.repeats:
            KERNEL_REPEATS = args.repeats
        bench_lora()
        bench_attention()
        bench_ssd()
