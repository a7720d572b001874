"""Benchmark harness — one entry per paper table/figure + framework benches.

Prints ``name,us_per_call,derived`` CSV rows (derived = the headline number
each benchmark exists to produce, e.g. Fig.2's %-reduction) and mirrors the
run machine-readably to ``results/BENCH_round.json`` (name →
{us_per_call, derived}) so the perf trajectory is diffable across PRs.

  fig2_delay      paper Fig. 2 (delay vs power, 4 strategies)  [the paper's
                  only results artifact]
  solver          exact Lemma-3 solver vs fmincon-equivalent NLP
  split_step      split-learning step vs monolithic autodiff (must match)
  fedsllm_round   one full Algorithm-1+2 global round (8 clients)
  campaign        multi-round campaign engine (resampled channels, elastic
                  cohort, deadline stragglers; must stay at 1 jit trace)
  des             event-driven execution schedules: pipelined-schedule
                  campaign vs sync (simulated-delay saving must be > 0)
  scale           mega-scale population campaigns (repro.pop): per-round
                  cost vs K ∈ {10³, 10⁴, 10⁵} at fixed cohort — must be
                  O(cohort); also writes results/BENCH_scale.json
  kernels         lora / attention / ssd micro-benches (median of
                  KERNEL_REPEATS calls; gated with per-entry thresholds)
"""

from __future__ import annotations

import json
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

RESULTS_DIR = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           os.pardir, "results")

ROWS: list[tuple[str, float, str]] = []


def emit(name: str, us: float, derived: str):
    ROWS.append((name, us, derived))
    print(f"{name},{us:.1f},{derived}", flush=True)


def write_json(path: str = os.path.join(RESULTS_DIR, "BENCH_round.json")):
    """Machine-readable mirror of the CSV rows emitted this run.

    Merged into the existing file (a subset invocation like ``run.py
    campaign`` must refresh its own entries, not clobber the others)."""
    if not ROWS:
        return
    table: dict = {}
    try:
        with open(path) as f:
            table = json.load(f)
    except (OSError, ValueError):
        pass
    table.update({name: {"us_per_call": round(us, 1), "derived": derived}
                  for name, us, derived in ROWS})
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        json.dump(table, f, indent=2, sort_keys=True)
    print(f"# wrote {os.path.relpath(path)} ({len(ROWS)}/{len(table)} entries "
          f"refreshed)", flush=True)


def bench_fig2():
    from benchmarks.fig2_delay import run

    t0 = time.time()
    s = run(powers_dbm=(0.0, 10.0, 20.0), num_clients=50, verbose=False)
    us = (time.time() - t0) * 1e6
    emit("fig2_delay", us / 3,
         f"avg_reduction_vs_BA={s['avg_reduction_vs_BA_pct']:.2f}%_paper=47.63%")


def bench_solver():
    from benchmarks.solver_bench import run

    rows = run(num_clients=(50,), repeats=3, verbose=False)
    r = rows[0]
    emit("solver_exact", r["exact_s"] * 1e6, f"T={r['exact_T']:.1f}s")
    emit("solver_scipy_fmincon_eq", r["scipy_s"] * 1e6,
         f"gap_vs_exact={r['gap_pct']:+.2f}%")


def bench_split_step():
    from repro.config import LoRAConfig, get_arch, smoke_variant
    from repro.core import lora as lora_lib, split
    from repro.models import transformer as T

    cfg = smoke_variant(get_arch("fedsllm-100m")).replace(lora=LoRAConfig(rank=4))
    params, axes = T.init_params(cfg, key=jax.random.PRNGKey(0))
    lora, _ = lora_lib.init_lora(params, axes, cfg, key=jax.random.PRNGKey(1))
    lc, ls = lora_lib.split_client_server(lora, 1, cfg)
    toks = jax.random.randint(jax.random.PRNGKey(2), (4, 64), 0, cfg.vocab_size)
    batch = {"tokens": toks, "labels": toks, "mask": jnp.ones((4, 64), jnp.float32)}
    fn = jax.jit(lambda lc, ls: split.split_value_and_grad(params, lc, ls, batch, cfg, 1)[0])
    fn(lc, ls).block_until_ready()
    t0 = time.perf_counter()
    n = 10
    for _ in range(n):
        fn(lc, ls).block_until_ready()
    us = (time.perf_counter() - t0) / n * 1e6
    mono = jax.jit(lambda lc, ls: split.monolithic_value_and_grad(params, lc, ls, batch, cfg, 1)[0])
    d = abs(float(fn(lc, ls)) - float(mono(lc, ls)))
    emit("split_step", us, f"split_vs_monolithic_loss_diff={d:.2e}")


def bench_fedsllm_round():
    from repro.api import Experiment
    from repro.config import (FedsLLMConfig, LoRAConfig, RunConfig, SHAPES,
                              get_arch, smoke_variant)
    from repro.data.tokens import TokenStream, client_batches

    cfg = smoke_variant(get_arch("fedsllm-100m")).replace(lora=LoRAConfig(rank=4))
    run_cfg = RunConfig(model=cfg, shape=SHAPES["train_4k"],
                        fedsllm=FedsLLMConfig(num_clients=8))
    exp = Experiment.from_config(run_cfg, eta=0.5, cut=1, allocator="EB")
    stream = TokenStream(2, 64, cfg.vocab_size, seed=0)
    batches = client_batches(stream, 0, 8)
    res = exp.run_round(batches)  # compile
    jax.block_until_ready(res.state.lora_c)
    t0 = time.perf_counter()
    res = exp.run_round(batches)
    jax.block_until_ready(res.state.lora_c)
    us = (time.perf_counter() - t0) * 1e6
    emit("fedsllm_round_8clients", us,
         f"loss={float(res.metrics['loss_round_start']):.3f}_"
         f"round_sim={res.wall_clock:.2f}s")


def bench_campaign():
    """Experiment.run: N resampled-channel rounds through one jit trace."""
    from repro.api import Experiment
    from repro.config import (FedsLLMConfig, LoRAConfig, RunConfig, SHAPES,
                              get_arch, smoke_variant)
    from repro.data.tokens import TokenStream

    cfg = smoke_variant(get_arch("fedsllm-100m")).replace(lora=LoRAConfig(rank=4))
    run_cfg = RunConfig(model=cfg, shape=SHAPES["train_4k"],
                        fedsllm=FedsLLMConfig(num_clients=8))
    exp = Experiment.from_config(run_cfg, eta=0.5, cut=1, allocator="EB")
    stream = TokenStream(2, 64, cfg.vocab_size, seed=0)
    # deadline at the 75th percentile of the round-0 delays: slow clients
    # under later fades become stragglers instead of stretching the round
    deadline = float(np.quantile(exp.timing.total, 0.75))
    exp.run(num_rounds=1, stream=stream, cohort=4, deadline=deadline)  # compile
    t0 = time.perf_counter()
    # rounds are absolute: this continues at round 1 and runs two more
    res = exp.run(num_rounds=3, stream=stream, cohort=4, deadline=deadline,
                  resample_channel=True)
    jax.block_until_ready(res.state.lora_c)
    us = (time.perf_counter() - t0) / res.num_rounds * 1e6
    emit("campaign_round_8users_cohort4", us,
         f"traces={exp.trace_count}_stragglers={res.straggler_rate:.2f}_"
         f"sim={res.total_time:.1f}s")

    # joint-η reallocation: every round re-solves (16)/(17) on its own
    # channel draw and adopts the solved η (quantized to the η-bucket grid),
    # so the jit cache must stay bounded by the bucket count — the
    # acceptance bar for re-solving Lemma 1/2 jointly without recompiling
    exp2 = Experiment.from_config(run_cfg, eta=0.2, cut=1, allocator="EB",
                                  scenario="geo-blockfade")
    exp2.run(num_rounds=1, stream=stream, cohort=4, reallocate=True)  # compile
    t0 = time.perf_counter()
    res2 = exp2.run(num_rounds=4, stream=stream, cohort=4, reallocate=True)
    jax.block_until_ready(res2.state.lora_c)
    us2 = (time.perf_counter() - t0) / res2.num_rounds * 1e6
    buckets = len(exp2.eta_buckets)
    assert exp2.trace_count <= buckets, (exp2.trace_count, buckets)
    emit("campaign_realloc_joint_eta", us2,
         f"traces={exp2.trace_count}_eta_buckets={buckets}_"
         f"scenario=geo-blockfade_sim={res2.total_time:.1f}s")

    # joint-η reallocation under a QUEUED backhaul: the edge-cloud fifo
    # metro link turns on the allocator↔queueing fixed point
    # (net.allocation.solve_wait_aware) inside every per-round warm
    # re-solve.  At the default metro capacity the loop early-exits right
    # after the wait-blind iterate, so this prices the full wiring (per-η
    # hop evaluation + true-queue pricing) at its steady-state cost — and
    # the jit cache must stay η-bucket bounded exactly like the serial
    # reallocating campaign above
    from repro.net.topology import EdgeCloudTopology

    exp4 = Experiment.from_config(
        run_cfg, eta=0.2, cut=1, allocator="proposed",
        scenario="geo-blockfade",
        topology=EdgeCloudTopology(num_edges=2, backhaul_model="fifo"))
    exp4.run(num_rounds=1, stream=stream, cohort=4, reallocate=True)  # compile
    t0 = time.perf_counter()
    res4 = exp4.run(num_rounds=3, stream=stream, cohort=4, reallocate=True)
    jax.block_until_ready(res4.state.lora_c)
    us4 = (time.perf_counter() - t0) / res4.num_rounds * 1e6
    buckets4 = len(exp4.eta_buckets)
    assert exp4.trace_count <= buckets4, (exp4.trace_count, buckets4)
    diag = exp4.topology.wait_diag
    assert diag and all(d.converged for d in diag), diag
    emit("campaign_realloc_queued", us4,
         f"traces={exp4.trace_count}_eta_buckets={buckets4}_"
         f"topology=edge-cloud+fifo_wait_iters="
         f"{max(d.iters for d in diag)}_sim={res4.total_time:.1f}s")

    # SCAFFOLD carries (K, …) control variates through the same jitted round
    # (value-only gather/scatter): the derived number is its per-round cost
    # relative to the gd campaign above, and the trace count must stay 1
    exp3 = Experiment.from_config(run_cfg, eta=0.5, cut=1, allocator="EB",
                                  local_algo="scaffold")
    exp3.run(num_rounds=1, stream=stream, cohort=4, deadline=deadline)  # compile
    t0 = time.perf_counter()
    res3 = exp3.run(num_rounds=3, stream=stream, cohort=4, deadline=deadline,
                    resample_channel=True)
    jax.block_until_ready(res3.state.lora_c)
    us3 = (time.perf_counter() - t0) / res3.num_rounds * 1e6
    assert exp3.trace_count == 1, exp3.trace_count
    emit("campaign_scaffold", us3,
         f"overhead_vs_gd={100.0 * (us3 / us - 1.0):+.1f}%_traces=1")


def bench_des():
    """Event-driven schedules: a pipelined-schedule campaign vs sync.

    The derived number is the simulated-delay saving the microbatch overlap
    buys on identical rounds (the acceptance bar: strictly positive); the
    wall-clock entry (``campaign_pipelined``) rides the compare.py gate so
    a planner-path slowdown fails CI like any other hot path."""
    from repro.api import Experiment
    from repro.config import (FedsLLMConfig, LoRAConfig, RunConfig, SHAPES,
                              get_arch, smoke_variant)
    from repro.data.tokens import TokenStream

    cfg = smoke_variant(get_arch("fedsllm-100m")).replace(lora=LoRAConfig(rank=4))
    run_cfg = RunConfig(model=cfg, shape=SHAPES["train_4k"],
                        fedsllm=FedsLLMConfig(num_clients=8))
    stream = TokenStream(2, 64, cfg.vocab_size, seed=0)

    def campaign(schedule):
        exp = Experiment.from_config(run_cfg, eta=0.5, cut=1, allocator="EB",
                                     schedule=schedule)
        exp.run(num_rounds=1, stream=stream, cohort=4)  # compile
        t0 = time.perf_counter()
        res = exp.run(num_rounds=3, stream=stream, cohort=4)
        jax.block_until_ready(res.state.lora_c)
        us = (time.perf_counter() - t0) / res.num_rounds * 1e6
        assert exp.trace_count == 1, exp.trace_count
        return us, res

    us_sync, res_sync = campaign("sync")
    us_pipe, res_pipe = campaign("pipelined")
    saved = 100.0 * (1.0 - res_pipe.total_time / res_sync.total_time)
    assert res_pipe.total_time < res_sync.total_time, (
        res_pipe.total_time, res_sync.total_time)
    emit("campaign_pipelined", us_pipe,
         f"sim_saved_vs_sync={saved:.2f}%_sync_round={us_sync:.0f}us_traces=1")


def write_scale_json(per_round_us: dict, cohort: int,
                     path: str = os.path.join(RESULTS_DIR,
                                              "BENCH_scale.json")):
    """Top-level scale trajectory: rounds/sec vs K at fixed cohort.

    Merged into the existing file like ``write_json`` (other entries — e.g.
    future sync-family or sharded-mesh trajectories — must survive a
    ``run.py scale`` refresh)."""
    table: dict = {}
    try:
        with open(path) as f:
            table = json.load(f)
    except (OSError, ValueError):
        pass
    ks = sorted(per_round_us)
    table["megascale_async_meanfield"] = {
        "cohort": cohort,
        "schedule": "async",
        "topology": "edge-cloud+fifo",
        "population": "meanfield",
        "us_per_round": {str(k): round(per_round_us[k], 1) for k in ks},
        "rounds_per_sec": {str(k): round(1e6 / per_round_us[k], 3)
                           for k in ks},
        "ratio_Kmax_vs_Kmin": round(per_round_us[ks[-1]]
                                    / per_round_us[ks[0]], 3),
    }
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        json.dump(table, f, indent=2, sort_keys=True)
    print(f"# wrote {os.path.relpath(path)}", flush=True)


def bench_scale():
    """Mega-scale population campaigns: per-round cost must be O(cohort).

    The same async edge-cloud+fifo campaign under the ``meanfield``
    population at K = 10³, 10⁴, 10⁵ simulated clients with a fixed cohort,
    frozen channel (``resample_channel=False`` — the constructor's one
    exact K-sized solve + queue pricing is the per-campaign cost; each
    round then costs only the window batch, the O(cohort) compaction and
    the O(C) timeline).  The gate entry is the K=10⁵ per-round wall-clock;
    the derived ratio vs K=10³ is the O(cohort) acceptance bar (the ISSUE
    asks < 2x at equal cohort)."""
    from repro.api import Experiment
    from repro.config import (FedsLLMConfig, LoRAConfig, RunConfig, SHAPES,
                              get_arch, smoke_variant)
    from repro.data.tokens import TokenStream
    from repro.net.topology import EdgeCloudTopology

    cfg = smoke_variant(get_arch("fedsllm-100m")).replace(lora=LoRAConfig(rank=4))
    stream = TokenStream(2, 64, cfg.vocab_size, seed=0)
    cohort = 8
    per_round_us: dict[int, float] = {}
    for K in (1_000, 10_000, 100_000):
        run_cfg = RunConfig(model=cfg, shape=SHAPES["train_4k"],
                            fedsllm=FedsLLMConfig(num_clients=K))
        exp = Experiment.from_config(
            run_cfg, eta=0.5, cut=1, allocator="EB",
            scenario="geo-blockfade", schedule="async",
            topology=EdgeCloudTopology(num_edges=8, backhaul_model="fifo"),
            population="meanfield")
        exp.run(num_rounds=1, stream=stream, cohort=cohort,
                resample_channel=False)  # compile at (cohort, …)
        t0 = time.perf_counter()
        res = exp.run(num_rounds=4, stream=stream, cohort=cohort,
                      resample_channel=False)
        jax.block_until_ready(res.state.lora_c)
        per_round_us[K] = (time.perf_counter() - t0) / res.num_rounds * 1e6
        assert exp.trace_count == 1, exp.trace_count
        assert all(len(r.client_ids) == cohort for r in res.records)
    ratio = per_round_us[100_000] / per_round_us[1_000]
    emit("campaign_megascale", per_round_us[100_000],
         f"K=1e5_cohort={cohort}_round_cost_vs_K1e3={ratio:.2f}x_traces=1")
    write_scale_json(per_round_us, cohort)


def bench_kernels():
    from benchmarks.kernel_bench import bench_attention, bench_lora, bench_ssd

    r = bench_lora(verbose=False)
    emit("kernel_lora_matmul_cpu_ref", r["cpu_ref_us"],
         f"v5e_fused={r['tpu_roofline_us']:.1f}us_vs_unfused={r['tpu_unfused_us']:.1f}us")
    r = bench_attention(verbose=False)
    emit("kernel_flash_attention_cpu_ref", r["cpu_ref_us"],
         f"v5e_flash={r['tpu_roofline_us']:.1f}us_vs_naive={r['tpu_naive_us']:.1f}us")
    r = bench_ssd(verbose=False)
    emit("kernel_ssd_scan_cpu_chunked", r["cpu_ref_us"], "chunked=MXU-friendly")


def bench_pipeline():
    """Split-learning microbatch pipelining speedup under §IV channel draws."""
    import numpy as np

    from repro.config import FedsLLMConfig
    from repro.core import delay_model as dm
    from repro.core import resource_alloc as ra
    from repro.parallel import pipeline

    fcfg = FedsLLMConfig(num_clients=20)
    net = dm.sample_network(fcfg, seed=0)
    t0 = time.time()
    a = ra.solve_fixed_eta_exact(fcfg, net, 0.1)
    stages = pipeline.split_stage_times(fcfg, net, 0.1, a.A, a)
    out = pipeline.pipeline_round_time(stages, 8)
    us = (time.time() - t0) * 1e6
    emit("split_pipeline_m8", us,
         f"median_speedup={float(np.median(out['speedup'])):.2f}x")


def bench_compression():
    from benchmarks.compression_delay import run

    t0 = time.time()
    rows = run(fractions=(1.0, 0.1), num_clients=20, verbose=False)
    us = (time.time() - t0) * 1e6 / len(rows)
    gain = 100 * (1 - rows[-1]["T"] / rows[0]["T"])
    emit("compression_delay", us, f"topk10pct_delay_gain={gain:.2f}%")


def main() -> None:
    from repro.launch.compile_cache import use_compile_cache

    use_compile_cache()
    which = sys.argv[1] if len(sys.argv) > 1 else "all"
    print("name,us_per_call,derived")
    if which in ("all", "solver"):
        bench_solver()
    if which in ("all", "split"):
        bench_split_step()
    if which in ("all", "round"):
        bench_fedsllm_round()
    if which in ("all", "campaign"):
        bench_campaign()
    if which in ("all", "des"):
        bench_des()
    if which in ("all", "scale"):
        bench_scale()
    if which in ("all", "kernels"):
        bench_kernels()
    if which in ("all", "pipeline"):
        bench_pipeline()
    if which in ("all", "compression"):
        bench_compression()
    if which in ("all", "fig2"):
        bench_fig2()
    write_json()


if __name__ == "__main__":
    main()
