#!/usr/bin/env python3
"""Run one benchmark cell once on the chip and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell (``BENCHMARK.json``) names a configuration (``bench/configs``) and a
traffic mix (``bench/traffic``).  The run builds the program's
``Experiment`` with the benchmark's weights from ``--seed``, drives it
through the rounds the output check follows (set-up, with every compile),
then times whole global rounds for ``--seconds`` through
``Experiment.run``.  ``--trace 1`` profiles the window and reports the
per-layer metrics instead of the end-to-end ones.  After the window the
program is dropped and the float32 reference follows the checked rounds.

It exits non-zero, printing no result, where JAX finds no TPU or fewer chips
than the cell needs, or a device the peaks table does not know.  The last
line of standard output is one JSON object; the last lines of standard error
are the compared numbers beside their limits.
"""

import time

T_START = time.time()

import argparse  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)


def parse(argv):
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return p.parse_args(argv)


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def main(argv=None) -> int:
    args = parse(argv)
    sys.path.insert(0, BENCH)
    sys.path.insert(0, os.path.join(ROOT, "src"))
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from harness import peaks, spec

    cell = spec.load(args.workload)
    import jax

    devices = jax.devices()
    d0 = devices[0]
    log(f"device platform={d0.platform} device_kind={d0.device_kind} count={len(devices)}")
    if d0.platform != "tpu":
        log(f"no TPU: JAX runs on {d0.platform!r}")
        return 2
    if len(devices) < cell.chips:
        log(f"the cell needs {cell.chips} chips, JAX finds {len(devices)}")
        return 2
    peak = peaks.lookup(d0.device_kind)

    from repro.launch.compile_cache import use_compile_cache

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    log(f"compile cache {use_compile_cache()}")
    result = measure(cell, args, peak, devices[:cell.chips])
    for name, c in result["checks"].items():
        log(f"check {name} {c['value']!r} limit {c['limit']!r}")
    print(json.dumps(result), flush=True)
    return 0


def measure(cell, args, peak: dict, devices) -> dict:
    import jax

    from harness import session, spec, trace, traffic

    sess = session.build(cell, args.seed)
    session.listen_compiles(sess)
    session.warm(sess)
    log(f"checked rounds: loss_round_start "
        f"{[c.loss for c in sess.checked]} trace_count {sess.exp.trace_count} "
        f"compiles {len(sess.compiles)}")
    tdir = tempfile.mkdtemp(prefix="bench-trace-") if args.trace else None
    if tdir:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0  # host spans and runtime events only
        jax.profiler.start_trace(tdir, profiler_options=opts)
    setup_s = time.time() - T_START
    win = session.timed(sess, args.seconds)
    if tdir:
        jax.profiler.stop_trace()
    mem = session.peak_bytes(len(devices))
    rounds = len(win.etas)
    tokens = sum(traffic.round_tokens(cell.traffic, e) for e in win.etas)
    log(f"window {win.seconds!r}s rounds {rounds} tokens {tokens} "
        f"loss_round_start {win.losses} round-fn traces {win.traces} "
        f"backend compiles {win.compiles}")
    if win.traces or win.compiles:
        raise RuntimeError(f"the window compiled: {win.traces} round-function "
                           f"traces, {win.compiles} backend compiles")

    lora0, prog_losses, prog_snaps = session.program_side(sess)
    session.drop_program(sess)
    t_ref = time.time()
    gaps, info = session.compare(sess, lora0, prog_losses, prog_snaps, session.follow(sess))
    log(f"reference {time.time() - t_ref:.1f}s; program losses {prog_losses}; {info}")
    failed = sum(not math.isfinite(x) for x in win.losses)
    from harness.check import judge

    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(devices), "memory_peak_bytes": mem}
    out = {"correct": judge(gaps, cell.limits) and failed == 0,
           "attempted": rounds, "failed": failed}
    if args.trace:
        red = trace.reduce(trace.load(trace.find(tdir)))
        shutil.rmtree(tdir, ignore_errors=True)
        device.update(busy_s=red["busy_s"], window_s=red["window_s"])
        flops = spec.module("flops", cell.family).flops_per_token(
            cell.config, cell.traffic["seq"])
        m = {"busy_s": red["busy_s"], "window_s": red["window_s"], "rounds": rounds,
             "model_flops": flops * tokens, "peak_flops": peak["bf16_flops"] * len(devices)}
        metrics = {}
        for entry in cell.per_layer:
            v = spec.module("metrics", entry["name"]).read(m)
            if v is not None:
                metrics[entry["name"]] = {"value": v, "unit": entry["unit"]}
        out.update(metrics=metrics, device=device, breakdown=red["breakdown"])
    else:
        e2e = {"tokens_per_s": tokens / win.seconds, "round_s": win.seconds / rounds,
               "setup_s": setup_s}
        out.update(metrics={m["name"]: {"value": e2e[m["name"]], "unit": m["unit"]}
                            for m in cell.end_to_end}, device=device)
    log(f"not compared: {({k: v for k, v in gaps.items() if k not in cell.limits})}")
    out["checks"] = {k: {"value": gaps[k], "limit": lim} for k, lim in cell.limits.items()}
    return out


if __name__ == "__main__":
    sys.exit(main())
