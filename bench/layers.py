#!/usr/bin/env python3
"""Profile timed windows of one cell on the chip and print the program's own
layers: device time under each named scope of the round, host time in each
``fedsllm.*`` span of the campaign, and idle time by span.

    python3 bench/layers.py --workload <cell> --seed <n> --seconds <s> [--windows <k>]

Set-up is ``bench/run.py``'s; then ``--windows`` windows are traced one after
another, as ``--trace 1`` traces its one (no reference check follows).  Each
window prints to standard error its scope and idle-by-span tables, the clock
check (each round program's run on the device's ``XLA Modules`` line lies
between its round's ``fedsllm.dispatch`` start and ``fedsllm.read`` end),
the round's device time under the client, server and aggregation scopes and
its largest ops outside them, the scope of the top self-time ops, and the
longest idle gap; to standard output one JSON line with the per-layer
readings of ``bench/metrics`` and the window's ``round_s``.
"""

import time

T_START = time.time()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
READERS = ["device_idle_share", "mfu", "round_device_ms", "host_ms_per_round",
           "client_ms", "server_ms", "merge_ms", "aggregate_ms", "attention_ms",
           "ssd_ms", "campaign_host_ms", "idle_campaign_ms"]


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def window_report(lt, m: dict, red: dict) -> dict:
    """Tables to standard error; the readings and checks as a dict.  ``red``
    is ``trace.reduce`` of the same trace."""
    from harness import layers, spec

    lo, hi = layers.window(lt)
    m = dict(m, **layers.keys(lt))
    readings = {n: spec.module("metrics", n).read(m) for n in READERS}
    self_s = layers.op_self_times(lt, lo, hi)
    for scope, s in sorted(layers.scope_seconds(self_s).items(), key=lambda kv: -kv[1]):
        log(f"scope {scope:24s} {s!r} s")
    idle = sorted(layers.idle_by_span(lt, lo, hi).items(), key=lambda kv: -kv[1][0])
    for (span, label), (s, n) in idle:
        log(f"idle {span:22s} {label:48s} {s!r} s ({n} gaps)")
    prog = layers.round_program(self_s)
    clock = layers.clock_check(lt, lo, hi, prog)
    share, outside = layers.coverage(self_s, prog)
    for op, p, s in outside:
        log(f"outside client/server/aggregate: {op} {p!r} {s!r} s")
    by_op = {}
    for (op, _, p), s in self_s.items():
        by_op.setdefault(op, []).append((s, p))
    top_scopes = [[op, s, max(by_op.get(op, [(0, "")]))[1]]
                  for op, s in red["breakdown"]["device_ops"]]
    for op, s, p in top_scopes:
        log(f"top op {op} {s!r} s: {p!r}")
    gaps = layers.idle_gaps(lt, lo, hi)
    longest = max(gaps, key=lambda g: g[1] - g[0]) if gaps else None
    return {"metrics": readings,
            "clock": {"rounds": len(clock),
                      "lead_ns": [min((a for a, _ in clock), default=None),
                                  max((a for a, _ in clock), default=None)],
                      "tail_ns": [min((b for _, b in clock), default=None),
                                  max((b for _, b in clock), default=None)]},
            "client_server_aggregate_share": share,
            "top_ops": top_scopes,
            "longest_gap_s": (longest[1] - longest[0]) / 1e9 if longest else None,
            "longest_gap_at": layers.label_gap(lt, lo, hi, longest) if longest else None}


def measure(cell, seed: int, seconds: float, windows: int, peak_flops: float):
    import jax

    from harness import layers, session, spec, trace, traffic

    sess = session.build(cell, seed)
    session.listen_compiles(sess)
    session.warm(sess)
    log(f"set-up {time.time() - T_START:.1f}s")
    flops = spec.module("flops", cell.family).flops_per_token(cell.config, cell.traffic["seq"])
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0  # host spans and runtime events only, as --trace 1
    for w in range(windows):
        tdir = tempfile.mkdtemp(prefix="bench-layers-")
        jax.profiler.start_trace(tdir, profiler_options=opts)
        win = session.timed(sess, seconds)
        jax.profiler.stop_trace()
        if win.traces or win.compiles:
            raise RuntimeError(f"the window compiled: {win.traces} round-function "
                               f"traces, {win.compiles} backend compiles")
        rounds = len(win.etas)
        tokens = sum(traffic.round_tokens(cell.traffic, e) for e in win.etas)
        t0 = time.time()
        lt = layers.load(trace.find(tdir))
        red = trace.reduce(layers.as_trace(lt))
        m = {"busy_s": red["busy_s"], "window_s": red["window_s"], "rounds": rounds,
             "model_flops": flops * tokens, "peak_flops": peak_flops}
        out = {"workload": cell.name, "seed": seed, "window": w,
               "round_s": win.seconds / rounds, "rounds": rounds,
               **window_report(lt, m, red)}
        shutil.rmtree(tdir, ignore_errors=True)
        log(f"window {w}: reduced in {time.time() - t0:.1f}s")
        print(json.dumps(out), flush=True)


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--windows", type=int, default=1)
    args = p.parse_args(argv)
    sys.path.insert(0, BENCH)
    sys.path.insert(0, os.path.join(ROOT, "src"))
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from harness import peaks, spec

    cell = spec.load(args.workload)
    import jax

    devices = jax.devices()
    d0 = devices[0]
    log(f"device platform={d0.platform} device_kind={d0.device_kind} count={len(devices)}")
    if d0.platform != "tpu" or len(devices) < cell.chips:
        log(f"the cell needs {cell.chips} TPU chips; JAX finds {len(devices)} {d0.platform}")
        return 2
    peak = peaks.lookup(d0.device_kind)

    from repro.launch.compile_cache import use_compile_cache

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    # JAX leaves op metadata out of the cache key by default, so a program
    # that differs from a cached one only in its named scopes would run the
    # cached executable, whose ops carry the old scope names: key on it here
    # (the profiled run compiles anew once per checkout path)
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    log(f"compile cache {use_compile_cache()}")
    measure(cell, args.seed, args.seconds, args.windows, peak["bf16_flops"] * cell.chips)
    return 0


if __name__ == "__main__":
    sys.exit(main())
