#!/usr/bin/env python3
"""Readings that the limits of a cell's output check are set from.

    python3 bench/calibrate.py --workload <cell> --seeds 1,2,... \\
        [--control-seeds 1,2,3] [--out calibrate-<cell>.json]

In one process on the chip, for each seed: the set-up of ``run.py`` (the
Experiment with the benchmark's weights, driven through the checked rounds
by the window's own call) and the float32 reference after it, giving the
program's readings.  For the control seeds also the control (the reference
computed in float8 in the program's place) and faults planted in the
reference put in the program's place: half of the cohort left out of the
round; the adapters' update left out (a state returned unchanged); the
update altered where it is produced (applied twice); and on the client's
side alone (the layers below the cut), its update left out and its update
halved, as a zeroed or halved gradient through the cut would leave them.
Each reading is one JSON line; the file gathers them all.
"""

import argparse
import json
import os
import sys
import time

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)


def main() -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", required=True)
    p.add_argument("--control-seeds", default="")
    p.add_argument("--out", default=None)
    a = p.parse_args()
    sys.path.insert(0, BENCH)
    sys.path.insert(0, os.path.join(ROOT, "src"))
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax

    from harness import session, spec
    from repro.launch.compile_cache import use_compile_cache

    if jax.devices()[0].platform != "tpu":
        print("no TPU", file=sys.stderr)
        return 2
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    use_compile_cache()
    cell = spec.load(a.workload)
    seeds = [int(s) for s in a.seeds.split(",") if s]
    controls = {int(s) for s in a.control_seeds.split(",") if s}
    rows = []
    for seed in seeds:
        t0 = time.time()
        sess = session.build(cell, seed)
        session.warm(sess)
        t1 = time.time()
        lora0, losses, snaps = session.program_side(sess)
        session.drop_program(sess)
        ref = session.follow(sess)
        t2 = time.time()
        cut = cell.traffic["cut"]

        def readings(snaps_, losses_=None):
            return session.compare(sess, lora0, ref[0] if losses_ is None else losses_,
                                   snaps_, ref)

        def on_client(f):
            """The reference's adapters with ``f(Δ)`` for Δ on the client's
            layers."""
            return [jax.tree.map(lambda r, a: r.at[:cut].set(a[:cut] + f(r[:cut] - a[:cut])),
                                 s, lora0) for s in ref[1]]

        row = {"seed": seed, "program": readings(snaps, losses),
               "program_losses": losses, "reference_losses": ref[0],
               "setup_s": t1 - t0, "reference_s": t2 - t1}
        if seed in controls:
            ctl = session.follow(sess, "fp8")
            row["control"] = readings(ctl[1], ctl[0])
            half = session.follow(sess, clients=range(cell.traffic["cohort"] // 2))
            row["fault_half_cohort"] = readings(half[1], half[0])
            row["fault_unchanged"] = readings([lora0] * len(ref[1]))
            row["fault_update_doubled"] = readings(
                [jax.tree.map(lambda a, b: 2 * a - b, r, lora0) for r in ref[1]])
            row["fault_client_unchanged"] = readings(on_client(lambda d: 0 * d))
            row["fault_client_halved"] = readings(on_client(lambda d: d / 2))
            row["control_s"] = time.time() - t2
        rows.append(row)
        print(json.dumps(row), flush=True)
    if a.out:
        os.makedirs(os.path.dirname(a.out) or ".", exist_ok=True)
        with open(a.out, "w") as f:
            json.dump({"cell": a.workload, "rows": rows}, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
