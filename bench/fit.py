#!/usr/bin/env python3
"""Compile each cell's round program for a described TPU v5e, no chip needed.

    JAX_PLATFORMS=cpu python3 bench/fit.py [cell ...]

For every cell of ``BENCHMARK.json`` (or those named) it builds the cell's
``Experiment`` and compiles the jitted round at the cell's shapes, with the
arguments ``Experiment.round_args`` gives, for one chip of a described
``v5e:2x2``, and prints ``memory_analysis()``: a program that does not fit
one chip's 16 GB is refused here, by the TPU compiler, before any chip time.
"""

import json
import os
import sys

BENCH = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, BENCH)
sys.path.insert(0, os.path.join(os.path.dirname(BENCH), "src"))


def compiled_bytes(compiled) -> dict:
    m = compiled.memory_analysis()
    out = {"argument": m.argument_size_in_bytes, "output": m.output_size_in_bytes,
           "temp": m.temp_size_in_bytes, "alias": m.alias_size_in_bytes}
    out["total"] = out["argument"] + out["output"] + out["temp"] - out["alias"]
    return out


def main(names) -> int:
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from harness import session, spec

    jax.config.update("jax_enable_compilation_cache", False)
    one = SingleDeviceSharding(
        topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2").devices[0])
    names = names or [w["name"] for w in spec.benchmark()["workloads"]]
    for name in names:
        cell = spec.load(name)
        tr = cell.traffic
        exp = session.build(cell, 0).exp
        shape = (tr["cohort"], tr["batch"], tr["seq"])
        batches = {"tokens": jax.ShapeDtypeStruct(shape, jnp.int32),
                   "labels": jax.ShapeDtypeStruct(shape, jnp.int32),
                   "mask": jax.ShapeDtypeStruct(shape, jnp.float32)}
        args = jax.tree.map(lambda a: jax.ShapeDtypeStruct(np.shape(a), a.dtype, sharding=one),
                            exp.round_args(batches, client_ids=np.arange(tr["cohort"])))
        mem = compiled_bytes(exp.round_fn.lower(*args).compile())
        print(json.dumps({"cell": name, "eta": exp.eta, "bytes": mem}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
