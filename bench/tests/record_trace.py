#!/usr/bin/env python3
"""Record the small trace ``data/v5e_small.xplane.pb`` that the trace
reduction is tested on: a few jitted products on the chip, inside the host
spans the session writes (``bench.window``, ``bench.run``, ``bench.batches``).

    python3 bench/tests/record_trace.py <out_dir>
"""

import glob
import os
import shutil
import sys
import time

import jax
import jax.numpy as jnp


def main(out_dir: str) -> int:
    f = jax.jit(lambda x: jnp.tanh(x @ x) @ x)
    x = jnp.ones((1024, 1024), jnp.bfloat16)
    f(x).block_until_ready()
    tmp = os.path.join(out_dir, "profile")
    with jax.profiler.trace(tmp):
        with jax.profiler.TraceAnnotation("bench.window"):
            for _ in range(3):
                with jax.profiler.TraceAnnotation("bench.batches"):
                    time.sleep(0.002)
                with jax.profiler.TraceAnnotation("bench.run"):
                    f(x).block_until_ready()
    path = max(glob.glob(os.path.join(tmp, "**", "*.xplane.pb"), recursive=True),
               key=os.path.getmtime)
    shutil.copy(path, os.path.join(out_dir, "v5e_small.xplane.pb"))
    shutil.rmtree(tmp)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
