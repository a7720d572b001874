#!/usr/bin/env python3
"""Record the small trace ``data/v5e_scoped.xplane.pb`` that the reduction
to the program's layers (``harness/layers.py``) is tested on: on the chip, a
jitted function with two named scopes (``fedsllm.client`` and
``fedsllm.server``) and an unscoped one, inside the campaign's host spans
(``fedsllm.campaign``, ``fedsllm.round`` and its steps) and the session's
(``bench.window``, ``bench.run``).

    python3 bench/tests/record_scoped_trace.py <out_dir>
"""

import glob
import os
import shutil
import sys
import time

import jax
import jax.numpy as jnp


def scoped(x):
    with jax.named_scope("fedsllm.client"):
        # the barrier keeps XLA from fusing the client's ops into the server's
        y = jax.lax.optimization_barrier(jnp.tanh(x @ x))
    with jax.named_scope("fedsllm.server"):
        return jnp.sum((y @ x).astype(jnp.float32))


def main(out_dir: str) -> int:
    f = jax.jit(scoped)
    g = jax.jit(lambda x: jnp.sin(x) * 2)
    x = jnp.ones((1024, 1024), jnp.bfloat16)
    float(f(g(x)))
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    tmp = os.path.join(out_dir, "profile")
    jax.profiler.start_trace(tmp, profiler_options=opts)
    with jax.profiler.TraceAnnotation("bench.window"):
        for r in range(3):
            with (jax.profiler.TraceAnnotation("bench.run"),
                  jax.profiler.TraceAnnotation("fedsllm.campaign"),
                  jax.profiler.TraceAnnotation("fedsllm.round", round=r, cohort=1, I_loc=1)):
                with jax.profiler.TraceAnnotation("fedsllm.plan"):
                    time.sleep(0.002)
                with jax.profiler.TraceAnnotation("fedsllm.batches"):
                    b = g(x)
                with jax.profiler.TraceAnnotation("fedsllm.dispatch"):
                    loss = f(b)
                with jax.profiler.TraceAnnotation("fedsllm.read"):
                    float(loss)
    jax.profiler.stop_trace()
    path = max(glob.glob(os.path.join(tmp, "**", "*.xplane.pb"), recursive=True),
               key=os.path.getmtime)
    shutil.copy(path, os.path.join(out_dir, "v5e_scoped.xplane.pb"))
    shutil.rmtree(tmp)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
