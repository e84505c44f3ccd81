#!/usr/bin/env python3
"""Records the small trace of ``benchmarks/tests/data/``: on the chip, three
rounds of a matmul program under a ``dispatch`` span, each followed by a
``host_pause`` span in which the device idles.  Run by hand, once."""

import shutil
import sys
import time
from pathlib import Path

import jax
import jax.numpy as jnp

out = Path(sys.argv[1] if len(sys.argv) > 1 else "chiprun_out/small_trace")
shutil.rmtree(out, ignore_errors=True)


@jax.jit
def work(x):
    def body(c, _):
        return jnp.tanh(c @ c) * 0.01 + c, None
    return jax.lax.scan(body, x, None, length=4)[0]


x = jnp.ones((1024, 1024), jnp.bfloat16)
work(x).block_until_ready()
opts = jax.profiler.ProfileOptions()
opts.python_tracer_level = 0
opts.host_tracer_level = 2
jax.profiler.start_trace(str(out), profiler_options=opts)
for _ in range(3):
    with jax.profiler.TraceAnnotation("dispatch"):
        y = work(x)
        y.block_until_ready()
    with jax.profiler.TraceAnnotation("host_pause"):
        time.sleep(0.005)
jax.profiler.stop_trace()
print(sorted(str(p) for p in out.rglob("*.xplane.pb")))
