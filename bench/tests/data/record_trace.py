#!/usr/bin/env python3
"""Record the small trace that ``test_bench_xplane.py`` reads: a few
jitted operations on the chip, inside the benchmark's own host spans
(``bench.window`` around all, ``bench.call`` around each call) with an
idle gap between calls.

    python bench/tests/data/record_trace.py <out.xplane.pb>
"""
import glob
import shutil
import sys
import tempfile
import time

import jax
import jax.numpy as jnp


def main(out: str) -> int:
    if jax.devices()[0].platform != "tpu":
        raise SystemExit("record_trace: needs a TPU")
    f = jax.jit(lambda x: jnp.tanh(x @ x).sum(axis=0))
    x = jnp.ones((1024, 1024), jnp.float32)
    f(x).block_until_ready()
    d = tempfile.mkdtemp()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(d, profiler_options=opts)
    with jax.profiler.TraceAnnotation("bench.window"):
        for _ in range(3):
            with jax.profiler.TraceAnnotation("bench.call"):
                f(x).block_until_ready()
            with jax.profiler.TraceAnnotation("bench.sleep"):
                time.sleep(0.002)
    jax.profiler.stop_trace()
    shutil.copy(glob.glob(f"{d}/**/*.xplane.pb", recursive=True)[0], out)
    shutil.rmtree(d)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
