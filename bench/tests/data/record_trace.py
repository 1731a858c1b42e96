#!/usr/bin/env python3
"""Record a small trace that ``test_bench_xplane.py`` reads: a few
jitted operations on the chip, inside the benchmark's own host spans
(``bench.window`` around all, ``bench.call`` around each call) with an
idle gap between calls.

    python bench/tests/data/record_trace.py <out.xplane.pb> [--chips N]

With ``--chips N`` every call runs the operation on each of the first N
chips at once (``tpu_four.xplane.pb`` was recorded with ``--chips 4``);
without it on the first chip (``tpu_small.xplane.pb``).
"""
import argparse
import glob
import shutil
import sys
import tempfile
import time

import jax
import jax.numpy as jnp


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("out")
    ap.add_argument("--chips", type=int, default=1)
    args = ap.parse_args()
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < args.chips:
        raise SystemExit(f"record_trace: needs {args.chips} TPU chips")
    f = jax.jit(lambda x: jnp.tanh(x @ x).sum(axis=0))
    xs = [jax.device_put(jnp.ones((1024, 1024), jnp.float32), d)
          for d in devs[:args.chips]]
    jax.block_until_ready([f(x) for x in xs])
    d = tempfile.mkdtemp()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(d, profiler_options=opts)
    with jax.profiler.TraceAnnotation("bench.window"):
        for _ in range(3):
            with jax.profiler.TraceAnnotation("bench.call"):
                jax.block_until_ready([f(x) for x in xs])
            with jax.profiler.TraceAnnotation("bench.sleep"):
                time.sleep(0.002)
    jax.profiler.stop_trace()
    shutil.copy(glob.glob(f"{d}/**/*.xplane.pb", recursive=True)[0],
                args.out)
    shutil.rmtree(d)
    return 0


if __name__ == "__main__":
    sys.exit(main())
