#!/usr/bin/env python3
"""Record the small trace that ``test_bench_trace.py`` reads, on a TPU.

    python3 bench/tests/make_trace_data.py <out_dir>

Three dispatches of a small matmul program, each under a
``bench.train_step`` host span and waited for, with a 20 ms host sleep
under ``bench.sleep`` between them, so the trace holds device ops and
named idle gaps. Writes ``<out_dir>/small.xplane.pb``.
"""
import glob
import os
import shutil
import sys
import tempfile
import time


def main(out_dir):
    import jax
    import jax.numpy as jnp
    if jax.devices()[0].platform != "tpu":
        sys.exit("make_trace_data: needs a TPU")
    f = jax.jit(lambda x: jnp.tanh(x @ x).sum())
    x = jnp.ones((2048, 2048), jnp.bfloat16)
    f(x).block_until_ready()
    tmp = tempfile.mkdtemp()
    jax.profiler.start_trace(tmp)
    for _ in range(3):
        with jax.profiler.TraceAnnotation("bench.train_step"):
            f(x).block_until_ready()
        with jax.profiler.TraceAnnotation("bench.sleep"):
            time.sleep(0.02)
    jax.profiler.stop_trace()
    (src,) = glob.glob(os.path.join(tmp, "**", "*.xplane.pb"),
                       recursive=True)
    os.makedirs(out_dir, exist_ok=True)
    shutil.copy(src, os.path.join(out_dir, "small.xplane.pb"))
    shutil.rmtree(tmp)


if __name__ == "__main__":
    main(sys.argv[1])
