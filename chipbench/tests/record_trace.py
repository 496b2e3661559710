#!/usr/bin/env python3
"""Record the small trace that ``test_trace_reduce.py`` checks the reduction
on.  Run ON THE CHIP, in a process of its own (it holds the chip):

    python3 chipbench/tests/record_trace.py <out.xplane.pb>

Two jitted programs with stable names (``jit_bench_matmul``,
``jit_bench_scan``: a scan, so that a ``while`` nests its body's operations)
run a few times each with a host sleep between them, so the trace has device
programs, nested operations and idle gaps of known order.
"""

import glob
import shutil
import sys
import tempfile
import time

import jax
import jax.numpy as jnp


def bench_matmul(x):
    return (x @ x).sum()


def bench_scan(x):
    def body(c, _):
        return jnp.tanh(c @ c) * 0.5, ()
    return jax.lax.scan(body, x, None, length=4)[0].sum()


def main(out: str) -> None:
    x = jnp.ones((1024, 1024), jnp.bfloat16)
    f, g = jax.jit(bench_matmul), jax.jit(bench_scan)
    f(x).block_until_ready()
    g(x).block_until_ready()
    d = tempfile.mkdtemp()
    jax.profiler.start_trace(d)
    for _ in range(3):
        f(x).block_until_ready()
        time.sleep(0.005)
        g(x).block_until_ready()
        time.sleep(0.01)
    jax.profiler.stop_trace()
    path = glob.glob(d + "/**/*.xplane.pb", recursive=True)[0]
    shutil.copy(path, out)
    print(f"{jax.devices()[0].device_kind}: wrote {out}")


if __name__ == "__main__":
    main(sys.argv[1])
