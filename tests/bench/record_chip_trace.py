"""Record the small chip trace that ``test_bench_trace.py`` reads.

  python3 tests/bench/record_chip_trace.py <out.xplane.pb>

On a TPU: a jitted matmul and the paged flash-decode kernel, three
rounds each inside the benchmark's host spans (``bench.window`` around
all, ``bench.step_decode`` around each call, ``bench.admit`` as pure
host work), traced with the JAX profiler; the one ``.xplane.pb`` is
copied to the given path.
"""
import glob
import os
import shutil
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro.kernels.flash_decode import flash_decode_paged  # noqa: E402

ROUNDS = 3


def main(out: str) -> int:
    if jax.devices()[0].platform != "tpu":
        print("needs a TPU", file=sys.stderr)
        return 1
    k = jax.random.split(jax.random.key(0), 4)
    x = jax.random.normal(k[0], (2048, 2048), jnp.bfloat16)
    mm = jax.jit(lambda a: a @ a)
    q = jax.random.normal(k[1], (4, 4, 8, 128), jnp.bfloat16)
    kp = jax.random.normal(k[2], (64, 16, 4, 128), jnp.bfloat16)
    vp = jax.random.normal(k[3], (64, 16, 4, 128), jnp.bfloat16)
    pos = jnp.array([15, 100, 200, 255], jnp.int32)
    pt = jnp.arange(64, dtype=jnp.int32).reshape(4, 16)
    fd = jax.jit(flash_decode_paged)
    jax.block_until_ready((mm(x), fd(q, kp, vp, pos, pt)))
    d = tempfile.mkdtemp()
    jax.profiler.start_trace(d)
    with jax.profiler.TraceAnnotation("bench.window"):
        for _ in range(ROUNDS):
            with jax.profiler.TraceAnnotation("bench.admit"):
                time.sleep(0.002)
            with jax.profiler.TraceAnnotation("bench.step_decode"):
                jax.block_until_ready(mm(x))
                jax.block_until_ready(fd(q, kp, vp, pos, pt))
    jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(d, "**", "*.xplane.pb"),
                        recursive=True)
    shutil.copy(path, out)
    print(f"{out}: {os.path.getsize(out)} bytes")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
