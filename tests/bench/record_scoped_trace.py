"""Record the small scoped chip trace that ``test_bench_scopes.py``
reads.

  python3 tests/bench/record_scoped_trace.py <out.xplane.pb>

On a TPU: a jitted step under the ``serve_step`` scope, a scan over two
"layers" (a matmul under ``attention``; under ``moe``, a softmax under
``router`` and a Pallas kernel under ``experts``, whose weight operand
is the scan's slice of a stacked array) and a reduction under ``head``.
Three rounds, each a host-only ``bench.admit`` span (2 ms asleep) then
a ``bench.step_decode`` span around the step, all inside one
``bench.window`` span, traced with the JAX profiler; the one
``.xplane.pb`` is copied to the given path.
"""
import glob
import os
import shutil
import sys
import tempfile
import time

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

ROUNDS = 3
N, D, LAYERS = 256, 512, 2


def _scale(w_ref, o_ref):
    o_ref[...] = w_ref[...] * 2


def experts(w):
    """A Pallas kernel over one layer's [D, D] weight."""
    return pl.pallas_call(
        _scale, out_shape=jax.ShapeDtypeStruct(w.shape, w.dtype))(w)


def layer(x, w):
    with jax.named_scope("attention"):
        x = x + x @ w["attn"]
    with jax.named_scope("moe"):
        with jax.named_scope("router"):
            g = jax.nn.softmax(x.astype(jnp.float32), axis=-1)
        with jax.named_scope("experts"):
            e = experts(w["expert"])
        x = x + (g.astype(x.dtype) @ e)
    return x


@jax.jit
@jax.named_scope("serve_step")
def step(x, ws):
    with jax.named_scope("layers"):
        x, _ = jax.lax.scan(lambda c, w: (layer(c, w), None), x, ws)
    with jax.named_scope("head"):
        return jnp.sum(x.astype(jnp.float32), axis=-1)


def main(out: str) -> int:
    if jax.devices()[0].platform != "tpu":
        print("needs a TPU", file=sys.stderr)
        return 1
    k = jax.random.split(jax.random.key(0), 3)
    x = jax.random.normal(k[0], (N, D), jnp.bfloat16)
    ws = {"attn": jax.random.normal(k[1], (LAYERS, D, D), jnp.bfloat16)
          / D ** 0.5,
          "expert": jax.random.normal(k[2], (LAYERS, D, D), jnp.bfloat16)
          / D ** 0.5}
    jax.block_until_ready(step(x, ws))
    d = tempfile.mkdtemp()
    jax.profiler.start_trace(d)
    with jax.profiler.TraceAnnotation("bench.window"):
        for _ in range(ROUNDS):
            with jax.profiler.TraceAnnotation("bench.admit"):
                time.sleep(0.002)
            with jax.profiler.TraceAnnotation("bench.step_decode"):
                jax.block_until_ready(step(x, ws))
    jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(d, "**", "*.xplane.pb"),
                        recursive=True)
    shutil.copy(path, out)
    print(f"{out}: {os.path.getsize(out)} bytes")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
