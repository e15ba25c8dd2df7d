"""Pallas TPU kernel for METRO's greedy routing (paper Alg. 1).

TPU adaptation of the paper's single-SM CUDA kernel (§V): the algorithm
is confined to one TensorCore's scalar unit with the per-device load
counters in SMEM — the direct analogue of the paper's SM-local shared
memory.  Locks are unnecessary: the loop is sequential (the paper itself
notes lock contention bounds useful concurrency below 64), and the
deterministic order means every device computes the identical routing
from the all-gathered inputs, so no routing table is ever exchanged.

Inputs (see ref.metro_route_ref for exact semantics):
  order        [N]    processing order (heavy-first, computed by the wrapper)
  token_counts [N]    T[1..N]
  expert_slots [N, W] candidate replica slots per expert (-1 pad)
Output:
  expert_slot  [N]    chosen slot per expert (-1 if no tokens)
Scratch (SMEM): act[G], tok[G] per-device load counters.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import interpret_mode

_BIG = jnp.iinfo(jnp.int32).max


def _kernel(order_ref, counts_ref, slots_ref, out_ref, act_ref, tok_ref,
            *, num_devices: int, slots_per_device: int, width: int):
    n = order_ref.shape[0]

    def init_dev(g, _):
        act_ref[g] = 0
        tok_ref[g] = 0
        return _

    jax.lax.fori_loop(0, num_devices, init_dev, None)

    def per_expert(i, carry):
        e = order_ref[i]
        t = counts_ref[e]

        # lexicographic argmin over candidates: (act, tok, dev), first hit
        def scan_cand(j, best):
            b_act, b_tok, b_dev, b_slot = best
            s = slots_ref[e, j]
            valid = s >= 0
            d = jnp.where(valid, s // slots_per_device, 0)
            a = jnp.where(valid, act_ref[d], _BIG)
            tk = jnp.where(valid, tok_ref[d], _BIG)
            better = (a < b_act) | ((a == b_act) & (tk < b_tok)) | \
                     ((a == b_act) & (tk == b_tok) & (d < b_dev))
            better = better & valid
            return (jnp.where(better, a, b_act),
                    jnp.where(better, tk, b_tok),
                    jnp.where(better, d, b_dev),
                    jnp.where(better, s, b_slot))

        best = jax.lax.fori_loop(
            0, width, scan_cand, (_BIG, _BIG, _BIG, jnp.int32(-1)))
        dev, slot = best[2], best[3]

        @pl.when(t > 0)
        def _assign():
            out_ref[e] = slot
            act_ref[dev] = act_ref[dev] + 1
            tok_ref[dev] = tok_ref[dev] + t

        @pl.when(t <= 0)
        def _skip():
            out_ref[e] = -1

        return carry

    jax.lax.fori_loop(0, n, per_expert, None)


@functools.partial(
    jax.jit,
    static_argnames=("num_devices", "slots_per_device", "interpret"))
def metro_route_pallas(token_counts, expert_slots, *, num_devices: int,
                       slots_per_device: int, interpret=None):
    """Greedy routing on the TPU scalar core. Returns expert_slot[N]."""
    n, width = expert_slots.shape
    order = jnp.argsort(-token_counts, stable=True).astype(jnp.int32)
    kernel = functools.partial(
        _kernel, num_devices=num_devices,
        slots_per_device=slots_per_device, width=width)
    return pl.pallas_call(
        kernel,
        grid=(),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_specs=pl.BlockSpec(memory_space=pltpu.SMEM),
        out_shape=jax.ShapeDtypeStruct((n,), jnp.int32),
        scratch_shapes=[
            pltpu.SMEM((num_devices,), jnp.int32),
            pltpu.SMEM((num_devices,), jnp.int32),
        ],
        interpret=interpret_mode(interpret),
    )(order, token_counts.astype(jnp.int32),
      expert_slots.astype(jnp.int32))
