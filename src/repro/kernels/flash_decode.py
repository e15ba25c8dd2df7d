"""Pallas TPU kernel: single-token decode attention over a (possibly
fp8-quantized) KV cache.

This is the OTHER memory-bound hot spot of the paper's regime: decode
latency = weight streaming (moe_ffn kernel) + KV-cache streaming (this
kernel).  The cache is read block-by-block HBM->VMEM in its STORED
dtype and dequantized in registers — so an fp8 cache genuinely halves
the dominant HBM traffic (the claim of EXPERIMENTS §Perf cells 2-3,
which plain XLA only realizes if the convert fuses).

Grid: (batch, kv_head, seq_blocks) — the seq dimension is innermost and
sequential, carrying the online-softmax state (m, l, acc) in VMEM
scratch.  Blocks fully beyond the request's position are masked.

Layout per program: q (1,1,G,hd), k/v (1,1,Sb,hd), out (1,1,G,hd).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import interpret_mode

_NEG = -1e30


def _kernel(pos_ref, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref,
            *, block_s: int, n_blocks: int, scale: float):
    b = pl.program_id(0)
    sb = pl.program_id(2)

    @pl.when(sb == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0, 0].astype(jnp.float32)                 # [G, hd]
    # dequantize in-register: HBM traffic stays at the stored dtype
    k = k_ref[0, 0].astype(jnp.float32)                 # [Sb, hd]
    v = v_ref[0, 0].astype(jnp.float32)

    s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale
    offs = sb * block_s + jax.lax.broadcasted_iota(
        jnp.int32, (1, block_s), 1)
    valid = offs <= pos_ref[b]
    s = jnp.where(valid, s, _NEG)                       # [G, Sb]

    m_prev = m_ref[...]                                 # [G, 1]
    m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new)                              # [G, Sb]
    l_ref[...] = l_ref[...] * alpha + p.sum(axis=-1, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha + jnp.dot(
        p, v, preferred_element_type=jnp.float32)
    m_ref[...] = m_new

    @pl.when(sb == n_blocks - 1)
    def _flush():
        o_ref[0, 0] = (acc_ref[...]
                       / jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("block_s", "interpret"))
def flash_decode_pallas(q, k_cache, v_cache, pos, *, block_s: int = 512,
                        interpret=None):
    """q: [B, KV, G, hd]; k/v_cache: [B, KV, S, hd] (bf16 or fp8);
    pos: [B] int32 (positions > pos are masked). Returns [B, KV, G, hd]
    in q.dtype."""
    b, kv, g, hd = q.shape
    s = k_cache.shape[2]
    block_s = min(block_s, s)
    assert s % block_s == 0, (s, block_s)
    n_blocks = s // block_s
    scale = 1.0 / (hd ** 0.5)
    kernel = functools.partial(_kernel, block_s=block_s,
                               n_blocks=n_blocks, scale=scale)
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b, kv, n_blocks),
            in_specs=[
                pl.BlockSpec((1, 1, g, hd), lambda i, j, sb, pos: (i, j, 0, 0)),
                pl.BlockSpec((1, 1, block_s, hd),
                             lambda i, j, sb, pos: (i, j, sb, 0)),
                pl.BlockSpec((1, 1, block_s, hd),
                             lambda i, j, sb, pos: (i, j, sb, 0)),
            ],
            out_specs=pl.BlockSpec((1, 1, g, hd),
                                   lambda i, j, sb, pos: (i, j, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((g, 1), jnp.float32),
                pltpu.VMEM((g, 1), jnp.float32),
                pltpu.VMEM((g, hd), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b, kv, g, hd), q.dtype),
        interpret=interpret_mode(interpret),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
    )(pos.astype(jnp.int32), q, k_cache, v_cache)


# ----------------------------------------------------------------------
# paged variant: KV lives in a shared page pool; the per-sequence page
# table is scalar-prefetched and drives the K/V BlockSpec index map, so
# each program DMAs exactly the physical page it needs — the kernel
# never sees (or pays HBM traffic for) another sequence's pages, and no
# dense [B, S] view is ever materialized.
# ----------------------------------------------------------------------


def _lane_view(pool):
    """[P, ps, KV, hd] pool -> [P, ps, KV*hd] (a free reshape).  A
    per-head block of the 4-D pool would be (1, ps, 1, hd), whose
    last two dims break the chip's (8, 128) block rule; on this view
    head j is the j-th hd-wide lane block, so the block is (1, ps, hd)
    and the pool layout every other path uses is unchanged."""
    p, ps, kv, hd = pool.shape
    return pool.reshape(p, ps, kv * hd)


def _paged_kernel(pos_ref, pt_ref, q_ref, k_ref, v_ref, o_ref,
                  m_ref, l_ref, acc_ref, *, page_size: int, n_pages: int,
                  scale: float):
    b = pl.program_id(0)
    pb = pl.program_id(2)

    @pl.when(pb == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0, 0].astype(jnp.float32)                 # [G, hd]
    k = k_ref[0].astype(jnp.float32)                    # [ps, hd]
    v = v_ref[0].astype(jnp.float32)

    s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale
    offs = pb * page_size + jax.lax.broadcasted_iota(
        jnp.int32, (1, page_size), 1)
    valid = (offs <= pos_ref[b]) & (pt_ref[b, pb] >= 0)
    s = jnp.where(valid, s, _NEG)                       # [G, ps]

    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new)
    l_ref[...] = l_ref[...] * alpha + p.sum(axis=-1, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha + jnp.dot(
        p, v, preferred_element_type=jnp.float32)
    m_ref[...] = m_new

    @pl.when(pb == n_pages - 1)
    def _flush():
        o_ref[0, 0] = (acc_ref[...]
                       / jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


def _paged_prefill_kernel(start_ref, pt_ref, q_ref, k_ref, v_ref, o_ref,
                          m_ref, l_ref, acc_ref, *, page_size: int,
                          n_pages: int, group: int, scale: float,
                          window: int):
    b = pl.program_id(0)
    pb = pl.program_id(2)

    @pl.when(pb == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0, 0].astype(jnp.float32)                 # [C*G, hd]
    k = k_ref[0].astype(jnp.float32)                    # [ps, hd]
    v = v_ref[0].astype(jnp.float32)
    cg = q.shape[0]

    s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale
    offs = pb * page_size + jax.lax.broadcasted_iota(
        jnp.int32, (1, page_size), 1)                   # [1, ps]
    # chunk-offset query window: row r of the q block is query
    # position start[b] + r // group
    qpos = start_ref[b] + jax.lax.broadcasted_iota(
        jnp.int32, (cg, 1), 0) // group                 # [C*G, 1]
    valid = (offs <= qpos) & (pt_ref[b, pb] >= 0)
    if window > 0:
        valid &= offs > qpos - window
    s = jnp.where(valid, s, _NEG)                       # [C*G, ps]

    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new)
    l_ref[...] = l_ref[...] * alpha + p.sum(axis=-1, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha + jnp.dot(
        p, v, preferred_element_type=jnp.float32)
    m_ref[...] = m_new

    @pl.when(pb == n_pages - 1)
    def _flush():
        o_ref[0, 0] = (acc_ref[...]
                       / jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("window", "interpret"))
def flash_prefill_paged(q, k_pool, v_pool, start, page_table, *,
                        window: int = 0, interpret=None):
    """Chunked-prefill flash attention over the paged KV pool.

    The multi-token sibling of :func:`flash_decode_paged`: one prefill
    *chunk* of C queries per sequence attends to everything already
    written to its pages (earlier chunks + this one — the engine
    scatters the chunk's K/V into the pool before calling), with a
    chunk-offset query window: the query at chunk row c sits at absolute
    position ``start[b] + c`` and masks positions beyond it (and, when
    ``window`` > 0, positions at or below ``start[b] + c - window`` —
    SWA layers store the full sequence in pages and mask at read time).

    q: [B, KV, C, G, hd]; k/v_pool: [num_pages, page_size, KV, hd] (bf16
    or fp8); start: [B] int32; page_table: [B, Pmax] int32 (-1 = hole).
    Returns [B, KV, C, G, hd] in q.dtype.

    Grid (batch, kv_head, logical_page): the page dimension is innermost
    and sequential, carrying the online-softmax state for all C*G query
    rows of the chunk in VMEM scratch; the K/V index map reads the
    prefetched page table, so address translation happens at DMA-issue
    time on the scalar core and activation memory is O(C), not
    O(max_len).
    """
    b, kv, c, g, hd = q.shape
    num_pages, ps, kv_p, _ = k_pool.shape
    assert kv_p == kv, (kv_p, kv)
    pmax = page_table.shape[1]
    scale = 1.0 / (hd ** 0.5)
    kernel = functools.partial(
        _paged_prefill_kernel, page_size=ps, n_pages=pmax, group=g,
        scale=scale, window=int(window or 0))
    qf = q.reshape(b, kv, c * g, hd)

    def kv_map(i, j, pb, start, pt):
        return (jnp.maximum(pt[i, pb], 0), 0, j)

    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b, kv, pmax),
            in_specs=[
                pl.BlockSpec((1, 1, c * g, hd),
                             lambda i, j, pb, start, pt: (i, j, 0, 0)),
                pl.BlockSpec((1, ps, hd), kv_map),
                pl.BlockSpec((1, ps, hd), kv_map),
            ],
            out_specs=pl.BlockSpec((1, 1, c * g, hd),
                                   lambda i, j, pb, start, pt: (i, j, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((c * g, 1), jnp.float32),
                pltpu.VMEM((c * g, 1), jnp.float32),
                pltpu.VMEM((c * g, hd), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b, kv, c * g, hd), q.dtype),
        interpret=interpret_mode(interpret),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
    )(start.astype(jnp.int32), page_table.astype(jnp.int32),
      qf, _lane_view(k_pool), _lane_view(v_pool))
    return out.reshape(b, kv, c, g, hd)


@functools.partial(jax.jit, static_argnames=("interpret",))
def flash_decode_paged(q, k_pool, v_pool, pos, page_table, *,
                       interpret=None):
    """Paged flash decode.  q: [B, KV, G, hd]; k/v_pool:
    [num_pages, page_size, KV, hd] (bf16 or fp8); pos: [B] int32;
    page_table: [B, Pmax] int32 physical page ids (-1 = hole; holes and
    positions > pos are masked).  Returns [B, KV, G, hd] in q.dtype.

    Grid (batch, kv_head, logical_page): the page dimension is innermost
    and sequential, carrying online-softmax state; the K/V index map
    reads the prefetched page table, i.e. the address translation
    happens at DMA-issue time on the scalar core.
    """
    b, kv, g, hd = q.shape
    num_pages, ps, kv_p, _ = k_pool.shape
    assert kv_p == kv, (kv_p, kv)
    pmax = page_table.shape[1]
    scale = 1.0 / (hd ** 0.5)
    kernel = functools.partial(_paged_kernel, page_size=ps, n_pages=pmax,
                               scale=scale)

    def kv_map(i, j, pb, pos, pt):
        return (jnp.maximum(pt[i, pb], 0), 0, j)

    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b, kv, pmax),
            in_specs=[
                pl.BlockSpec((1, 1, g, hd),
                             lambda i, j, pb, pos, pt: (i, j, 0, 0)),
                pl.BlockSpec((1, ps, hd), kv_map),
                pl.BlockSpec((1, ps, hd), kv_map),
            ],
            out_specs=pl.BlockSpec((1, 1, g, hd),
                                   lambda i, j, pb, pos, pt: (i, j, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((g, 1), jnp.float32),
                pltpu.VMEM((g, 1), jnp.float32),
                pltpu.VMEM((g, hd), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b, kv, g, hd), q.dtype),
        interpret=interpret_mode(interpret),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
    )(pos.astype(jnp.int32), page_table.astype(jnp.int32),
      q, _lane_view(k_pool), _lane_view(v_pool))
