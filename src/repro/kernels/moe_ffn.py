"""Pallas TPU kernels: grouped expert-FFN matmuls with activated-expert-
only weight streaming, plus the fused one-pass up→act→down megakernel.

This is the memory-traffic mechanism METRO optimizes (paper §III-B): in
the memory-bound regime the MoE layer's runtime is dominated by expert
weight loads HBM->VMEM.  Every kernel here indexes its weight BlockSpec
by the scalar-prefetched ``tile_group`` map, so a weight tile is DMA'd
iff some *live* token tile references that expert — non-activated
experts' weights are never touched, and dead tiles (``tile_group[i] ==
-1``: buffer tiles holding only padding rows) repeat the previous live
tile's block indices so Pallas skips their DMA entirely (a repeated
block index is never refetched) and ``pl.when`` skips their FLOPs.

Two kernels:

``grouped_ffn_pallas``  — one grouped matmul (one of the two passes of
    the classic expert FFN).  Grid ``(m_tiles, f_tiles, k_tiles)``, K
    innermost for accumulation.  Semantics == ref.grouped_matmul_ref on
    live tiles; dead tiles emit zeros.

``fused_expert_ffn_pallas`` — the whole expert FFN in ONE kernel:
    per resident token tile it streams the group's up-projection
    k-tiles into an fp32 VMEM accumulator, applies the silu/gelu gating
    *in VMEM*, then streams the down-projection k-tiles and accumulates
    the output.  The ``[tile_m, n_up*fe]`` hidden never touches HBM,
    and each activated expert's weights are loaded exactly once per
    resident token tile.  Grid ``(m_tiles, k_up_tiles + k_down_tiles)``
    — the second dimension enumerates the up phases then the down
    phases; scratch persists across phases of the same token tile.
    Semantics == ref.fused_expert_ffn_ref.

VMEM sizing rule (see kernels/README.md): the fused kernel keeps
``tile_m * n_up*fe`` fp32 hidden + ``tile_m * fe`` gated + ``tile_m *
d`` fp32 output accumulators resident, plus one ``tile_k_up x n_up*fe``
up-weight tile and one ``tile_k_dn x d`` down-weight tile — choose
``tile_m`` / ``tile_k_*`` so the sum stays under ~half of VMEM
(double-buffered DMA needs the rest).

The MoE layer guarantees tile alignment and the trailing-dead layout
(all fully-dead tiles follow the last live tile) via build_pair_buffer.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import interpret_mode


def pick_tile(n: int, want: int) -> int:
    """Largest divisor of ``n`` that is <= ``want``, preferring lane-
    aligned (multiple of 128) ones — the chip's compiler refuses a
    block that is neither aligned nor the whole dimension.  qwen3's
    ``fe=768`` asked for 512 gets 384; small test widths keep their
    unaligned divisors (they only run in the interpreter)."""
    divs = [t for t in range(1, min(want, n) + 1) if n % t == 0]
    aligned = [t for t in divs if t % 128 == 0]
    return (aligned or divs)[-1]


# ----------------------------------------------------------------------
# two-pass grouped matmul (one pass per call)
# ----------------------------------------------------------------------


def _kernel(tile_group, n_live, x_ref, w_ref, out_ref, acc_ref, *,
            k_tiles: int):
    i = pl.program_id(0)
    ki = pl.program_id(2)
    live = tile_group[i] >= 0

    @pl.when(live & (ki == 0))
    def _zero():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(live)
    def _mac():
        acc_ref[...] += jnp.dot(x_ref[...], w_ref[0],
                                preferred_element_type=jnp.float32)

    @pl.when(live & (ki == k_tiles - 1))
    def _flush():
        out_ref[...] = acc_ref[...].astype(out_ref.dtype)

    @pl.when(~live & (ki == k_tiles - 1))
    def _flush_dead():
        out_ref[...] = jnp.zeros_like(out_ref)


def _dma_row(i, nl):
    """Last live token-tile row for grid step ``i``: dead tiles (which
    are trailing — build_pair_buffer's layout) repeat the previous live
    tile's block index, so Pallas never re-DMAs for them."""
    return jnp.maximum(jnp.minimum(i, nl[0] - 1), 0)


def _freeze(i, nl, live_idx, frozen_idx):
    """Block index for a possibly-dead grid row: live rows walk their
    own index, dead rows PARK on the last live tile's final index (the
    index must not change across a dead tile's grid steps, or Pallas
    would re-DMA — freezing the phase/k component is as load-bearing
    as freezing the group)."""
    return jnp.where(i < nl[0], live_idx, frozen_idx)


@functools.partial(
    jax.jit,
    static_argnames=("tile_m", "tile_k", "tile_f", "interpret"))
def grouped_ffn_pallas(x, w, tile_group, *, tile_m: int = 0,
                       tile_k: int = 512, tile_f: int = 512,
                       interpret=None):
    """x: [C, d] (C = n_tiles * tile_m, sorted/tile-aligned); w: [S, d, f];
    tile_group: [n_tiles] int32, -1 = dead tile (skipped: no weight DMA,
    no FLOPs, zero output). Returns [C, f] in x.dtype."""
    c, d = x.shape
    s, _, f = w.shape
    n_tiles = tile_group.shape[0]
    tile_m = tile_m or c // n_tiles
    assert c == n_tiles * tile_m, (c, n_tiles, tile_m)
    tile_k = pick_tile(d, tile_k)
    tile_f = pick_tile(f, tile_f)
    k_tiles = d // tile_k

    tile_group = tile_group.astype(jnp.int32)
    n_live = jnp.sum(tile_group >= 0).astype(jnp.int32)[None]

    grid = (n_tiles, f // tile_f, k_tiles)
    kernel = functools.partial(_kernel, k_tiles=k_tiles)
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=grid,
            in_specs=[
                pl.BlockSpec(
                    (tile_m, tile_k),
                    lambda i, j, k, tg, nl: (
                        _dma_row(i, nl),
                        _freeze(i, nl, k, k_tiles - 1))),
                # weight tile selected by the token tile's expert — the
                # activated-expert-only streaming (dead tiles park on
                # the last live tile's FINAL (k, j) block: repeated
                # index, no DMA)
                pl.BlockSpec(
                    (1, tile_k, tile_f),
                    lambda i, j, k, tg, nl: (
                        jnp.maximum(tg[_dma_row(i, nl)], 0),
                        _freeze(i, nl, k, k_tiles - 1),
                        _freeze(i, nl, j, f // tile_f - 1))),
            ],
            out_specs=pl.BlockSpec((tile_m, tile_f),
                                   lambda i, j, k, tg, nl: (i, j)),
            scratch_shapes=[pltpu.VMEM((tile_m, tile_f), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((c, f), x.dtype),
        interpret=interpret_mode(interpret),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary", "arbitrary")),
    )(tile_group, n_live, x, w)


# ----------------------------------------------------------------------
# fused one-pass expert FFN: up → act → down, hidden stays in VMEM
# ----------------------------------------------------------------------


def _activate(h, fe: int, gated: bool):
    """silu(gate) * up (or gelu) of the dtype-cast matmul output ``h``,
    evaluated in fp32 and rounded once.  Mosaic cannot lower these
    activations on bf16 vectors (their fp32 constants fail its
    element-type check), and one rounding is also what XLA's fused
    two-pass epilogue produces."""
    hf = h.astype(jnp.float32)
    if gated:
        act = jax.nn.silu(hf[:, :fe]) * hf[:, fe:]
    else:
        act = jax.nn.gelu(hf)
    return act.astype(h.dtype)


def _fused_kernel(tile_group, n_live, x_ref, wu_ref, wd_ref, out_ref,
                  h_ref, hg_ref, acc_ref, *, k_up: int, k_dn: int,
                  tile_k_dn: int, fe: int, gated: bool):
    i = pl.program_id(0)
    j = pl.program_id(1)
    live = tile_group[i] >= 0

    @pl.when(j == 0)
    def _zero():
        h_ref[...] = jnp.zeros_like(h_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # ---- up phases: accumulate the hidden in fp32 VMEM --------------
    @pl.when(live & (j < k_up))
    def _up():
        h_ref[...] += jnp.dot(x_ref[...], wu_ref[0],
                              preferred_element_type=jnp.float32)

    # ---- gate in VMEM after the last up k-tile ----------------------
    @pl.when(live & (j == k_up - 1))
    def _gate():
        # cast the fp32 accumulator to the compute dtype BEFORE the
        # activation — the two-pass datapath gates on the dtype-cast
        # matmul output (ragged_dot accumulates f32 internally, then
        # casts), and matching it keeps fused serve token-identical
        hg_ref[...] = _activate(h_ref[...].astype(hg_ref.dtype), fe,
                                gated)

    # ---- down phases: stream w_down, accumulate the output ----------
    @pl.when(live & (j >= k_up))
    def _down():
        kf = j - k_up
        off = pl.multiple_of(kf * tile_k_dn, tile_k_dn)
        hblk = hg_ref[:, pl.ds(off, tile_k_dn)]
        acc_ref[...] += jnp.dot(hblk, wd_ref[0],
                                preferred_element_type=jnp.float32)

    @pl.when(live & (j == k_up + k_dn - 1))
    def _flush():
        out_ref[...] = acc_ref[...].astype(out_ref.dtype)

    @pl.when(~live & (j == k_up + k_dn - 1))
    def _flush_dead():
        out_ref[...] = jnp.zeros_like(out_ref)


@functools.partial(
    jax.jit,
    static_argnames=("gated", "tile_m", "tile_k_up", "tile_k_dn",
                     "interpret"))
def fused_expert_ffn_pallas(x, w_up, w_down, tile_group, *, gated: bool,
                            tile_m: int = 0, tile_k_up: int = 512,
                            tile_k_dn: int = 512, interpret=None):
    """One-pass expert FFN: out = act(x @ w_up[g]) @ w_down[g] per tile.

    x: [C, d] sorted/tile-aligned buffer (C = n_tiles * tile_m);
    w_up: [S, d, n_up*fe] (n_up = 2 when ``gated``: [gate | up] halves);
    w_down: [S, fe, d]; tile_group: [n_tiles] int32, -1 = dead tile.
    Returns [C, d] in x.dtype; dead tiles yield exact zeros.

    The hidden activation never leaves VMEM and each live tile streams
    its group's up+down weights exactly once (dead tiles: no DMA, no
    FLOPs — their block indices repeat the last live tile's).
    """
    c, d = x.shape
    s, _, f_up = w_up.shape
    _, fe, _ = w_down.shape
    n_up = 2 if gated else 1
    assert f_up == n_up * fe, (f_up, n_up, fe)
    n_tiles = tile_group.shape[0]
    tile_m = tile_m or c // n_tiles
    assert c == n_tiles * tile_m, (c, n_tiles, tile_m)
    tile_k_up = pick_tile(d, tile_k_up)
    tile_k_dn = pick_tile(fe, tile_k_dn)
    k_up = d // tile_k_up
    k_dn = fe // tile_k_dn

    tile_group = tile_group.astype(jnp.int32)
    n_live = jnp.sum(tile_group >= 0).astype(jnp.int32)[None]

    grid = (n_tiles, k_up + k_dn)
    kernel = functools.partial(
        _fused_kernel, k_up=k_up, k_dn=k_dn, tile_k_dn=tile_k_dn, fe=fe,
        gated=gated)

    def _g(i, nl, tg):
        return jnp.maximum(tg[_dma_row(i, nl)], 0)

    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=grid,
            in_specs=[
                # x k-tile: advances over the up phases, parks on the
                # last up index during the down phases (no refetch);
                # dead tiles park on the last live tile's final index
                pl.BlockSpec(
                    (tile_m, tile_k_up),
                    lambda i, j, tg, nl: (
                        _dma_row(i, nl),
                        _freeze(i, nl, jnp.minimum(j, k_up - 1),
                                k_up - 1))),
                # up-weight tile: advances over up phases, parks after
                pl.BlockSpec(
                    (1, tile_k_up, f_up),
                    lambda i, j, tg, nl: (
                        _g(i, nl, tg),
                        _freeze(i, nl, jnp.minimum(j, k_up - 1),
                                k_up - 1), 0)),
                # down-weight tile: parks on 0 during up phases (its
                # single prefetch is the tile the first down phase
                # needs), advances over the down phases; dead tiles
                # park on the final down index
                pl.BlockSpec(
                    (1, tile_k_dn, d),
                    lambda i, j, tg, nl: (
                        _g(i, nl, tg),
                        _freeze(i, nl, jnp.maximum(j - k_up, 0),
                                k_dn - 1), 0)),
            ],
            out_specs=pl.BlockSpec((tile_m, d),
                                   lambda i, j, tg, nl: (i, 0)),
            scratch_shapes=[
                pltpu.VMEM((tile_m, f_up), jnp.float32),   # hidden acc
                pltpu.VMEM((tile_m, fe), x.dtype),         # gated hidden
                pltpu.VMEM((tile_m, d), jnp.float32),      # output acc
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((c, d), x.dtype),
        interpret=interpret_mode(interpret),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
    )(tile_group, n_live, x, w_up, w_down)


# ----------------------------------------------------------------------
# paged fused expert FFN: weights live in a frame pool, manual
# double-buffered DMA overlaps tile i's compute with tile i+1's fetch
# ----------------------------------------------------------------------


def _paged_kernel(tile_group, n_live, frame_map, x_ref, wu_hbm, wd_hbm,
                  out_ref, wu_buf, wd_buf, sem, *, fe: int, gated: bool):
    i = pl.program_id(0)
    n = pl.num_programs(0)
    live = tile_group[i] >= 0

    def _copies(idx, slot):
        f = frame_map[jnp.maximum(tile_group[idx], 0)]
        return (pltpu.make_async_copy(wu_hbm.at[f], wu_buf.at[slot],
                                      sem.at[slot, 0]),
                pltpu.make_async_copy(wd_hbm.at[f], wd_buf.at[slot],
                                      sem.at[slot, 1]))

    # warm start: the first tile's weights have no earlier grid step to
    # hide behind
    @pl.when((i == 0) & live)
    def _warm():
        for cp in _copies(0, 0):
            cp.start()

    # prefetch the NEXT live tile's frame into the other buffer slot
    # while this tile computes — the double-buffered overlap.  Dead
    # tiles issue nothing (manual DMA needs no index-parking trick).
    nxt = jnp.minimum(i + 1, n - 1)

    @pl.when((i + 1 < n) & (tile_group[nxt] >= 0))
    def _prefetch():
        for cp in _copies(nxt, (i + 1) % 2):
            cp.start()

    @pl.when(live)
    def _compute():
        slot = i % 2
        for cp in _copies(i, slot):
            cp.wait()
        h = jnp.dot(x_ref[...], wu_buf[slot],
                    preferred_element_type=jnp.float32)
        # cast before the activation: parity with the two-pass datapath
        # (and fused_expert_ffn_pallas), which gates on the dtype-cast
        # matmul output
        act = _activate(h.astype(out_ref.dtype), fe, gated)
        y = jnp.dot(act, wd_buf[slot],
                    preferred_element_type=jnp.float32)
        out_ref[...] = y.astype(out_ref.dtype)

    @pl.when(~live)
    def _dead():
        out_ref[...] = jnp.zeros_like(out_ref)


@functools.partial(
    jax.jit,
    static_argnames=("gated", "tile_m", "interpret"))
def fused_expert_ffn_paged_pallas(x, wu_pool, wd_pool, frame_map,
                                  tile_group, *, gated: bool,
                                  tile_m: int = 0,
                                  interpret=None):
    """Fused expert FFN reading weights from a paged frame pool.

    ``wu_pool``: [F, d, n_up*fe] and ``wd_pool``: [F, fe, d] hold F
    weight *frames* (F >= number of distinct live groups); they stay in
    ``ANY`` memory space (HBM) and are never blocked by the pipeline.
    ``frame_map``: [S] int32 maps expert slot -> frame index, so the
    caller (serving/expert_pool.py) controls physical placement.
    ``tile_group``: [n_tiles] int32 slot per token tile, -1 = dead.

    Per live tile the kernel manually DMAs frame ``frame_map[group]``'s
    up+down weights into a 2-slot VMEM ring — tile i's copy is started
    during tile i-1's compute (double-buffered overlap), with a warm
    start for tile 0 — then runs up → act → down entirely in VMEM.

    DMA contract: exactly one up + one down copy per LIVE tile; dead
    tiles issue nothing (no index-parking — the copies are explicit
    ``pl.when``-guarded ``make_async_copy`` calls, so even an all-dead
    grid moves zero weight bytes, unlike the automatic pipeline which
    must prefetch a parked block).  Adjacent same-group tiles refetch
    (no revisit-skip in the manual path) — acceptable at the pool's
    page granularity; see kernels/README.md.

    Semantics == fused_expert_ffn_pallas(x, wu_pool[frame_map],
    wd_pool[frame_map], tile_group) == ref.fused_expert_ffn_ref.
    """
    c, d = x.shape
    _, _, f_up = wu_pool.shape
    _, fe, _ = wd_pool.shape
    n_up = 2 if gated else 1
    assert f_up == n_up * fe, (f_up, n_up, fe)
    n_tiles = tile_group.shape[0]
    tile_m = tile_m or c // n_tiles
    assert c == n_tiles * tile_m, (c, n_tiles, tile_m)

    tile_group = tile_group.astype(jnp.int32)
    n_live = jnp.sum(tile_group >= 0).astype(jnp.int32)[None]
    frame_map = frame_map.astype(jnp.int32)

    kernel = functools.partial(_paged_kernel, fe=fe, gated=gated)
    # the two weight rings dominate VMEM (18.9 MB at qwen3-30b-a3b's
    # d=2048, fe=768 in bf16) and exceed the default scoped limit, so
    # the limit is raised to what the rings plus the pipelined x/out
    # blocks need, with headroom for Mosaic's own scratch
    isz = jnp.dtype(x.dtype).itemsize
    vmem_bytes = (2 * (d * f_up + fe * d) * isz
                  + 2 * 2 * tile_m * d * isz + (16 << 20))
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(n_tiles,),
            in_specs=[
                pl.BlockSpec((tile_m, d), lambda i, tg, nl, fm: (i, 0)),
                pl.BlockSpec(memory_space=pl.ANY),   # up-weight pool
                pl.BlockSpec(memory_space=pl.ANY),   # down-weight pool
            ],
            out_specs=pl.BlockSpec((tile_m, d),
                                   lambda i, tg, nl, fm: (i, 0)),
            scratch_shapes=[
                pltpu.VMEM((2, d, f_up), x.dtype),   # up-weight ring
                pltpu.VMEM((2, fe, d), x.dtype),     # down-weight ring
                pltpu.SemaphoreType.DMA((2, 2)),     # per slot: up, down
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((c, d), x.dtype),
        interpret=interpret_mode(interpret),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=vmem_bytes),
    )(tile_group, n_live, frame_map, x, wu_pool, wd_pool)
