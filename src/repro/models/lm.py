"""Decoder-only LM assembly for every assigned architecture family.

Layer stacks are a `lax.scan` over *pattern blocks*: each block holds one
period of the config's layer pattern (e.g. gemma3's [5x local, 1x global],
jamba's [mamba x3, attn, mamba x3 + MoE interleave]), with parameters
stacked on a leading n_blocks axis — keeping the HLO O(period) regardless
of depth (95-layer deepseek compiles as fast as 16-layer olmo).

Modes:
  train   — full causal pass, logits for loss; no cache.
  prefill — causal pass that also *fills* the KV/SSM caches.
  decode  — single token against caches (the paper's memory-bound phase);
            MoE layers run in features mode with METRO routing.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.models import layers as L
from repro.models import mamba as M
from repro.models import moe as MOE
from repro.models import scopes as SC
from repro.core.types import Placement
from repro.sharding.policy import Dist


# ----------------------------------------------------------------------
# init
# ----------------------------------------------------------------------


def _init_layer(cfg: ModelConfig, key, dist: Dist, mixer: str, ffn: str,
                replica_expert, dtype):
    ks = jax.random.split(key, 4)
    p = {"norm1": L.init_norm(cfg, ks[0])}
    if mixer.startswith("attn"):
        p["attn"] = L.init_attention(cfg, ks[1], tp=dist.ep_size)
    elif mixer == "mamba":
        p["mamba"] = M.init_mamba(cfg, ks[1])
    if ffn == "dense":
        p["norm2"] = L.init_norm(cfg, ks[2])
        p["mlp"] = L.init_mlp(cfg, ks[3])
    elif ffn == "moe":
        p["norm2"] = L.init_norm(cfg, ks[2])
        p["moe"] = MOE.init_moe(cfg, ks[3], dist, replica_expert, dtype)
    return cast_params(p, dtype)


def init_lm(cfg: ModelConfig, key, dist: Dist,
            replica_expert: Optional[np.ndarray] = None,
            dtype=jnp.float32):
    """Full parameter pytree in ``dtype``: fp32 is the training master;
    serving asks for its compute dtype, and gets the values
    :func:`cast_params` would make of that master.  MoE layers need the
    physical replica layout (replica_expert from the placement).

    Blocks are built one at a time, each layer cast to ``dtype`` as it
    is made, and written into a preallocated stack, so the device never
    holds more than the stack, one block and one expert tensor's fp32
    temporaries — a whole-model fp32 tree of a published-width MoE does
    not fit one chip.  Each large tensor and each block is waited for,
    so that asynchronous dispatch does not queue (and allocate) the
    draws of later ones meanwhile."""
    if cfg.family == "encdec":
        from repro.models import encdec
        return cast_params(encdec.init_encdec(cfg, key, dist), dtype)
    kinds = cfg.layer_kinds()
    n_blocks = cfg.num_layers // len(kinds)
    k_emb, k_blocks, k_norm, k_head = jax.random.split(key, 4)
    d, v = cfg.d_model, cfg.vocab_size
    params = {}
    # even embeddings-mode archs (VLM stub) keep a token table: prefill
    # consumes precomputed patch embeddings, decode embeds generated text
    params["embed"] = jax.block_until_ready((jax.random.normal(
        k_emb, (v, d), jnp.float32) * 0.02).astype(dtype))
    if not cfg.tie_embeddings:
        params["unembed"] = jax.block_until_ready((jax.random.normal(
            k_head, (d, v), jnp.float32) / np.sqrt(d)).astype(dtype))
    params["final_norm"] = cast_params(L.init_norm(cfg, k_norm), dtype)

    def one_block(bk):
        lkeys = jax.random.split(bk, len(kinds))
        return {f"l{i}": _init_layer(cfg, lkeys[i], dist, mixer, ffn,
                                     replica_expert, dtype)
                for i, (mixer, ffn) in enumerate(kinds)}

    bkeys = jax.random.split(k_blocks, n_blocks)
    blocks = jax.tree.map(
        lambda a: jnp.zeros((n_blocks,) + a.shape, a.dtype),
        jax.eval_shape(one_block, bkeys[0]))
    for i in range(n_blocks):
        blocks = jax.block_until_ready(
            _set_block(blocks, one_block(bkeys[i]), i))
    params["blocks"] = blocks
    return params


@functools.partial(jax.jit, donate_argnums=0)
def _set_block(stack, block, i):
    """Write one block into the (donated) stacked tree in place."""
    return jax.tree.map(
        lambda s, b: jax.lax.dynamic_update_index_in_dim(s, b, i, 0),
        stack, block)


def build_lm_routing(cfg: ModelConfig, placement: Placement,
                     table_width: Optional[int] = None):
    """Per-layer routing tables, stacked over blocks (same placement for
    every MoE layer by default; the serving engine may rebalance
    per-layer by stacking different placements)."""
    if not cfg.is_moe:
        return {}
    kinds = cfg.layer_kinds()
    n_blocks = cfg.num_layers // len(kinds)
    t = MOE.routing_tables(placement, table_width)
    out = {}
    for i, (_, ffn) in enumerate(kinds):
        if ffn == "moe":
            out[f"l{i}"] = jax.tree.map(
                lambda a: jnp.broadcast_to(a, (n_blocks,) + a.shape), t)
    return out


# ----------------------------------------------------------------------
# caches
# ----------------------------------------------------------------------


def init_cache(cfg: ModelConfig, dist: Dist, batch: int, max_len: int,
               dtype=jnp.bfloat16):
    """Decode caches for all layers, stacked over blocks."""
    if cfg.family == "encdec":
        from repro.models import encdec
        return encdec.init_encdec_cache(cfg, dist, batch, max_len, dtype)
    kinds = cfg.layer_kinds()
    n_blocks = cfg.num_layers // len(kinds)
    cache = {}
    for i, (mixer, _) in enumerate(kinds):
        if mixer == "attn_full":
            c = L.init_kv_cache(cfg, batch, max_len, None, dtype,
                                tp=dist.ep_size)
        elif mixer == "attn_swa":
            c = L.init_kv_cache(cfg, batch, max_len, cfg.sliding_window,
                                dtype, tp=dist.ep_size)
        elif mixer == "mamba":
            c = M.init_mamba_cache(cfg, batch, dtype)
        else:
            continue
        cache[f"l{i}"] = jax.tree.map(
            lambda a: jnp.broadcast_to(a, (n_blocks,) + a.shape), c)
    return cache


def init_paged_cache(cfg: ModelConfig, dist: Dist, num_pages: int,
                     page_size: int, max_batch: int, dtype=jnp.bfloat16):
    """Serving cache with paged attention layers: per attention layer a
    shared page pool [n_blocks, num_pages, page_size, kv, hd]; mamba
    layers keep per-slot state (their state is O(1) per sequence, there
    is nothing to page).  ``dtype`` sets the attention pool element
    type (the engine's ``kv_dtype``: bf16/fp32/fp8 — paged reads are
    dequant-aware); mamba recurrence state is never quantized below
    bf16 (it feeds a sequential scan, not a dequantizing gather)."""
    kinds = cfg.layer_kinds()
    n_blocks = cfg.num_layers // len(kinds)
    mamba_dtype = jnp.bfloat16 if jnp.dtype(dtype).itemsize == 1 else dtype
    cache = {}
    for i, (mixer, _) in enumerate(kinds):
        if mixer.startswith("attn"):
            c = L.init_paged_kv_cache(cfg, num_pages, page_size, dtype,
                                      tp=dist.ep_size)
        elif mixer == "mamba":
            c = M.init_mamba_cache(cfg, max_batch, mamba_dtype)
        else:
            continue
        cache[f"l{i}"] = jax.tree.map(
            lambda a: jnp.broadcast_to(a, (n_blocks,) + a.shape), c)
    return cache


def init_wave_cache(cfg: ModelConfig, dist: Dist, batch: int, length: int,
                    dtype=jnp.bfloat16):
    """Scratch cache for one batched prefill wave: attention buffers are
    FULL length (never rolling) so every position lands at its own index
    and can be scattered into the serving cache afterwards.

    Legacy path: only ``prefill_mode="wave"`` (and the dense KV layout)
    still allocates this persistent O(batch * length * n_layers)
    scratch — the engine's default chunked prefill
    (``mode="chunk_prefill"``) writes each O(prefill_chunk) chunk
    straight into the paged serving cache and allocates no full-length
    wave scratch at all (see attention_prefill_paged's memory note for
    the reference path's per-layer transient)."""
    kinds = cfg.layer_kinds()
    n_blocks = cfg.num_layers // len(kinds)
    cache = {}
    for i, (mixer, _) in enumerate(kinds):
        if mixer.startswith("attn"):
            c = L.init_kv_cache(cfg, batch, length, None, dtype,
                                tp=dist.ep_size)
        elif mixer == "mamba":
            c = M.init_mamba_cache(cfg, batch, dtype)
        else:
            continue
        cache[f"l{i}"] = jax.tree.map(
            lambda a: jnp.broadcast_to(a, (n_blocks,) + a.shape), c)
    return cache


def merge_wave_cache(cfg: ModelConfig, cache, wave_cache, slot_idx,
                     lengths, *, page_table=None, page_size: int = 0):
    """Scatter a prefill wave's filled scratch cache into the serving
    cache (jit-traceable; called inside the wave-prefill step).

    cache: engine cache — paged pools when ``page_table`` is given, else
    dense per-slot buffers.  wave_cache: from :func:`init_wave_cache`
    after ``apply_lm(mode="prefill")``.  slot_idx: [B] engine slot per
    wave row (out-of-range = padding row, dropped).  lengths: [B] true
    prompt lengths (positions beyond a row's length are not scattered
    into pages).  page_table: [B, Pmax] physical page per logical page.
    """
    wb = len(slot_idx)
    out = {}
    for li, full in cache.items():
        wave = wave_cache[li]
        if "conv" in full:                       # mamba: per-slot rows
            out[li] = jax.tree.map(
                lambda f, p: f.at[:, slot_idx].set(
                    p.astype(f.dtype), mode="drop"), full, wave)
            continue
        # attention: wave k/v [nb, B, kv, L, hd]
        l_pad = wave["k"].shape[3]
        if page_table is not None:
            ps = page_size
            tt = jnp.broadcast_to(jnp.arange(l_pad), (wb, l_pad))
            phys = jnp.take_along_axis(page_table, tt // ps, axis=1)
            valid = (tt < lengths[:, None]) & (phys >= 0)
            num_pages = full["k"].shape[1]
            flat_idx = jnp.where(valid, phys * ps + tt % ps,
                                 num_pages * ps).reshape(-1)

            def scatter(pool, w):
                nb, p, ps_, kvh, hd = pool.shape
                vals = w.transpose(0, 1, 3, 2, 4).reshape(
                    nb, wb * l_pad, kvh, hd)
                flat = pool.reshape(nb, p * ps_, kvh, hd)
                flat = flat.at[:, flat_idx].set(
                    vals.astype(flat.dtype), mode="drop")
                return flat.reshape(pool.shape)

            out[li] = {k: scatter(full[k], wave[k]) for k in ("k", "v")}
        else:
            s_buf = full["k"].shape[3]
            if l_pad <= s_buf:
                out[li] = {
                    k: full[k].at[:, slot_idx, :, :l_pad].set(
                        wave[k].astype(full[k].dtype), mode="drop")
                    for k in ("k", "v")}
            else:
                # rolling (SWA) buffer: keep each row's last s_buf REAL
                # positions at slots p % s_buf (attention_decode's
                # mapping).  Per-row gather — taking the padded tail
                # would both store garbage keys and roll real in-window
                # context out of the buffer.
                sel = jnp.asarray(slot_idx)[:, None]
                src_pos = lengths[:, None] - s_buf + \
                    jnp.arange(s_buf)[None, :]          # [B, s_buf]
                dst = jnp.where(src_pos >= 0, src_pos % s_buf, s_buf)

                def roll(f, w):
                    g = jnp.take_along_axis(
                        w, jnp.clip(src_pos, 0, l_pad - 1)[
                            None, :, None, :, None], axis=3)
                    vals = g.transpose(1, 3, 0, 2, 4)   # [B,s_buf,nb,kv,hd]
                    return f.at[:, sel, :, dst].set(
                        vals.astype(f.dtype), mode="drop")

                out[li] = {k: roll(full[k], wave[k]) for k in ("k", "v")}
    return out


def cache_pspec(cfg: ModelConfig, dist: Dist, long_context: bool = False):
    """PartitionSpecs for the cache pytree (for dry-run in_shardings).

    KV: heads sharded over the TP axis; for long-context cells the
    sequence dim is additionally sharded over the data axes.
    Mamba: channels over TP.
    """
    from jax.sharding import PartitionSpec as P
    from repro.models.layers import attn_dims
    kinds = cfg.layer_kinds()
    ax, dp = dist.tp_axis, dist.dp_axes
    kv_ok = (dist.mesh is not None and ax is not None
             and attn_dims(cfg, dist.ep_size).kv % dist.ep_size == 0)
    specs = {}
    for i, (mixer, _) in enumerate(kinds):
        if mixer.startswith("attn"):
            # batch over DP; long-context (batch=1) shards the KV
            # sequence over the data axes instead (DESIGN.md §7).
            # kv heads shard over TP when divisible, else the sequence
            # dim takes the TP axis (no head padding — see attn_dims).
            batch_ax = None if long_context else dp
            head_ax = ax if kv_ok else None
            if long_context and mixer == "attn_full":
                seq_ax = dp if kv_ok else tuple(dp) + (ax,)
            else:
                seq_ax = None if kv_ok else ax
            s = P(None, batch_ax, head_ax, seq_ax, None)
            specs[f"l{i}"] = {"k": s, "v": s}
        elif mixer == "mamba":
            batch_ax = None if long_context else dp
            specs[f"l{i}"] = {"conv": P(None, batch_ax, None, ax),
                              "h": P(None, batch_ax, ax, None)}
    return specs


# ----------------------------------------------------------------------
# forward
# ----------------------------------------------------------------------


def cast_params(params, dtype=jnp.bfloat16):
    """Cast float params to the compute dtype (mixed-precision fwd);
    numerically-sensitive leaves are re-upcast inside their layers."""
    return jax.tree.map(
        lambda a: a.astype(dtype)
        if hasattr(a, "dtype") and a.dtype == jnp.float32 else a, params)


def _mixer_apply(cfg, dist, lp, mixer, x, *, mode, lc, pos, chunk,
                 slot_idx=None, page_table=None, row_valid=None,
                 use_flash=False):
    """Apply attention/mamba; returns (y, new_layer_cache or {}).

    Decode-time serving extensions: ``slot_idx`` gathers only the active
    cache rows into the (bucketed) batch and scatters updates back
    (out-of-range entries are padding rows and are dropped);
    ``page_table`` switches attention layers to the paged KV pool.
    """
    window = cfg.sliding_window if mixer == "attn_swa" else None
    if mode == "chunk_prefill":
        # resumable chunked prefill: a [B, C] chunk runs against the
        # SERVING cache (paged pools / per-slot mamba state) instead of a
        # full-length wave scratch buffer.  ``pos`` is each row's chunk
        # start; row_valid's per-row prefix length is the chunk's n_tok.
        n_tok = (jnp.sum(row_valid.astype(jnp.int32), axis=1)
                 if row_valid is not None
                 else jnp.full((x.shape[0],), x.shape[1], jnp.int32))
        if mixer == "mamba":
            if slot_idx is None:
                return M.mamba_chunk(cfg, lp["mamba"], x, lc, n_tok,
                                     dist=dist)
            rows = jax.tree.map(
                lambda a: a[jnp.minimum(slot_idx, a.shape[0] - 1)], lc)
            y, nc = M.mamba_chunk(cfg, lp["mamba"], x, rows, n_tok,
                                  dist=dist)
            nc = jax.tree.map(
                lambda full, part: full.at[slot_idx].set(
                    part.astype(full.dtype), mode="drop"), lc, nc)
            return y, nc
        assert page_table is not None, \
            "chunked prefill requires the paged KV layout"
        return L.attention_prefill_paged(
            cfg, lp["attn"], x, lc, page_table, pos, n_tok,
            window=window, dims=L.attn_dims(cfg, dist.ep_size), dist=dist)
    if mixer == "mamba":
        if mode == "decode":
            if slot_idx is None:
                return M.mamba_decode(cfg, lp["mamba"], x, lc, dist=dist)
            rows = jax.tree.map(
                lambda a: a[jnp.minimum(slot_idx, a.shape[0] - 1)], lc)
            y, nc = M.mamba_decode(cfg, lp["mamba"], x, rows, dist=dist)
            nc = jax.tree.map(
                lambda full, part: full.at[slot_idx].set(
                    part.astype(full.dtype), mode="drop"), lc, nc)
            return y, nc
        # prefill on a length-padded batch: hand the decode cache off at
        # each row's true last position (the recurrence has no position
        # mask, so the final state would have absorbed padding tokens)
        lengths = (jnp.sum(row_valid, axis=1)
                   if mode == "prefill" and row_valid is not None
                   and row_valid.ndim == 2 else None)
        y, st = M.mamba_train(cfg, lp["mamba"], x, dist=dist,
                              return_state=(mode == "prefill"),
                              lengths=lengths)
        return y, (st if mode == "prefill" else {})
    dims = L.attn_dims(cfg, dist.ep_size)
    # attention
    if mode == "decode":
        if page_table is not None:
            return L.attention_decode_paged(
                cfg, lp["attn"], x, lc, page_table, pos,
                window=window, dims=dims, dist=dist,
                use_flash=use_flash)
        if slot_idx is not None:
            rows = {k: v[jnp.minimum(slot_idx, v.shape[0] - 1)]
                    for k, v in lc.items()}
            y, nc_rows = L.attention_decode(cfg, lp["attn"], x, rows, pos,
                                            window=window, dims=dims,
                                            dist=dist)
            nc = {k: lc[k].at[slot_idx].set(nc_rows[k], mode="drop")
                  for k in lc}
            return y, nc
        return L.attention_decode(cfg, lp["attn"], x, lc, pos,
                                  window=window, dims=dims, dist=dist)
    y, kv = L.attention_train(cfg, lp["attn"], x, window=window, dims=dims,
                              chunk=chunk, dist=dist,
                              return_kv=(mode == "prefill"))
    if mode != "prefill":
        return y, {}
    # fill the cache buffers from the prefill K/V
    k, v = kv
    s = x.shape[1]
    buf_k, buf_v = lc["k"], lc["v"]
    w = buf_k.shape[2]
    with jax.named_scope(SC.KV_WRITE):
        if window and w <= s:
            kw, vw = k[:, :, -w:], v[:, :, -w:]
            slots = (jnp.arange(s - w, s) % w)
            new_k = buf_k.at[:, :, slots].set(kw.astype(buf_k.dtype))
            new_v = buf_v.at[:, :, slots].set(vw.astype(buf_v.dtype))
        else:
            new_k = jax.lax.dynamic_update_slice_in_dim(
                buf_k, k.astype(buf_k.dtype), 0, axis=2)
            new_v = jax.lax.dynamic_update_slice_in_dim(
                buf_v, v.astype(buf_v.dtype), 0, axis=2)
    return y, {"k": new_k, "v": new_v}


_REMAT_POLICIES = {
    "dots_no_batch": lambda: jax.checkpoint_policies
    .dots_with_no_batch_dims_saveable,
    "dots": lambda: jax.checkpoint_policies.everything_saveable,
    "nothing": lambda: jax.checkpoint_policies.nothing_saveable,
    "save_moe": lambda: jax.checkpoint_policies.save_only_these_names(
        "moe_h", "moe_y"),
}


def apply_lm(cfg: ModelConfig, dist: Dist, params, *, tokens=None,
             embeds=None, pos=None, cache=None, routing=None,
             mode: str = "train", algo: str = "eplb",
             moe_impl: str = "ragged", chunk: int = 1024,
             remat: bool = False, capacity_factor: float = 1.25,
             use_pallas_route: bool = False, frames=None,
             compute_dtype=jnp.bfloat16, remat_policy: str = "dots_no_batch",
             slot_idx=None, page_table=None, row_valid=None,
             use_flash_kernel: bool = False):
    """Returns (logits, new_cache, stats).

    Serving (decode) extras: ``slot_idx`` [B] selects which cache rows
    this (bucketed) batch occupies; ``page_table`` [B, Pmax] switches
    attention to paged-KV pools (cache from :func:`init_paged_cache`);
    ``row_valid`` (bool, [B] decode / [B, S] prefill) keeps padding
    tokens out of MoE routing, making routing decisions — and therefore
    the numerics — invariant to batch-bucket and length padding;
    ``use_flash_kernel`` runs paged decode attention through the Pallas
    ``flash_decode_paged`` kernel (full-attention layers only — SWA
    keeps the gather reference).

    ``moe_impl`` selects the grouped expert-FFN datapath per MoE layer:
    ``"ragged"`` (lax.ragged_dot, the XLA fast path), ``"scan_tiles"``,
    ``"onehot"`` (oracle), ``"pallas"`` (two-pass Pallas kernel), or
    ``"fused"`` (one-pass up→act→down Pallas megakernel — the hidden
    activation never touches HBM; forward/serving only, train with a
    two-pass impl; kernels/README.md has the matrix).
    ``use_pallas_route`` moves METRO's Alg. 1 greedy onto the Pallas
    scalar-core kernel.

    ``mode="chunk_prefill"``: resumable chunked prefill.  ``tokens`` is
    a [B, C] chunk, ``pos`` [B] the absolute position of each row's
    first chunk token, ``cache`` the SERVING cache (paged pools +
    per-slot mamba state — no wave scratch buffer), ``row_valid``
    [B, C] a per-row contiguous prefix mask (its row-sum is the chunk's
    valid-token count).  Attention reads already-written pages, mamba
    carries {conv, h} across calls, so any chunk split of a prompt is
    bitwise identical to one monolithic chunk_prefill call — the
    invariant tests/test_chunked_prefill.py locks down.
    """
    if cfg.family == "encdec":
        from repro.models import encdec
        return encdec.apply_encdec(
            cfg, dist, params, tokens=tokens, embeds=embeds, pos=pos,
            cache=cache, mode=mode, chunk=chunk, frames=frames)

    kinds = cfg.layer_kinds()
    n_blocks = cfg.num_layers // len(kinds)
    dp = dist.dp_axes
    params = cast_params(params, compute_dtype)

    with jax.named_scope(SC.EMBED):
        if cfg.input_mode == "embeddings" and mode != "decode":
            x = embeds
        else:
            x = params["embed"][tokens]
        x = x.astype(compute_dtype)
        x = dist.shard(x, dp, None, None)

    routing = routing or {}
    cache = cache or {}
    moe_mode = "features" if mode == "decode" else "tokens"

    def block_body(x, blk):
        bp, bc, brt = blk
        new_bc = {}
        stats_l = []
        for i, (mixer, ffn) in enumerate(kinds):
            li = f"l{i}"
            lp = bp[li]
            with jax.named_scope(
                    SC.MAMBA if mixer == "mamba" else SC.ATTENTION):
                h = L.apply_norm(cfg, lp["norm1"], x)
                y, nc = _mixer_apply(cfg, dist, lp, mixer, h, mode=mode,
                                     lc=bc.get(li), pos=pos, chunk=chunk,
                                     slot_idx=slot_idx,
                                     page_table=page_table,
                                     row_valid=row_valid,
                                     use_flash=use_flash_kernel)
            if nc:
                new_bc[li] = nc
            # cast keeps the residual stream in the compute dtype even
            # when the mixer read a wider KV pool (kv_dtype="fp32")
            x = x + y.astype(x.dtype)
            if ffn == "dense":
                with jax.named_scope(SC.MLP):
                    h2 = L.apply_norm(cfg, lp["norm2"], x)
                    y2 = L.apply_mlp(cfg, lp["mlp"], h2, dist=dist)
                x = x + y2.astype(x.dtype)
            elif ffn == "moe":
                with jax.named_scope(SC.MOE):
                    h2 = L.apply_norm(cfg, lp["norm2"], x)
                    if moe_mode == "features":
                        h2f = h2[:, 0]          # [B, 1, d] -> [B, d]
                        y2, st = MOE.moe_ffn(
                            cfg, dist, lp["moe"], brt[li], h2f, algo=algo,
                            impl=moe_impl, mode="features",
                            capacity_factor=capacity_factor,
                            use_pallas_route=use_pallas_route,
                            row_valid=row_valid)
                        y2 = y2[:, None]
                    else:
                        y2, st = MOE.moe_ffn(
                            cfg, dist, lp["moe"], brt[li], h2, algo=algo,
                            impl=moe_impl, mode="tokens",
                            capacity_factor=capacity_factor,
                            use_pallas_route=use_pallas_route,
                            row_valid=row_valid)
                stats_l.append(st)
                x = x + y2.astype(x.dtype)
        if stats_l:
            stats = jax.tree.map(lambda *v: jnp.stack(v), *stats_l)
        else:
            stats = {}
        return x, (new_bc, stats)

    body = block_body
    if remat and mode == "train":
        body = jax.checkpoint(
            block_body, policy=_REMAT_POLICIES[remat_policy]())

    with jax.named_scope(SC.LAYERS):
        x, (new_cache, stats) = jax.lax.scan(
            body, x, (params["blocks"], cache, routing))

    with jax.named_scope(SC.HEAD):
        x = L.apply_norm(cfg, params["final_norm"], x)
        if cfg.tie_embeddings and cfg.input_mode == "tokens":
            unembed = params["embed"].T
        else:
            unembed = params["unembed"]
        logits = x @ unembed.astype(x.dtype)
        logits = dist.shard(logits, dp, None, dist.tp_axis)

    # reduce per-(block, layer) stats
    if stats:
        stats = {
            "aux_loss": jnp.mean(stats["aux_loss"]),
            "max_activated": jnp.max(stats["max_activated"]),
            "mean_activated": jnp.mean(stats["mean_activated"]),
            "max_tokens": jnp.max(stats["max_tokens"]),
            # summed over layers -> rebalance signal [N]
            "expert_hist": jnp.sum(stats["expert_hist"], axis=(0, 1)),
            # kept per MoE layer [L_moe, R] (layer order): the expert
            # pool pages weights per (layer, slot), so the executor
            # replays layers in sequence, not a summed blur
            "slot_hist": stats["slot_hist"].reshape(
                -1, stats["slot_hist"].shape[-1]),
        }
    else:
        stats = {"aux_loss": jnp.zeros((), jnp.float32),
                 "max_activated": jnp.zeros((), jnp.float32),
                 "mean_activated": jnp.zeros((), jnp.float32),
                 "max_tokens": jnp.zeros((), jnp.float32),
                 "expert_hist": jnp.zeros((max(cfg.num_experts, 1),),
                                          jnp.float32),
                 "slot_hist": jnp.zeros((1, 1), jnp.float32)}
    return logits, new_cache, stats


def lm_loss(cfg: ModelConfig, dist: Dist, params, batch, *, routing=None,
            algo: str = "eplb", moe_impl: str = "ragged",
            remat: bool = False, aux_coef: float = 0.01,
            chunk: int = 1024, remat_policy: str = "dots_no_batch"):
    """Mean next-token NLL + MoE aux loss. Labels are pre-shifted."""
    logits, _, stats = apply_lm(
        cfg, dist, params, tokens=batch.get("tokens"),
        embeds=batch.get("embeds"), frames=batch.get("frames"),
        routing=routing, mode="train",
        algo=algo, moe_impl=moe_impl, remat=remat, chunk=chunk,
        remat_policy=remat_policy)
    labels = batch["labels"]
    logits = logits.astype(jnp.float32)
    lse = jax.nn.logsumexp(logits, axis=-1)
    ll = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    nll = jnp.mean(lse - ll)
    loss = nll + aux_coef * stats["aux_loss"]
    stats = dict(stats, nll=nll)
    return loss, stats
