"""Shared neural layers: norms, RoPE, MLP, attention (all variants).

Attention paths:
  * full/causal train+prefill — chunked online-softmax ("flash-style")
    scan over KV chunks; memory O(S * chunk) instead of O(S^2).
  * sliding-window train+prefill — banded: each Q chunk attends only to
    its own chunk + the preceding window (statically-sized slice), so
    compute is O(S * (W + chunk)), not O(S^2).
  * decode (q_len = 1) — dense scores against the KV cache (linear in
    cache length); SWA uses a rolling-buffer cache of width W.

GQA is computed in grouped form (no materialized head repetition).
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.models import scopes as SC

# ----------------------------------------------------------------------
# norms
# ----------------------------------------------------------------------


def rms_norm(x, scale, eps: float = 1e-6):
    dtype = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    y = x * jax.lax.rsqrt(var + eps)
    if scale is not None:
        y = y * (1.0 + scale.astype(jnp.float32))
    return y.astype(dtype)


def layer_norm(x, scale, bias, eps: float = 1e-5):
    """Non-parametric when scale/bias are None (olmo)."""
    dtype = x.dtype
    x = x.astype(jnp.float32)
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.var(x, axis=-1, keepdims=True)
    y = (x - mu) * jax.lax.rsqrt(var + eps)
    if scale is not None:
        y = y * scale.astype(jnp.float32)
    if bias is not None:
        y = y + bias.astype(jnp.float32)
    return y.astype(dtype)


def apply_norm(cfg: ModelConfig, params, x):
    if cfg.nonparametric_norm:
        return layer_norm(x, None, None)
    return rms_norm(x, params["scale"])


def init_norm(cfg: ModelConfig, key):
    if cfg.nonparametric_norm:
        return {}
    return {"scale": jnp.zeros((cfg.d_model,), jnp.float32)}


# ----------------------------------------------------------------------
# RoPE
# ----------------------------------------------------------------------


def rope_frequencies(head_dim: int, theta: float) -> jax.Array:
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32)
                            / head_dim))


def apply_rope(x, positions, theta: float):
    """x: [..., S, head_dim]; positions: broadcastable to [..., S]."""
    hd = x.shape[-1]
    freqs = rope_frequencies(hd, theta)                    # [hd/2]
    angles = positions[..., None].astype(jnp.float32) * freqs  # [..., S, hd/2]
    sin, cos = jnp.sin(angles), jnp.cos(angles)
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    y = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return y.astype(x.dtype)


# ----------------------------------------------------------------------
# dense MLP (SwiGLU or GELU)
# ----------------------------------------------------------------------


def init_mlp(cfg: ModelConfig, key, d_ff: Optional[int] = None):
    d, f = cfg.d_model, d_ff or cfg.d_ff
    k1, k2, k3 = jax.random.split(key, 3)
    scale_in = 1.0 / np.sqrt(d)
    scale_out = 1.0 / np.sqrt(f)
    p = {"w_up": jax.random.normal(k2, (d, f), jnp.float32) * scale_in,
         "w_down": jax.random.normal(k3, (f, d), jnp.float32) * scale_out}
    if cfg.gated_mlp:
        p["w_gate"] = jax.random.normal(k1, (d, f), jnp.float32) * scale_in
    return p


def apply_mlp(cfg: ModelConfig, params, x, dist=None):
    h_up = x @ params["w_up"]
    if dist is not None:
        h_up = dist.shard(h_up, dist.dp_axes, None, dist.tp_axis)
    if cfg.gated_mlp:
        h = jax.nn.silu(x @ params["w_gate"]) * h_up
    else:
        h = jax.nn.gelu(h_up)
    y = h @ params["w_down"]
    if dist is not None:
        y = dist.shard(y, dist.dp_axes, None, None)
    return y


# ----------------------------------------------------------------------
# attention
# ----------------------------------------------------------------------

_NEG = -1e30


@dataclasses.dataclass(frozen=True)
class AttnDims:
    """Resolved attention head layout after TP-divisibility padding.

    If num_kv_heads doesn't divide the TP axis, KV heads are logically
    replicated to ``kv`` so the KV tensors shard (standard GQA-on-TP
    practice; noted in DESIGN.md).
    """
    heads: int
    kv: int
    head_dim: int

    @property
    def group(self) -> int:
        return self.heads // self.kv


def attn_dims(cfg: ModelConfig, tp: int = 1) -> AttnDims:
    # no KV-head padding: when kv doesn't divide the TP axis, the KV
    # *cache* shards its sequence dim instead (lm.cache_pspec), which
    # avoids doubling cache bytes for kv=8 archs on 16-way TP.
    h, kv = cfg.num_heads, cfg.num_kv_heads
    if kv <= 0:
        kv = h
    if kv and h % kv != 0:            # safety: fall back to MHA grouping
        kv = h
    return AttnDims(h, kv, cfg.head_dim)


def init_attention(cfg: ModelConfig, key, tp: int = 1):
    d = cfg.d_model
    dims = attn_dims(cfg, tp)
    kq, kk, kv_, ko, kn = jax.random.split(key, 5)
    s = 1.0 / np.sqrt(d)
    p = {
        "wq": jax.random.normal(kq, (d, dims.heads * dims.head_dim), jnp.float32) * s,
        "wk": jax.random.normal(kk, (d, dims.kv * dims.head_dim), jnp.float32) * s,
        "wv": jax.random.normal(kv_, (d, dims.kv * dims.head_dim), jnp.float32) * s,
        "wo": jax.random.normal(ko, (dims.heads * dims.head_dim, d), jnp.float32)
        * (1.0 / np.sqrt(dims.heads * dims.head_dim)),
    }
    if cfg.qk_norm:
        p["q_norm"] = jnp.zeros((dims.head_dim,), jnp.float32)
        p["k_norm"] = jnp.zeros((dims.head_dim,), jnp.float32)
    return p


def _project_qkv(cfg, params, x, positions, dims: AttnDims, *, rope=True):
    b, s, _ = x.shape
    q = (x @ params["wq"]).reshape(b, s, dims.heads, dims.head_dim)
    k = (x @ params["wk"]).reshape(b, s, dims.kv, dims.head_dim)
    v = (x @ params["wv"]).reshape(b, s, dims.kv, dims.head_dim)
    if cfg.qk_norm:
        q = rms_norm(q, params["q_norm"])
        k = rms_norm(k, params["k_norm"])
    if rope:
        q = apply_rope(q, positions[:, :, None], cfg.rope_theta)
        k = apply_rope(k, positions[:, :, None], cfg.rope_theta)
    # [B, kv, group|1, S, hd]
    q = q.reshape(b, s, dims.kv, dims.group, dims.head_dim).transpose(0, 2, 3, 1, 4)
    k = k.transpose(0, 2, 1, 3)[:, :, None]
    v = v.transpose(0, 2, 1, 3)[:, :, None]
    return q, k, v


def _flash_causal(q, k, v, *, chunk: int, window: Optional[int], scale):
    """Online-softmax attention over KV chunks.

    q: [B, KV, G, S, hd]; k/v: [B, KV, 1, S, hd].  For SWA (window W),
    each Q chunk attends to a statically-sized banded KV slice instead of
    scanning all chunks.
    """
    b, kvh, g, s_orig, hd = q.shape
    chunk = min(chunk, s_orig)
    pad = (-s_orig) % chunk
    if pad:
        q = jnp.pad(q, ((0, 0),) * 3 + ((0, pad), (0, 0)))
        k = jnp.pad(k, ((0, 0),) * 3 + ((0, pad), (0, 0)))
        v = jnp.pad(v, ((0, 0),) * 3 + ((0, pad), (0, 0)))
    s = s_orig + pad
    n_q = s // chunk
    qs = q.reshape(b, kvh, g, n_q, chunk, hd)

    if window is not None and window < s:
        band = int(np.ceil(window / chunk)) * chunk  # look-back, full chunks
        kv_len = band + chunk

        def per_qchunk(qi, idx):
            # KV slice [idx*chunk - band, idx*chunk + chunk)
            start = idx * chunk
            k_sl = jax.lax.dynamic_slice_in_dim(
                jnp.pad(k, ((0, 0), (0, 0), (0, 0), (band, 0), (0, 0))),
                start, kv_len, axis=3)
            v_sl = jax.lax.dynamic_slice_in_dim(
                jnp.pad(v, ((0, 0), (0, 0), (0, 0), (band, 0), (0, 0))),
                start, kv_len, axis=3)
            qpos = start + jnp.arange(chunk)
            kpos = start - band + jnp.arange(kv_len)
            mask = (kpos[None, :] <= qpos[:, None]) & \
                   (kpos[None, :] > qpos[:, None] - window) & \
                   (kpos[None, :] >= 0)
            logits = jnp.einsum("bkgqh,bkgsh->bkgqs", qi, k_sl,
                                preferred_element_type=jnp.float32) * scale
            logits = jnp.where(mask[None, None, None], logits, _NEG)
            p = jax.nn.softmax(logits, axis=-1).astype(v.dtype)
            return jnp.einsum("bkgqs,bkgsh->bkgqh", p, v_sl)

        out = jax.lax.map(
            lambda t: per_qchunk(t[0], t[1]),
            (qs.transpose(3, 0, 1, 2, 4, 5), jnp.arange(n_q)))
        out = out.transpose(1, 2, 3, 0, 4, 5).reshape(b, kvh, g, s, hd)
        return out[:, :, :, :s_orig]

    # full causal: scan KV chunks with running (m, l, o)
    n_kv = s // chunk
    ks = k.reshape(b, kvh, 1, n_kv, chunk, hd)
    vs = v.reshape(b, kvh, 1, n_kv, chunk, hd)
    qpos = jnp.arange(s)

    def body(carry, kv_idx):
        m, l, o = carry
        kj = jax.lax.dynamic_index_in_dim(ks, kv_idx, axis=3, keepdims=False)
        vj = jax.lax.dynamic_index_in_dim(vs, kv_idx, axis=3, keepdims=False)
        kpos = kv_idx * chunk + jnp.arange(chunk)
        logits = jnp.einsum("bkgqh,bkgsh->bkgqs", q, kj,
                            preferred_element_type=jnp.float32) * scale
        mask = kpos[None, :] <= qpos[:, None]
        logits = jnp.where(mask[None, None, None], logits, _NEG)
        m_new = jnp.maximum(m, logits.max(axis=-1))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(logits - m_new[..., None])
        l_new = l * alpha + p.sum(axis=-1)
        o_new = o * alpha[..., None] + jnp.einsum(
            "bkgqs,bkgsh->bkgqh", p.astype(vj.dtype), vj).astype(jnp.float32)
        return (m_new, l_new, o_new), None

    m0 = jnp.full((b, kvh, g, s), _NEG, jnp.float32)
    l0 = jnp.zeros((b, kvh, g, s), jnp.float32)
    o0 = jnp.zeros((b, kvh, g, s, hd), jnp.float32)
    (m, l, o), _ = jax.lax.scan(body, (m0, l0, o0), jnp.arange(n_kv))
    out = (o / jnp.maximum(l, 1e-30)[..., None]).astype(q.dtype)
    return out[:, :, :, :s_orig]


def attention_train(cfg: ModelConfig, params, x, *, positions=None,
                    window: Optional[int] = None, dims: Optional[AttnDims] = None,
                    chunk: int = 1024, rope: bool = True, dist=None,
                    return_kv: bool = False):
    """Causal (optionally sliding-window) attention, train/prefill.

    Returns (out, kv) where kv = (k [B,KV,S,hd], v) when return_kv (for
    prefill cache fills) else None."""
    b, s, d = x.shape
    dims = dims or attn_dims(cfg)
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(s), (b, s))
    q, k, v = _project_qkv(cfg, params, x, positions, dims, rope=rope)
    if dist is not None:
        q = dist.shard(q, dist.dp_axes, dist.tp_axis)
        k = dist.shard(k, dist.dp_axes, dist.tp_axis)
        v = dist.shard(v, dist.dp_axes, dist.tp_axis)
    scale = 1.0 / np.sqrt(dims.head_dim)
    o = _flash_causal(q, k, v, chunk=chunk, window=window, scale=scale)
    o = o.transpose(0, 3, 1, 2, 4).reshape(b, s, dims.heads * dims.head_dim)
    out = o @ params["wo"]
    if return_kv:
        return out, (k[:, :, 0], v[:, :, 0])
    return out, None


def attention_bidir(cfg: ModelConfig, params, x, *, dims=None, dist=None):
    """Bidirectional attention (whisper encoder). Small S: dense scores."""
    b, s, d = x.shape
    dims = dims or attn_dims(cfg)
    positions = jnp.broadcast_to(jnp.arange(s), (b, s))
    q, k, v = _project_qkv(cfg, params, x, positions, dims, rope=False)
    scale = 1.0 / np.sqrt(dims.head_dim)
    logits = jnp.einsum("bkgqh,bkgsh->bkgqs", q, k,
                        preferred_element_type=jnp.float32) * scale
    p = jax.nn.softmax(logits, axis=-1).astype(v.dtype)
    o = jnp.einsum("bkgqs,bkgsh->bkgqh", p, v)
    o = o.transpose(0, 3, 1, 2, 4).reshape(b, s, dims.heads * dims.head_dim)
    return o @ params["wo"]


def attention_cross(cfg: ModelConfig, params, x, kv_cache, *, dims=None):
    """Cross-attention against precomputed encoder K/V (whisper decoder).

    kv_cache: {"k": [B, KV, F, hd], "v": ...} (no RoPE on cross keys)."""
    b, s, d = x.shape
    dims = dims or attn_dims(cfg)
    q = (x @ params["wq"]).reshape(b, s, dims.heads, dims.head_dim)
    if cfg.qk_norm:
        q = rms_norm(q, params["q_norm"])
    q = q.reshape(b, s, dims.kv, dims.group, dims.head_dim).transpose(0, 2, 3, 1, 4)
    k, v = kv_cache["k"][:, :, None], kv_cache["v"][:, :, None]
    scale = 1.0 / np.sqrt(dims.head_dim)
    logits = jnp.einsum("bkgqh,bkgsh->bkgqs", q, k,
                        preferred_element_type=jnp.float32) * scale
    p = jax.nn.softmax(logits, axis=-1).astype(v.dtype)
    o = jnp.einsum("bkgqs,bkgsh->bkgqh", p, v)
    o = o.transpose(0, 3, 1, 2, 4).reshape(b, s, dims.heads * dims.head_dim)
    return o @ params["wo"]


def cross_kv(cfg: ModelConfig, params, enc_out, *, dims=None):
    """Precompute cross-attention K/V from encoder output."""
    b, f, _ = enc_out.shape
    dims = dims or attn_dims(cfg)
    k = (enc_out @ params["wk"]).reshape(b, f, dims.kv, dims.head_dim)
    v = (enc_out @ params["wv"]).reshape(b, f, dims.kv, dims.head_dim)
    if cfg.qk_norm:
        k = rms_norm(k, params["k_norm"])
    return {"k": k.transpose(0, 2, 1, 3), "v": v.transpose(0, 2, 1, 3)}


# ---------------------------- decode ----------------------------------


def init_kv_cache(cfg: ModelConfig, batch: int, max_len: int,
                  window: Optional[int] = None, dtype=jnp.bfloat16,
                  tp: int = 1):
    dims = attn_dims(cfg, tp)
    n = min(window, max_len) if window else max_len
    shape = (batch, dims.kv, n, dims.head_dim)
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


def init_paged_kv_cache(cfg: ModelConfig, num_pages: int, page_size: int,
                        dtype=jnp.bfloat16, tp: int = 1):
    """Flat page pool shared by all sequences of one attention layer.

    Layout [num_pages, page_size, kv, head_dim]: (page, offset) flattens
    to one linear token index, so reads/writes are single gathers and
    scatters over a ``[num_pages * page_size, kv, hd]`` view."""
    dims = attn_dims(cfg, tp)
    shape = (num_pages, page_size, dims.kv, dims.head_dim)
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


def attention_prefill_paged(cfg: ModelConfig, params, x, cache, page_table,
                            start, n_tok, *, window: Optional[int] = None,
                            dims=None, rope: bool = True, dist=None):
    """One resumable prefill chunk against the *paged* KV pool.

    x: [B, C, d] chunk activations; cache k/v: [P, ps, KV, hd] (shared
    pools); page_table: [B, Pmax]; start: [B] absolute position of each
    row's first chunk token; n_tok: [B] valid tokens this chunk (<= C).
    Tokens past a row's n_tok are padding: their K/V writes are dropped
    and their outputs are garbage the caller must mask.

    The chunk's K/V are scattered through the page table first, then
    every query attends over the full gathered page view with the causal
    mask ``spos <= start + i`` (+ window for SWA).  Because the gathered
    view always has the same Pmax*ps length and later positions are
    masked to exact zeros, the outputs — and the written pages — are
    bitwise invariant to how a prompt is split into chunks; a single
    call covering the whole prompt is the reference semantics the
    chunked-prefill equivalence suite pins down.

    Memory note: this reference path materializes the gathered
    [B, Pmax*ps, KV, hd] view (an O(max_len) TRANSIENT, one layer at a
    time) — what chunking eliminates is the wave path's PERSISTENT
    all-layer O(max_len) scratch pytree.  The Pallas twin
    (``kernels/flash_decode.flash_prefill_paged``) streams pages
    page-by-page for a true O(chunk) footprint.

    Returns (out [B, C, d], new_cache).
    """
    b, c, d = x.shape
    dims = dims or attn_dims(cfg)
    num_pages, ps, kvh, hd = cache["k"].shape
    pmax = page_table.shape[1]
    offs = jnp.arange(c)
    positions = start[:, None] + offs[None, :]                 # [B, C]
    q, k, v = _project_qkv(cfg, params, x, positions, dims, rope=rope)
    # q: [B, KV, G, C, hd]; k/v: [B, KV, 1, C, hd]

    # scatter this chunk's K/V through the page table (flat token view;
    # padding tokens and unmapped pages -> OOB index -> dropped)
    with jax.named_scope(SC.KV_WRITE):
        k_tok = k[:, :, 0].transpose(0, 2, 1, 3)           # [B, C, KV, hd]
        v_tok = v[:, :, 0].transpose(0, 2, 1, 3)
        lp = jnp.minimum(positions // ps, pmax - 1)
        phys = jnp.take_along_axis(page_table, lp, axis=1)     # [B, C]
        valid_w = (offs[None, :] < n_tok[:, None]) & (phys >= 0)
        flat_idx = jnp.where(valid_w, phys * ps + positions % ps,
                             num_pages * ps)
        kf = cache["k"].reshape(num_pages * ps, kvh, hd)
        vf = cache["v"].reshape(num_pages * ps, kvh, hd)
        kf = kf.at[flat_idx.reshape(-1)].set(
            k_tok.reshape(-1, kvh, hd).astype(kf.dtype), mode="drop")
        vf = vf.at[flat_idx.reshape(-1)].set(
            v_tok.reshape(-1, kvh, hd).astype(vf.dtype), mode="drop")
        new_cache = {"k": kf.reshape(num_pages, ps, kvh, hd),
                     "v": vf.reshape(num_pages, ps, kvh, hd)}

    # gather this batch's pages and attend with a chunk-offset query
    # window (the Pallas twin is kernels/flash_decode.flash_prefill_paged)
    pt_safe = jnp.maximum(page_table, 0)
    kg = new_cache["k"][pt_safe].reshape(b, pmax * ps, kvh, hd)
    vg = new_cache["v"][pt_safe].reshape(b, pmax * ps, kvh, hd)
    kg = kg.transpose(0, 2, 1, 3)
    vg = vg.transpose(0, 2, 1, 3)
    if kg.dtype.itemsize == 1:          # fp8 pool: dequantize for dots
        kg = kg.astype(jnp.bfloat16)
        vg = vg.astype(jnp.bfloat16)

    scale = 1.0 / np.sqrt(dims.head_dim)
    logits = jnp.einsum("bkgqh,bksh->bkgqs", q, kg,
                        preferred_element_type=jnp.float32) * scale
    spos = jnp.arange(pmax * ps)
    valid = (spos[None, None, :] <= positions[:, :, None]) & \
        jnp.repeat(page_table >= 0, ps, axis=1)[:, None, :]
    if window:
        valid &= spos[None, None, :] > positions[:, :, None] - window
    logits = jnp.where(valid[:, None, None, :, :], logits, _NEG)
    p = jax.nn.softmax(logits, axis=-1).astype(vg.dtype)
    o = jnp.einsum("bkgqs,bksh->bkgqh", p, vg)
    o = o.transpose(0, 3, 1, 2, 4).reshape(b, c, dims.heads * dims.head_dim)
    return o @ params["wo"], new_cache


def attention_decode_paged(cfg: ModelConfig, params, x, cache, page_table,
                           pos, *, window: Optional[int] = None, dims=None,
                           rope: bool = True, dist=None,
                           use_flash: bool = False):
    """Single-token decode against a *paged* KV pool.

    x: [B, 1, d]; cache k/v: [P, ps, KV, hd] (the shared page pool);
    page_table: [B, Pmax] physical page per logical page (-1 = hole);
    pos: [B] absolute position of the new token.  The new token's page
    must already be mapped (the engine's allocator guarantees it);
    writes through an unmapped table entry are dropped, so padding rows
    (page_table row of -1s) are harmless.  Returns (out, new_cache).

    Sliding-window layers store the full sequence in pages and mask the
    window at read time — unlike the dense rolling buffer this keeps
    positions linear, so padded prefill garbage can never alias a live
    slot.

    ``use_flash`` routes the attention reduction through the Pallas
    ``flash_decode_paged`` kernel (page-table-driven DMA, no gathered
    [B, Pmax*ps] view, in-register dequant for fp8 pools) instead of
    the jnp gather reference.  The K/V *write* path is shared — only
    the read/softmax differs, and the kernel's fp32 online softmax
    matches the reference to accumulation-order tolerance (the
    interpret-mode parity test).  SWA layers keep the reference read
    (the decode kernel has no window mask yet).
    """
    b, s1, d = x.shape
    assert s1 == 1
    dims = dims or attn_dims(cfg)
    num_pages, ps, kvh, hd = cache["k"].shape
    pmax = page_table.shape[1]
    q = (x @ params["wq"]).reshape(b, 1, dims.heads, dims.head_dim)
    k = (x @ params["wk"]).reshape(b, 1, dims.kv, dims.head_dim)
    v = (x @ params["wv"]).reshape(b, 1, dims.kv, dims.head_dim)
    if cfg.qk_norm:
        q = rms_norm(q, params["q_norm"])
        k = rms_norm(k, params["k_norm"])
    if rope:
        q = apply_rope(q, pos[:, None, None], cfg.rope_theta)
        k = apply_rope(k, pos[:, None, None], cfg.rope_theta)

    # write the new token through the page table (1-scatter on the flat
    # token view; unmapped pages -> OOB index -> dropped)
    with jax.named_scope(SC.KV_WRITE):
        lp = jnp.minimum(pos // ps, pmax - 1)
        phys = page_table[jnp.arange(b), lp]                   # [B]
        flat_idx = jnp.where(phys >= 0, phys * ps + pos % ps,
                             num_pages * ps)
        kf = cache["k"].reshape(num_pages * ps, kvh, hd)
        vf = cache["v"].reshape(num_pages * ps, kvh, hd)
        kf = kf.at[flat_idx].set(k[:, 0].astype(kf.dtype), mode="drop")
        vf = vf.at[flat_idx].set(v[:, 0].astype(vf.dtype), mode="drop")
        new_cache = {"k": kf.reshape(num_pages, ps, kvh, hd),
                     "v": vf.reshape(num_pages, ps, kvh, hd)}

    if use_flash and not window:
        from repro.kernels.flash_decode import flash_decode_paged
        q4 = q.reshape(b, dims.kv, dims.group, dims.head_dim)
        o = flash_decode_paged(
            q4, new_cache["k"], new_cache["v"], pos, page_table)
        o = o.reshape(b, 1, dims.heads * dims.head_dim)
        return o @ params["wo"], new_cache

    # page-table-indexed read: gather this batch's pages into a
    # [B, KV, Pmax*ps, hd] view (the Pallas paged kernel streams the
    # same pages without materializing the view; kernels/flash_decode)
    pt_safe = jnp.maximum(page_table, 0)
    kg = new_cache["k"][pt_safe].reshape(b, pmax * ps, kvh, hd)
    vg = new_cache["v"][pt_safe].reshape(b, pmax * ps, kvh, hd)
    kg = kg.transpose(0, 2, 1, 3)
    vg = vg.transpose(0, 2, 1, 3)
    if kg.dtype.itemsize == 1:          # fp8 pool: dequantize for dots
        kg = kg.astype(jnp.bfloat16)
        vg = vg.astype(jnp.bfloat16)

    q = q.reshape(b, dims.kv, dims.group, dims.head_dim)
    scale = 1.0 / np.sqrt(dims.head_dim)
    logits = jnp.einsum("bkgh,bksh->bkgs", q, kg,
                        preferred_element_type=jnp.float32) * scale
    spos = jnp.arange(pmax * ps)
    valid = (spos[None, :] <= pos[:, None]) & \
        jnp.repeat(page_table >= 0, ps, axis=1)
    if window:
        valid &= spos[None, :] > pos[:, None] - window
    logits = jnp.where(valid[:, None, None, :], logits, _NEG)
    p = jax.nn.softmax(logits, axis=-1).astype(vg.dtype)
    o = jnp.einsum("bkgs,bksh->bkgh", p, vg)
    o = o.reshape(b, 1, dims.heads * dims.head_dim)
    return o @ params["wo"], new_cache


def attention_decode(cfg: ModelConfig, params, x, cache, pos, *,
                     window: Optional[int] = None, dims=None,
                     rope: bool = True, dist=None):
    """Single-token decode against a KV cache.

    x: [B, 1, d]; cache k/v: [B, KV, S_cache, hd]; pos: [B] absolute
    position of the new token.  SWA uses a rolling buffer (S_cache == W).
    Returns (out [B, 1, d], new_cache).
    """
    b, s1, d = x.shape
    assert s1 == 1
    dims = dims or attn_dims(cfg)
    s_cache = cache["k"].shape[2]
    q = (x @ params["wq"]).reshape(b, 1, dims.heads, dims.head_dim)
    k = (x @ params["wk"]).reshape(b, 1, dims.kv, dims.head_dim)
    v = (x @ params["wv"]).reshape(b, 1, dims.kv, dims.head_dim)
    if cfg.qk_norm:
        q = rms_norm(q, params["q_norm"])
        k = rms_norm(k, params["k_norm"])
    if rope:
        q = apply_rope(q, pos[:, None, None], cfg.rope_theta)
        k = apply_rope(k, pos[:, None, None], cfg.rope_theta)

    with jax.named_scope(SC.KV_WRITE):
        slot = pos % s_cache if (window and window <= s_cache) else pos
        slot = jnp.minimum(slot, s_cache - 1)
        bidx = jnp.arange(b)
        new_k = cache["k"].at[bidx, :, slot].set(
            k[:, 0].astype(cache["k"].dtype))
        new_v = cache["v"].at[bidx, :, slot].set(
            v[:, 0].astype(cache["v"].dtype))

    q = q.reshape(b, dims.kv, dims.group, dims.head_dim)
    scale = 1.0 / np.sqrt(dims.head_dim)
    # fp8 KV cache support: dequantize for the attention dots (on TPU the
    # convert fuses into the HBM read stream -> 2x less cache traffic)
    k_c = new_k.astype(jnp.bfloat16) if new_k.dtype.itemsize == 1 else new_k
    v_c = new_v.astype(jnp.bfloat16) if new_v.dtype.itemsize == 1 else new_v
    logits = jnp.einsum("bkgh,bksh->bkgs", q, k_c,
                        preferred_element_type=jnp.float32) * scale
    spos = jnp.arange(s_cache)
    if window and window <= s_cache:
        # rolling buffer: slot j holds absolute position
        # p(j) = pos - ((pos - j) mod S); valid iff p(j) >= 0
        absp = pos[:, None] - ((pos[:, None] - spos[None, :]) % s_cache)
        valid = absp >= 0
    else:
        valid = spos[None, :] <= pos[:, None]
    logits = jnp.where(valid[:, None, None, :], logits, _NEG)
    p = jax.nn.softmax(logits, axis=-1).astype(v_c.dtype)
    o = jnp.einsum("bkgs,bksh->bkgh", p, v_c)
    o = o.reshape(b, 1, dims.heads * dims.head_dim)
    return o @ params["wo"], {"k": new_k, "v": new_v}
