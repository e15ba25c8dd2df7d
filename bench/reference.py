"""Plain float32 reference of the Qwen family's models, and its fp8
control; bound by ``bench/arch/qwen3_moe.py``, through which the
harness reaches it.

Written from the published architecture (Qwen3-MoE / Qwen2-MoE
decoders), in ``jax.numpy`` under ``default_matmul_precision("highest")``,
with no kernels, cache or batching, and nothing imported from the
program.  The weights are the seeded bf16 draws of :mod:`bench.weights`
read in float32.  Replicas of an expert hold the same weights, so the
reference routes to experts, not slots: a served token agrees with it
whichever replica METRO or EPLB chose.

It runs once the window has closed, layer by layer over all sampled
sequences, so that only one layer's float32 weights live at a time:

* **served-token gap.**  Each sequence is a prompt followed by the
  tokens the program served for it.  At each served position the gap
  is the reference's best logit minus its logit of the served token:
  0 where the program chose the reference's argmax.
* **control.**  The same forward with every linear layer computed in
  fp8 (e4m3, per-output-channel weight scales, per-token activation
  scales, float32 accumulation), the next precision below the served
  bf16.  At each position its argmax is read, and the reference's gap
  of that token is the control's gap.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from bench import weights as W
from bench.sizes import Sizes

F32 = jnp.float32
FP8 = jnp.float8_e4m3fn
FP8_MAX = 448.0
BLOCK_Q = 512           # query rows per attention block
BLOCK_V = 512           # rows per LM-head block


def _fp8(a, axis):
    """Round ``a`` to e4m3 with one scale per slice along ``axis``
    (the max of |a| maps to the format's largest value)."""
    s = jnp.max(jnp.abs(a), axis=axis, keepdims=True) / FP8_MAX
    s = jnp.where(s > 0, s, 1.0)
    return (a / s).astype(FP8).astype(F32) * s


def _mm(x, w, quant: bool):
    """x [..., k] @ w [k, n]; with ``quant`` both sides in fp8."""
    if quant:
        x = _fp8(x, -1)
        w = _fp8(w, 0)
    return x @ w


def rms_norm(x, scale, eps):
    y = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return y * (1.0 + scale)


def rope(x, pos, theta):
    """Rotate-half RoPE; x [L, heads, hd], pos [L]."""
    hd = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=F32) / hd)
    ang = pos[:, None, None].astype(F32) * inv
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    c, s = jnp.cos(ang), jnp.sin(ang)
    return jnp.concatenate([x1 * c - x2 * s, x1 * s + x2 * c], axis=-1)


def attention(s: Sizes, lw, h, quant):
    """Causal GQA self-attention of one sequence h [L, d].  Padding
    after the sequence attends too, but nothing attends to it and its
    rows are never read."""
    L = h.shape[0]
    g = s.heads // s.kv_heads
    q = _mm(h, lw["wq"], quant).reshape(L, s.heads, s.head_dim)
    k = _mm(h, lw["wk"], quant).reshape(L, s.kv_heads, s.head_dim)
    v = _mm(h, lw["wv"], quant).reshape(L, s.kv_heads, s.head_dim)
    if s.qk_norm:
        q = rms_norm(q, lw["q_norm"], s.eps)
        k = rms_norm(k, lw["k_norm"], s.eps)
    pos = jnp.arange(L)
    q = rope(q, pos, s.rope_theta).reshape(L, s.kv_heads, g, s.head_dim)
    k = rope(k, pos, s.rope_theta)
    scale = 1.0 / np.sqrt(s.head_dim)

    def block(i):
        qb = jax.lax.dynamic_slice_in_dim(q, i * BLOCK_Q, BLOCK_Q)
        qpos = i * BLOCK_Q + jnp.arange(BLOCK_Q)
        sc = jnp.einsum("qkgh,skh->kgqs", qb, k) * scale
        mask = pos[None, :] <= qpos[:, None]
        sc = jnp.where(mask[None, None], sc, -jnp.inf)
        p = jax.nn.softmax(sc, axis=-1)
        return jnp.einsum("kgqs,skh->qkgh", p, v)

    o = jax.lax.map(block, jnp.arange(L // BLOCK_Q))
    o = o.reshape(L, s.heads * s.head_dim)
    return _mm(o, lw["wo"], quant)


def route(s: Sizes, logits):
    """Top-k experts and their gates from router logits [L, N]."""
    if s.norm_topk:
        vals, ids = jax.lax.top_k(logits, s.top_k)
        return ids, jax.nn.softmax(vals, axis=-1)
    gates, ids = jax.lax.top_k(jax.nn.softmax(logits, axis=-1), s.top_k)
    return ids, gates


def _swiglu(x, up, down, quant):
    """silu(x @ up[:, 0]) * (x @ up[:, 1]) @ down; up [d, 2, f]."""
    d = up.shape[0]
    h = _mm(x, up.reshape(d, -1), quant)
    f = up.shape[-1]
    return _mm(jax.nn.silu(h[:, :f]) * h[:, f:], down, quant)


def moe(s: Sizes, lw, h, quant):
    """Routed experts (every expert over every row, weighted by its gate,
    0 where not chosen) plus the shared expert."""
    logits = _mm(h, lw["router"], quant)
    ids, gates = route(s, logits)
    comb = jnp.zeros((h.shape[0], s.experts), F32).at[
        jnp.arange(h.shape[0])[:, None], ids].add(gates)

    def one(acc, e):
        y = _swiglu(h, lw["w_up"][e], lw["w_down"][e], quant)
        return acc + comb[:, e][:, None] * y, None

    out, _ = jax.lax.scan(one, jnp.zeros_like(h), jnp.arange(s.experts))
    if s.f_shared:
        out = out + _swiglu(h, lw["shared_up"], lw["shared_down"], quant)
    return out


@functools.partial(jax.jit, static_argnums=(0, 3))
def _layer(s: Sizes, lw, x, quant):
    h = rms_norm(x, lw["norm1"], s.eps)
    x = x + attention(s, lw, h, quant)
    h = rms_norm(x, lw["norm2"], s.eps)
    return x + moe(s, lw, h, quant)


@functools.partial(jax.jit, static_argnums=(0,))
def _layer_weights(s: Sizes, root, layer):
    lw = {n: W.tensor(root, s, n, layer).astype(F32)
          for n in W.layer_names(s)}
    ids = jnp.arange(s.experts)
    for n in W.EXPERT:
        lw[n] = W.experts(root, s, n, layer, ids).astype(F32)
    return lw


@functools.partial(jax.jit, static_argnums=(0,))
def _embed(s: Sizes, root, tokens):
    return W.tensor(root, s, "embed")[tokens].astype(F32)


@functools.partial(jax.jit, static_argnums=(0,))
def _head(s: Sizes, root):
    return (W.tensor(root, s, "final_norm").astype(F32),
            W.tensor(root, s, "unembed").astype(F32))


@functools.partial(jax.jit, static_argnums=(0, 5))
def _score_block(s: Sizes, norm, unembed, x_ref, x_ctl, with_control,
                 tokens):
    """Per row: the reference's best logit, its logit of ``tokens`` and,
    with the control's rows, its logit of the control's argmax."""
    lr = rms_norm(x_ref, norm, s.eps) @ unembed
    best = lr.max(-1)
    served = jnp.take_along_axis(lr, tokens[:, None], 1)[:, 0]
    if not with_control:
        return best, served, served
    lc = _mm(rms_norm(x_ctl, norm, s.eps), unembed, True)
    pick = jnp.take_along_axis(lr, lc.argmax(-1)[:, None], 1)[:, 0]
    return best, served, pick


def pad_len(n: int) -> int:
    """Sequence length the reference computes at: whole query blocks."""
    return -(-n // BLOCK_Q) * BLOCK_Q


def served_gaps(s: Sizes, seed: int, seqs, pad_to: int, *,
                control: bool = False):
    """``seqs``: list of (context tokens, served tokens), where the
    context is what precedes the first served token; every sequence is
    padded to ``pad_to`` tokens (one compiled layer per cell).  Returns,
    per sequence, the reference's gap at each served token and (with
    ``control``) the control's gap at each of those positions."""
    root = W.root_key(seed)
    with jax.default_matmul_precision("highest"):
        xs, meta = [], []
        for ctx, out in seqs:
            toks = np.concatenate([ctx, out[:-1]]).astype(np.int32)
            n = len(toks)
            assert n <= pad_to and pad_to % BLOCK_Q == 0, (n, pad_to)
            padded = np.zeros(pad_to, np.int32)
            padded[:n] = toks
            xs.append(_embed(s, root, jnp.asarray(padded)))
            meta.append((n, len(ctx), np.asarray(out, np.int32)))
        ctl = list(xs) if control else None
        for li in range(s.layers):
            lw = _layer_weights(s, root, li)
            xs = [_layer(s, lw, x, False) for x in xs]
            if control:
                ctl = [_layer(s, lw, x, True) for x in ctl]
            del lw
        norm, unembed = _head(s, root)
        result = []
        for i, (n, n_ctx, out) in enumerate(meta):
            rows = np.arange(n_ctx - 1, n)          # predict out[j]
            gap, cgap = [], []
            for b in range(0, len(rows), BLOCK_V):
                r = rows[b:b + BLOCK_V]
                pad = np.full(BLOCK_V, r[-1])
                pad[:len(r)] = r
                tok = np.zeros(BLOCK_V, np.int32)
                tok[:len(r)] = out[b:b + len(r)]
                best, served, pick = _score_block(
                    s, norm, unembed, xs[i][pad],
                    ctl[i][pad] if control else xs[i][pad], control,
                    jnp.asarray(tok))
                best, served, pick = (np.asarray(a)[:len(r)]
                                      for a in (best, served, pick))
                gap.append(best - served)
                cgap.append(best - pick)
            result.append({"gap": np.concatenate(gap),
                           "control_gap": np.concatenate(cgap)
                           if control else None})
    return result


def logits(s: Sizes, seed: int, tokens, *, control: bool = False):
    """The reference's (or the control's) logits [n, vocab] at every
    position of one sequence (small sizes: the tests)."""
    root = W.root_key(seed)
    tokens = np.asarray(tokens, np.int32)
    n = len(tokens)
    padded = np.zeros(pad_len(n), np.int32)
    padded[:n] = tokens
    with jax.default_matmul_precision("highest"):
        x = _embed(s, root, jnp.asarray(padded))
        for li in range(s.layers):
            x = _layer(s, _layer_weights(s, root, li), x, control)
        norm, unembed = _head(s, root)
        return np.asarray(_mm(rms_norm(x[:n], norm, s.eps), unembed,
                              control))
