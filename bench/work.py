"""The work an operation needs, counted from shapes, and the chip's
peaks.  These are the numerators of the roofline shares and of
``step_mfu``: what the algorithm requires, whatever implements it.

All matrices are served in bf16 (2 bytes an element).
"""
from __future__ import annotations

import json
import os

from bench.sizes import Sizes

BYTES = 2
PEAKS_FILE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          "peaks.json")


def peaks(device_kind: str) -> dict:
    """The peaks of a device kind, from ``peaks.json``; an unknown kind
    is an error, never a default."""
    with open(PEAKS_FILE) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; "
                       f"peaks.json has {sorted(table)}")
    return table[device_kind]


def min_seconds(flops: float, nbytes: float, pk: dict) -> float:
    """The least time the chip could take: the larger of the compute
    bound and the memory bound."""
    return max(flops / pk["bf16_flops_per_s"],
               nbytes / pk["hbm_bytes_per_s"])


def expert_ffn(s: Sizes, slot_hist) -> tuple[float, float]:
    """One MoE layer's routed-expert FFN over the (token, expert) pairs
    counted per replica slot in ``slot_hist``: (FLOPs, bytes).  Each
    pair costs up, gate and down projections, 6 * d * fe FLOPs; each
    slot with at least one pair reads its three matrices once; each
    pair reads its token row and writes its output row."""
    pairs = float(sum(slot_hist))
    active = float(sum(1 for c in slot_hist if c > 0))
    flops = 6.0 * s.d * s.fe * pairs
    nbytes = active * 3 * s.d * s.fe * BYTES + pairs * 2 * s.d * BYTES
    return flops, nbytes


def flash_decode(s: Sizes, positions) -> tuple[float, float]:
    """One layer's decode attention for live rows writing at
    ``positions``: each row reads the K and V of its ``p + 1`` context
    tokens, and its query and output.  (FLOPs, bytes)."""
    ctx = float(sum(p + 1 for p in positions))
    rows = len(positions)
    flops = 4.0 * s.heads * s.head_dim * ctx
    nbytes = (ctx * s.kv_bytes_per_token_layer
              + rows * 2 * s.heads * s.head_dim * BYTES)
    return flops, nbytes


def dense_flops_per_token(s: Sizes) -> float:
    """Matmul FLOPs of one token through one layer, outside attention's
    scores: the projections, the router, the token's top-k experts and
    the shared expert."""
    q, kv = s.heads * s.head_dim, s.kv_heads * s.head_dim
    params = (s.d * (q + 2 * kv) + q * s.d + s.d * s.experts
              + s.top_k * 3 * s.d * s.fe + 3 * s.d * s.f_shared)
    return 2.0 * params


def step_flops(s: Sizes, prefill, decode_positions) -> float:
    """Model FLOPs of one engine step: ``prefill`` is [(start, n)] per
    chunk row, ``decode_positions`` the position each live decode row
    writes.  Attention scores count each token's causal context; the LM
    head counts only rows whose logits are used (decode rows)."""
    per_tok = dense_flops_per_token(s)
    attn = 4.0 * s.heads * s.head_dim
    tokens = ctx = 0.0
    for start, n in prefill:
        tokens += n
        ctx += n * start + n * (n + 1) / 2      # sum of (p + 1)
    for p in decode_positions:
        tokens += 1
        ctx += p + 1
    head = 2.0 * s.d * s.vocab * len(decode_positions)
    return s.layers * (tokens * per_tok + attn * ctx) + head
