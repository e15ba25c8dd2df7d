"""The work an operation needs, counted from shapes, and the chip's
peaks.  These are the numerators of the roofline shares and of
``step_mfu``: what the algorithm requires, whatever implements it.

What every family shares is counted here: the routed experts' FFN,
from the ``d`` and ``fe`` of a family's sizes.  A family's own counts
(a step's model FLOPs, its decode attention) are in its architecture
module, ``bench/arch/<model_type>.py``.  All matrices are served in
bf16 (2 bytes an element).
"""
from __future__ import annotations

import json
import os

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


def expert_ffn(s, slot_hist) -> tuple[float, float]:
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
