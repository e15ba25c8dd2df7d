"""Pallas TPU kernels for the paper's compute hot spots.

  metro_route.py  — Alg. 1 greedy routing on the scalar core (SMEM
                    load counters; TPU analogue of the single-SM CUDA
                    kernel, §V)
  moe_ffn.py      — grouped expert FFN with activated-expert-only
                    weight-tile streaming (the memory-bound mechanism
                    METRO optimizes, §III-B): the two-pass
                    grouped_ffn_pallas and the one-pass
                    fused_expert_ffn_pallas megakernel (up→act→down,
                    hidden resident in VMEM, dead-tile DMA/FLOP skip)
  flash_decode.py — online-softmax decode attention over bf16/fp8 KV
                    caches (in-register dequant after the block DMA)

Every kernel takes ``interpret=None``: :func:`interpret_mode` resolves
it from the backend.  ref.py: pure-numpy oracles the tests sweep
against.  README.md here: impl matrix, VMEM sizing rule, dead-tile
contract.
"""
from __future__ import annotations

from typing import Optional

import jax


def interpret_mode(interpret: Optional[bool] = None) -> bool:
    """The one rule for Pallas execution: compiled on a TPU, the
    interpreter on any other backend.  An explicit bool overrides it
    (tests that pin one mode)."""
    if interpret is not None:
        return bool(interpret)
    return jax.default_backend() != "tpu"
