"""Kernels: device time under ``serve_step/.../experts`` (the expert
weights' operand layout and the fused expert FFN kernel) per decode
step, in ms, in the scoped window (``bench/scopes.py``)."""
from bench import scopes


def read(run):
    return scopes.window_metrics(run).get("decode_experts_ms")
