"""Attention: device time under ``serve_step/.../attention`` (the norm,
the projections, the KV cache write and the attention over the
contiguous cache) per decode step, in ms, in the scoped window
(``bench/scopes.py``)."""
from bench import scopes


def read(run):
    return scopes.window_metrics(run).get("decode_attention_ms")
