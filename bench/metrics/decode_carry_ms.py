"""Model step: the self time of ``serve_step/.../layers`` (the layer
scan's own per-layer slicing and stacking of weights, caches and
outputs, under no layer of the block) per decode step, in ms, in the
scoped window (``bench/scopes.py``)."""
from bench import scopes


def read(run):
    return scopes.window_metrics(run).get("decode_carry_ms")
