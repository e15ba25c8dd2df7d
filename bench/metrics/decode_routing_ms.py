"""MoE layer: device time under ``router``, ``route``, ``dispatch`` and
``combine`` within ``serve_step`` (gating and top-k, METRO's slot
choice, the pair buffer and token gather, the gate scale and
scatter-add: everything the layer does besides the experts) per decode
step, in ms, in the scoped window (``bench/scopes.py``)."""
from bench import scopes


def read(run):
    return scopes.window_metrics(run).get("decode_routing_ms")
