"""A model's sizes, read from the ``model`` and ``architecture`` groups
of a configuration file (``configs/<config>.json``).

The ``model`` group holds the published ``config.json`` keys, with the
depth as run; ``architecture`` states the structural facts the run
uses (query/key norms, q/k/v biases, a shared-expert gate).
"""
from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class Sizes:
    layers: int
    d: int              # hidden size
    vocab: int
    heads: int
    kv_heads: int
    head_dim: int
    experts: int
    top_k: int
    fe: int             # routed expert width
    f_shared: int       # shared expert width (0: none)
    norm_topk: bool
    qk_norm: bool
    rope_theta: float
    eps: float

    @property
    def kv_bytes_per_token_layer(self) -> int:
        """bf16 K and V of one token in one layer."""
        return 2 * self.kv_heads * self.head_dim * 2


def sizes(cfg: dict) -> Sizes:
    m, a = cfg["model"], cfg["architecture"]
    heads = m["num_attention_heads"]
    return Sizes(
        layers=m["num_hidden_layers"], d=m["hidden_size"],
        vocab=m["vocab_size"], heads=heads,
        kv_heads=m["num_key_value_heads"],
        head_dim=m.get("head_dim") or m["hidden_size"] // heads,
        experts=m["num_experts"], top_k=m["num_experts_per_tok"],
        fe=m["moe_intermediate_size"],
        f_shared=m.get("shared_expert_intermediate_size", 0),
        norm_topk=bool(m["norm_topk_prob"]), qk_norm=bool(a["qk_norm"]),
        rope_theta=float(m["rope_theta"]), eps=float(m["rms_norm_eps"]))


def reduced(cfg: dict) -> dict:
    """The same configuration at a size a CPU test can hold: every
    structural choice kept, widths cut (tests only; never a cell)."""
    m = dict(cfg["model"])
    shared = m.get("shared_expert_intermediate_size", 0)
    mha = m["num_key_value_heads"] == m["num_attention_heads"]
    m.update(hidden_size=64, num_attention_heads=4,
             num_key_value_heads=4 if mha else 2,
             head_dim=16, moe_intermediate_size=32, vocab_size=256,
             num_experts=8, num_experts_per_tok=2, num_hidden_layers=2,
             intermediate_size=128)
    if shared:
        m["shared_expert_intermediate_size"] = 64
    return dict(cfg, model=m)
