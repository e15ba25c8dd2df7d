"""The Qwen3-MoE block: GQA attention with query/key norms, softmax
top-k routing over fine-grained experts, and (as Qwen2-MoE has) an
optional shared expert; every layer one attention and one MoE layer.

The family's sizes (``bench/sizes.py``), seeded tensors
(``bench/weights.py``) and float32 reference (``bench/reference.py``)
are bound here; the program's model and step configuration, its
weights and the family's work counts are written here.  What a module
gives is set out in ``bench/arch/__init__.py``.  One chip computes the
whole EP group (virtual EP): every replica slot is drawn.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from bench import weights as W
from bench.reference import logits, pad_len, served_gaps  # noqa: F401
from bench.sizes import Sizes, reduced, sizes  # noqa: F401
from bench.work import BYTES
from repro.configs.base import ModelConfig
from repro.core import build_placement, slots_for_ratio
from repro.sharding.policy import make_dist


def model_config(cfg: dict) -> ModelConfig:
    """The program's model: one layer kind (attention, then MoE), which
    the weights' layout of :func:`program_params` assumes."""
    m, s = cfg["model"], sizes(cfg)
    assert not s.f_shared or s.f_shared % s.fe == 0, \
        "the engine builds the shared expert as whole expert widths"
    mcfg = ModelConfig(
        name=cfg["name"], family="moe", num_layers=s.layers, d_model=s.d,
        num_heads=s.heads, num_kv_heads=s.kv_heads, head_dim=s.head_dim,
        d_ff=m["intermediate_size"], vocab_size=s.vocab,
        qk_norm=s.qk_norm, rope_theta=s.rope_theta,
        max_seq_len=m["max_position_embeddings"],
        num_experts=s.experts, num_experts_per_tok=s.top_k,
        num_shared_experts=s.f_shared // s.fe, d_ff_expert=s.fe,
        norm_topk_prob=s.norm_topk,
        tie_embeddings=bool(m["tie_word_embeddings"]),
        gated_mlp=m["hidden_act"] == "silu")
    assert mcfg.pattern_period == 1 and mcfg.layer_kinds() == [
        ("attn_full", "moe")], "the weights' layout is one MoE layer kind"
    return mcfg


def placement_for(cfg: dict):
    """EPLB's initial placement of the deployment's replica slots, and
    the slots per EP rank."""
    s, dep = sizes(cfg), cfg["deployment"]
    spd = slots_for_ratio(s.experts, dep["ep_size"],
                          dep["replication_ratio"])
    return build_placement(s.experts, dep["ep_size"], spd), spd


def step_config(cfg: dict):
    """The launch path's ``StepConfig`` for the configuration's
    deployment, and its placement.  The ``Dist`` is the whole EP group,
    computed on this chip."""
    from repro.launch.steps import StepConfig
    dep = cfg["deployment"]
    placement, spd = placement_for(cfg)
    dist = make_dist(None, ep_size=dep["ep_size"], slots_per_device=spd)
    sc = StepConfig(cfg=model_config(cfg), dist=dist,
                    algo_decode=dep["decode_algo"],
                    algo_train=dep["prefill_algo"],
                    moe_impl=cfg["engine"]["moe_impl"],
                    replication_ratio=dep["replication_ratio"])
    return sc, placement


def _program_params(root, s: Sizes, replica_expert):
    """The program's parameter tree, drawn on the device in one program:
    layers one after another (``lax.map``), each slot from its expert's
    key, every tensor in the served dtype."""
    ids = jnp.arange(s.layers)

    def layer(li):
        t = {n: W.tensor(root, s, n, li) for n in W.layer_names(s)}
        attn = {"wq": t["wq"], "wk": t["wk"], "wv": t["wv"], "wo": t["wo"]}
        if s.qk_norm:
            attn.update(q_norm=t["q_norm"], k_norm=t["k_norm"])
        moe = {"w_router": t["router"],
               "w_up": W.experts(root, s, "w_up", li, replica_expert),
               "w_down": W.experts(root, s, "w_down", li, replica_expert)}
        if s.f_shared:
            moe.update(shared_up=t["shared_up"],
                       shared_down=t["shared_down"])
        return {"norm1": {"scale": t["norm1"]}, "attn": attn,
                "norm2": {"scale": t["norm2"]}, "moe": moe}

    return {"embed": W.tensor(root, s, "embed"),
            "unembed": W.tensor(root, s, "unembed"),
            "final_norm": {"scale": W.tensor(root, s, "final_norm")},
            "blocks": {"l0": jax.lax.map(layer, ids)}}


_draw_params = jax.jit(_program_params, static_argnums=(1,))


def program_params(cfg: dict, seed: int, placement):
    """The program's weights from ``seed``: every replica slot of
    ``placement`` holds its expert's draw."""
    return _draw_params(W.root_key(seed), sizes(cfg),
                        jnp.asarray(placement.replica_expert))


# ----------------------------------------------------------------------
# work counts (bench/work.py keeps what every family shares)
# ----------------------------------------------------------------------


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
    """Model FLOPs of one step: ``prefill`` is [(start, n)] per chunk
    row, ``decode_positions`` the position each live decode row writes.
    Attention scores count each token's causal context; the LM head
    counts only rows whose logits are used (decode rows)."""
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
