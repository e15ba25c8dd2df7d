"""Step-function builders: train_step / prefill_step / serve_step with
full input/param/cache shardings for a given (arch x shape x mesh).

These are what the dry-run lowers and what launch/train.py and the
serving engine execute.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.configs.shapes import ShapeCell
from repro.core import build_placement, slots_for_ratio
from repro.models import lm as LM
from repro.models import scopes as SC
from repro.sharding.policy import Dist, param_pspecs
from repro.training.optimizer import AdamWConfig, adamw_init, adamw_update


@dataclasses.dataclass(frozen=True)
class StepConfig:
    """Everything needed to build and shard one step function."""
    cfg: ModelConfig
    dist: Dist
    algo_decode: str = "metro"      # the paper's technique (decode phase)
    algo_train: str = "eplb"        # token-balanced for compute-bound
    moe_impl: str = "ragged"
    remat: bool = True
    replication_ratio: float = 1.25
    opt: AdamWConfig = AdamWConfig(moment_dtype="bfloat16")
    attn_chunk: int = 1024
    long_context: bool = False      # shard KV sequence over data axes
    microbatches: int = 1           # grad-accumulation steps per train step
    fsdp: bool = True               # ZeRO-3-style param/opt sharding (train)
    remat_policy: str = "dots_no_batch"  # dots_no_batch | dots | nothing
    kv_dtype: str = "bfloat16"      # bfloat16 | float8_e4m3fn (fp8 KV cache)


def kv_needs_replication(cfg: ModelConfig, dist: Dist) -> bool:
    if not dist.mesh or cfg.num_heads <= 0:
        return False
    from repro.models.layers import attn_dims
    return attn_dims(cfg, dist.ep_size).kv % dist.ep_size != 0


def step_pspecs(sc: StepConfig, tree, *, fsdp=None, kv_rep=None):
    """kv replication removes per-layer K/V activation gathers in train;
    in decode it would instead ADD wk/wv weight re-reads on every chip
    (weight-streaming-bound), so it is train-only by default."""
    use_fsdp = sc.fsdp if fsdp is None else fsdp
    if kv_rep is None:
        kv_rep = use_fsdp and kv_needs_replication(sc.cfg, sc.dist)
    return param_pspecs(tree, sc.dist, fsdp=use_fsdp, kv_replicated=kv_rep)


def make_placement(sc: StepConfig):
    cfg, dist = sc.cfg, sc.dist
    if not cfg.is_moe:
        return None
    return build_placement(cfg.num_experts, dist.ep_size,
                           dist.slots_per_device)


def default_slots_per_device(cfg: ModelConfig, ep_size: int,
                             ratio: float) -> int:
    if not cfg.is_moe:
        return 1
    return slots_for_ratio(cfg.num_experts, ep_size, ratio)


# ----------------------------------------------------------------------
# sharding helpers
# ----------------------------------------------------------------------


def _ns(dist: Dist, spec: P):
    return NamedSharding(dist.mesh, spec) if dist.mesh else None


def batch_pspecs(cfg: ModelConfig, dist: Dist, batch_tree):
    """Shard the batch dim over (pod, data); fall back when indivisible."""
    def one(leaf):
        return dist.spec(leaf, dist.dp_axes,
                         *([None] * (len(leaf.shape) - 1)))
    return jax.tree.map(one, batch_tree)


def tree_named(dist: Dist, spec_tree):
    if dist.mesh is None:
        return None
    return jax.tree.map(lambda s: NamedSharding(dist.mesh, s), spec_tree,
                        is_leaf=lambda s: isinstance(s, P))


# ----------------------------------------------------------------------
# train step
# ----------------------------------------------------------------------


def make_train_step(sc: StepConfig):
    cfg, dist = sc.cfg, sc.dist
    m = sc.microbatches

    def loss_fn(p, batch, routing):
        return LM.lm_loss(cfg, dist, p, batch, routing=routing,
                          algo=sc.algo_train, moe_impl=sc.moe_impl,
                          remat=sc.remat, chunk=sc.attn_chunk,
                          remat_policy=sc.remat_policy)

    def train_step(params, opt_state, batch, routing):
        # differentiate w.r.t. the bf16 compute copy: every gradient
        # collective (per-microbatch reduce-scatters, DP all-reduces)
        # then moves bf16 instead of f32 — 2x less ICI traffic (perf
        # iteration, EXPERIMENTS.md §Perf). Accumulation stays f32.
        bf16_params = LM.cast_params(params)
        if dist.mesh is not None:
            # pin the bf16 copy to the param sharding so XLA gathers
            # (fwd) and reduce-scatters (bwd) in bf16, not on the f32
            # master at the use site
            bf16_params = jax.lax.with_sharding_constraint(
                bf16_params, tree_named(dist, step_pspecs(sc, params)))

        if m == 1:
            (loss, stats), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(bf16_params, batch, routing)
        else:
            # gradient accumulation over microbatches: activations for
            # only one microbatch are live at a time
            mb = jax.tree.map(
                lambda a: a.reshape((m, a.shape[0] // m) + a.shape[1:]),
                batch)

            def acc(carry, mbatch):
                g_acc, l_acc = carry
                (l, st), g = jax.value_and_grad(
                    loss_fn, has_aux=True)(bf16_params, mbatch, routing)
                g_acc = jax.tree.map(
                    lambda a, b: a + b.astype(jnp.float32), g_acc, g)
                return (g_acc, l_acc + l), st

            g0 = jax.tree.map(
                lambda a: jnp.zeros(a.shape, jnp.float32), params)
            (grads, loss), stats_all = jax.lax.scan(
                acc, (g0, jnp.zeros((), jnp.float32)), mb)
            grads = jax.tree.map(lambda g: g / m, grads)
            loss = loss / m
            stats = jax.tree.map(lambda s: jnp.mean(s), stats_all)
        # grads are averaged over the batch via the loss mean; pjit
        # inserts the cross-replica psum automatically from the
        # sharding constraints.
        new_params, new_opt, metrics = adamw_update(
            sc.opt, grads, opt_state, params)
        return new_params, new_opt, loss, dict(stats, **metrics)

    return train_step


def train_shardings(sc: StepConfig, params_shape, opt_shape, batch_specs):
    dist = sc.dist
    pspec = step_pspecs(sc, params_shape)
    ospec = {"mu": step_pspecs(sc, opt_shape["mu"]),
             "nu": step_pspecs(sc, opt_shape["nu"]),
             "step": P()}
    in_shardings = (tree_named(dist, pspec), tree_named(dist, ospec),
                    tree_named(dist, batch_specs), None)
    out_shardings = (tree_named(dist, pspec), tree_named(dist, ospec),
                     None, None)
    return in_shardings, out_shardings


# ----------------------------------------------------------------------
# serve (decode) + prefill steps
# ----------------------------------------------------------------------


def make_serve_step(sc: StepConfig, *, greedy: bool = True):
    cfg, dist = sc.cfg, sc.dist

    @jax.named_scope(SC.SERVE_STEP)
    def serve_step(params, tokens, pos, cache, routing):
        logits, new_cache, stats = LM.apply_lm(
            cfg, dist, params, tokens=tokens, pos=pos, cache=cache,
            routing=routing, mode="decode", algo=sc.algo_decode,
            moe_impl=sc.moe_impl)
        with jax.named_scope(SC.HEAD):
            next_tok = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
        return next_tok, new_cache, stats

    return serve_step


def make_prefill_step(sc: StepConfig):
    cfg, dist = sc.cfg, sc.dist

    @jax.named_scope(SC.PREFILL_STEP)
    def prefill_step(params, batch, cache, routing):
        logits, new_cache, stats = LM.apply_lm(
            cfg, dist, params, tokens=batch.get("tokens"),
            embeds=batch.get("embeds"), frames=batch.get("frames"),
            cache=cache, routing=routing, mode="prefill",
            algo=sc.algo_train, moe_impl=sc.moe_impl, chunk=sc.attn_chunk)
        with jax.named_scope(SC.HEAD):
            next_tok = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
        return next_tok, new_cache, stats

    return prefill_step


def serve_shardings(sc: StepConfig, params_shape, cache_specs_tree,
                    batch_size: int):
    dist = sc.dist
    pspec = step_pspecs(sc, params_shape, fsdp=False)
    tok_spec = P(dist.dp_axes) if (
        dist.mesh and batch_size % dist.dp_size == 0) else P()
    in_shardings = (
        tree_named(dist, pspec),
        _ns(dist, P(*tok_spec, None)),     # tokens [B, 1]
        _ns(dist, tok_spec),               # pos [B]
        tree_named(dist, cache_specs_tree),
        None,                              # routing tables (replicated)
    )
    out_shardings = (_ns(dist, tok_spec),
                     tree_named(dist, cache_specs_tree), None)
    return in_shardings, out_shardings


def serve_cache_pspecs(cfg: ModelConfig, dist: Dist,
                       long_context: bool = False):
    if cfg.family == "encdec":
        ax = dist.tp_axis
        s = P(None, dist.dp_axes, ax, None, None)
        return {"self_k": s, "self_v": s, "cross_k": s, "cross_v": s}
    return LM.cache_pspec(cfg, dist, long_context)


def sanitize_specs(spec_tree, shape_tree, dist: Dist):
    """Per-dim divisibility fallback for a PartitionSpec pytree against
    the matching ShapeDtypeStruct pytree (e.g. whisper's 8 KV heads on a
    16-way model axis fall back to replication)."""
    import numpy as np

    def ok(dim, axes):
        if axes is None or dist.mesh is None:
            return False
        if isinstance(axes, str):
            axes = (axes,)
        return dim % int(np.prod([dist.mesh.shape[a] for a in axes])) == 0

    def one(spec, aval):
        fixed = tuple(a if ok(d, a) else None
                      for d, a in zip(aval.shape, tuple(spec)))
        return P(*fixed)

    return jax.tree.map(one, spec_tree, shape_tree,
                        is_leaf=lambda s: isinstance(s, P))
