"""Token-routing algorithms (the paper's core contribution), in JAX.

All routers answer the same question: given the per-(token, k) top-k
expert choices for a batch, *which physical replica slot* serves each
(token, k) pair?  (This is "token routing" in the paper's sense — replica
selection, not top-k selection.)

  * :func:`route_metro`   — the paper's greedy algorithm (Alg. 1): per
    expert with T[i] > 0, activate the replica on the candidate device
    with the fewest activated experts.  Per Lemma 1, *all* tokens of an
    expert go to that single replica.  Implemented as a `lax.scan` over
    experts (the TPU-native analogue of the paper's single-SM CUDA
    kernel; see kernels/metro_route.py for the Pallas version).
  * :func:`route_eplb`    — the token-balancing baseline used by
    vLLM/SGLang EPLB: expert i's tokens are round-robined across its
    replicas so every replica gets an even share.
  * :func:`route_single`  — degenerate router for no-replication
    placements (slot 0 of each expert); also the "hypothetical ideal"
    lower bound of Fig. 4 when replication is 1.0x.

Everything here is shape-static and jit-friendly: placement tables are
device arrays (step inputs), token counts are data.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

_INT = jnp.int32
_BIG = jnp.iinfo(jnp.int32).max


def topk_histogram(expert_ids: jax.Array, num_experts: int) -> jax.Array:
    """T[1..N] of the paper: tokens per logical expert for this batch.

    ``expert_ids`` is any-shaped int array of top-k selections (pad with
    -1 for invalid entries)."""
    flat = expert_ids.reshape(-1)
    valid = flat >= 0
    return jnp.zeros(num_experts, _INT).at[
        jnp.where(valid, flat, 0)
    ].add(valid.astype(_INT))


def rank_within_expert(expert_ids: jax.Array) -> jax.Array:
    """Rank of each (token, k) pair among pairs that picked the same
    expert, in flat position order.  O(B log B) via stable sort; used by
    the EPLB round-robin router."""
    flat = expert_ids.reshape(-1)
    b = flat.shape[0]
    order = jnp.argsort(flat, stable=True)
    sorted_e = flat[order]
    seg_start = jnp.searchsorted(sorted_e, sorted_e, side="left")
    rank_sorted = jnp.arange(b, dtype=_INT) - seg_start.astype(_INT)
    rank = jnp.zeros(b, _INT).at[order].set(rank_sorted)
    return rank.reshape(expert_ids.shape)


@partial(jax.jit, static_argnames=("num_devices", "slots_per_device"))
def route_metro(
    token_counts: jax.Array,      # [N] int, T[1..N]
    expert_slots: jax.Array,      # [N, max_rep] int, -1 padded
    *,
    num_devices: int,
    slots_per_device: int,
) -> jax.Array:
    """METRO greedy (paper Alg. 1). Returns expert_slot[N]: the single
    replica slot activated for each expert (-1 if the expert has no
    tokens this batch).

    Experts are processed in descending token count order — the
    activated-expert objective (lambda) is order-invariant for the greedy,
    but heavy-first gives better *secondary* token balance among devices
    with equal activation counts, which we use as the tie-break exactly so
    the router degrades gracefully toward token balance when activation
    counts tie (beyond-paper refinement; the paper's lock ordering is
    arbitrary thread order).
    """
    n = token_counts.shape[0]
    order = jnp.argsort(-token_counts, stable=True)

    def step(carry, i):
        act_load, tok_load = carry                      # [G], [G]
        t_i = token_counts[i]
        slots = expert_slots[i]                          # [max_rep]
        valid = slots >= 0
        devs = jnp.where(valid, slots // slots_per_device, 0)
        # lexicographic argmin over (activated, tokens, device id),
        # masked to valid candidate replicas:
        act = jnp.where(valid, act_load[devs], _BIG)
        best_act = jnp.min(act)
        tie1 = act == best_act
        tok = jnp.where(tie1, tok_load[devs], _BIG)
        best_tok = jnp.min(tok)
        tie2 = tie1 & (tok == best_tok)
        dev_key = jnp.where(tie2, devs, _BIG)
        j = jnp.argmin(dev_key)
        slot = slots[j]
        dev = devs[j]
        take = t_i > 0
        act_load = act_load.at[dev].add(jnp.where(take, 1, 0))
        tok_load = tok_load.at[dev].add(jnp.where(take, t_i, 0))
        return (act_load, tok_load), jnp.where(take, slot, -1)

    init = (jnp.zeros(num_devices, _INT), jnp.zeros(num_devices, _INT))
    (_, _), picked = jax.lax.scan(step, init, order)
    # scatter back from processing order to expert index
    expert_slot = jnp.zeros(n, _INT).at[order].set(picked)
    return expert_slot


def metro_token_slots(
    expert_ids: jax.Array,        # [..., k] int, -1 pad
    expert_slot: jax.Array,       # [N] from route_metro
) -> jax.Array:
    """Per-(token, k) slot under METRO (Lemma 1: all tokens of an expert
    share its one activated replica)."""
    safe = jnp.maximum(expert_ids, 0)
    slots = expert_slot[safe]
    return jnp.where(expert_ids >= 0, slots, -1)


def route_eplb(
    expert_ids: jax.Array,        # [..., k] int, -1 pad
    expert_slots: jax.Array,      # [N, max_rep]
    expert_num_replicas: jax.Array,  # [N]
) -> jax.Array:
    """EPLB token-balanced baseline: round-robin each expert's tokens
    across its replicas (the vLLM/SGLang implementation the paper
    compares against).  Returns per-(token, k) slot ids."""
    ranks = rank_within_expert(expert_ids)
    safe = jnp.maximum(expert_ids, 0)
    n_rep = jnp.maximum(expert_num_replicas[safe], 1)
    j = ranks % n_rep
    slots = jnp.take_along_axis(
        expert_slots[safe], j[..., None].astype(_INT), axis=-1)[..., 0]
    return jnp.where(expert_ids >= 0, slots, -1)


def route_single(
    expert_ids: jax.Array,
    expert_slots: jax.Array,
) -> jax.Array:
    """Always use replica 0 — exact for 1.0x replication placements."""
    safe = jnp.maximum(expert_ids, 0)
    slots = expert_slots[safe, 0]
    return jnp.where(expert_ids >= 0, slots, -1)


def route(
    algo: str,
    expert_ids: jax.Array,
    token_counts: jax.Array,
    expert_slots: jax.Array,
    expert_num_replicas: jax.Array,
    *,
    num_devices: int,
    slots_per_device: int,
    use_pallas: bool = False,
) -> jax.Array:
    """Dispatch on routing algorithm name -> per-(token, k) slot ids."""
    if algo == "metro":
        if use_pallas:
            from repro.kernels.metro_route import metro_route_pallas
            expert_slot = metro_route_pallas(
                token_counts, expert_slots,
                num_devices=num_devices, slots_per_device=slots_per_device)
        else:
            expert_slot = route_metro(
                token_counts, expert_slots,
                num_devices=num_devices, slots_per_device=slots_per_device)
        return metro_token_slots(expert_ids, expert_slot)
    if algo == "eplb":
        return route_eplb(expert_ids, expert_slots, expert_num_replicas)
    if algo == "single":
        return route_single(expert_ids, expert_slots)
    raise ValueError(f"unknown routing algo: {algo!r}")
