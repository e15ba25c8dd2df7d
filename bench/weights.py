"""Seeded weights: each tensor of the model is a pure function of the
run's seed, its name, its layer and (for an expert) the expert's id.

The served weights are these draws rounded to bfloat16, and the
reference reads the very same bfloat16 values in float32.  Both sides
draw them through :func:`draw`; a replica slot draws with its expert's
id, so replicas are identical and the reference, which routes to
experts, needs no slot table.  ``jax.random`` is vmap-consistent, so a
stack drawn over many experts or layers at once holds the same values
as each drawn alone.
"""
from __future__ import annotations

import zlib

import jax
import jax.numpy as jnp
import numpy as np

from bench.sizes import Sizes

SERVED_DTYPE = jnp.bfloat16


def root_key(seed: int):
    """A key from a seed of any size up to 64 bits."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.key(seed & 0xFFFFFFFF),
                              (seed >> 32) & 0xFFFFFFFF)


def _leaf_id(name: str) -> int:
    return zlib.crc32(name.encode()) & 0x7FFFFFFF


def draw(root, name: str, layer, expert, shape, std: float):
    """One tensor in the served dtype.  ``layer`` and ``expert`` may be
    traced scalars (vmapped over); ``expert`` is 0 for non-expert
    tensors."""
    k = jax.random.fold_in(root, _leaf_id(name))
    k = jax.random.fold_in(jax.random.fold_in(k, layer), expert)
    return (jax.random.normal(k, shape, jnp.float32) * std).astype(
        SERVED_DTYPE)


def stds(s: Sizes) -> dict:
    """Scale of each tensor: unit-variance activations through every
    projection; norm scales are offsets from 1."""
    return {
        "embed": 0.02, "unembed": 1 / np.sqrt(s.d), "final_norm": 0.1,
        "norm1": 0.1, "norm2": 0.1, "q_norm": 0.1, "k_norm": 0.1,
        "wq": 1 / np.sqrt(s.d), "wk": 1 / np.sqrt(s.d),
        "wv": 1 / np.sqrt(s.d), "wo": 1 / np.sqrt(s.heads * s.head_dim),
        "router": 1 / np.sqrt(s.d),
        "w_up": 1 / np.sqrt(s.d), "w_down": 1 / np.sqrt(s.fe),
        "shared_up": 1 / np.sqrt(s.d),
        "shared_down": 1 / np.sqrt(max(s.f_shared, 1)),
    }


def shapes(s: Sizes) -> dict:
    """Shape of each tensor (an expert tensor: of one expert)."""
    q, kv = s.heads * s.head_dim, s.kv_heads * s.head_dim
    out = {
        "embed": (s.vocab, s.d), "unembed": (s.d, s.vocab),
        "final_norm": (s.d,), "norm1": (s.d,), "norm2": (s.d,),
        "wq": (s.d, q), "wk": (s.d, kv), "wv": (s.d, kv), "wo": (q, s.d),
        "router": (s.d, s.experts),
        "w_up": (s.d, 2, s.fe), "w_down": (s.fe, s.d),
    }
    if s.qk_norm:
        out["q_norm"] = (s.head_dim,)
        out["k_norm"] = (s.head_dim,)
    if s.f_shared:
        out["shared_up"] = (s.d, 2, s.f_shared)
        out["shared_down"] = (s.f_shared, s.d)
    return out


GLOBAL = ("embed", "unembed", "final_norm")
EXPERT = ("w_up", "w_down")


def tensor(root, s: Sizes, name: str, layer=0):
    """A non-expert tensor (global ones take layer 0)."""
    return draw(root, name, layer, 0, shapes(s)[name], stds(s)[name])


def experts(root, s: Sizes, name: str, layer, expert_ids):
    """The stack [len(expert_ids), ...] of one expert tensor."""
    return jax.vmap(lambda e: draw(root, name, layer, e, shapes(s)[name],
                                   stds(s)[name]))(expert_ids)


def layer_names(s: Sizes) -> list[str]:
    return [n for n in shapes(s) if n not in GLOBAL and n not in EXPERT]
