"""Serving launcher: run the continuous-batching engine on a synthetic
request stream.

  PYTHONPATH=src python -m repro.launch.serve --arch mixtral-8x22b \
      --reduced --requests 8 --algo metro

:func:`build_engine` is the one serving setup; ``chip_smoke.py`` at the
repository root builds its engines through it too.
"""
import argparse
import os
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config
from repro.configs.base import ModelConfig
from repro.core import build_placement, slots_for_ratio
from repro.models import init_lm
from repro.serving import EngineConfig, ServingEngine
from repro.sharding.policy import make_dist

# a fixed, git-ignored path inside the checkout, so the next run of the
# same checkout finds what this one compiled
COMPILE_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache():
    """Turn on JAX's persistent compile cache for an entry point.  JAX
    reads ``JAX_COMPILATION_CACHE_DIR`` itself when it is set; only
    otherwise is the cache put at :data:`COMPILE_CACHE_DIR`."""
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          str(COMPILE_CACHE_DIR))


def build_engine(cfg: ModelConfig, ecfg: EngineConfig, *, ep: int = 4,
                 seed: int = 0) -> ServingEngine:
    """A serving engine over a virtual EP group of ``ep`` ranks holding
    ``ecfg.replication_ratio`` x the experts in replica slots, EPLB's
    initial placement, and weights drawn from ``seed`` straight into
    the bf16 compute dtype (block by block: no fp32 whole-model copy)."""
    spd = (slots_for_ratio(cfg.num_experts, ep, ecfg.replication_ratio)
           if cfg.is_moe else 1)
    dist = make_dist(None, ep_size=ep, slots_per_device=spd)
    placement = (build_placement(cfg.num_experts, ep, spd)
                 if cfg.is_moe else None)
    params = init_lm(cfg, jax.random.PRNGKey(seed), dist,
                     replica_expert=placement.replica_expert
                     if placement else None, dtype=jnp.bfloat16)
    return ServingEngine(cfg, dist, params, ecfg)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--algo", default="metro",
                    choices=["metro", "eplb", "single"])
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--max-batch", type=int, default=8)
    ap.add_argument("--max-len", type=int, default=128)
    ap.add_argument("--replication", type=float, default=1.25)
    ap.add_argument("--rebalance-every", type=int, default=64)
    ap.add_argument("--ep", type=int, default=4)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    use_compile_cache()
    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    eng = build_engine(cfg, EngineConfig(
        max_batch=args.max_batch, max_len=args.max_len,
        decode_algo=args.algo, rebalance_every=args.rebalance_every,
        replication_ratio=args.replication), ep=args.ep, seed=args.seed)
    rng = np.random.default_rng(args.seed)
    for _ in range(args.requests):
        n = int(rng.integers(4, min(32, args.max_len // 2)))
        eng.submit(rng.integers(0, cfg.vocab_size, n), args.gen)
    summary = eng.run()
    for k, v in summary.items():
        print(f"{k}: {v}")


if __name__ == "__main__":
    main()
