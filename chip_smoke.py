"""Smoke test of the serving path on a TPU, at qwen3-30b-a3b's published
widths.

  python chip_smoke.py               # one chip: phases (a) and (b)
  python chip_smoke.py --four-chips  # four chips: the EP=4 MoE layer only

One chip serves ``qwen3-30b-a3b`` (the paper's real-system model) with
every published width — d_model 2048, 32/4 heads of 128, 128 experts,
top-8, expert hidden 768, vocabulary 151936 — and only the depth cut to
:data:`LAYERS`, so that bf16 weights for a 4-rank EP group at 1.25x
replication (160 expert slots, about 7.4 GB), the KV pool, the step
temporaries and a rebalance fit the chip's 16 GB.  Weights are random,
drawn from a seed.

  (a) serve the requests with METRO, then with EPLB, each through
      ``ServingEngine`` with rebalances firing; every request must
      complete with in-vocabulary tokens.
  (b) serve them again on the Pallas datapath (fused expert kernel,
      paged flash decode, METRO routing kernel); the step must contain
      compiled kernels (``tpu_custom_call``), and one decode step's
      logits must agree with the XLA datapath's.

``--four-chips`` runs one full-width MoE layer expert-parallel over a
(1, 4) mesh, METRO and EPLB, in decode and prefill modes, against the
mesh-less virtual-EP layer on the same weights and tables.

The script runs in one process and exits nonzero, printing no result,
when JAX finds no TPU.  Its last line of output is one JSON object
naming the device.
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs import get_config  # noqa: E402
from repro.launch.serve import build_engine, use_compile_cache  # noqa: E402
from repro.models import lm as LM  # noqa: E402
from repro.serving import EngineConfig  # noqa: E402

ARCH = "qwen3-30b-a3b"
LAYERS = 4                  # of 48: the only cut (see module docstring)
N_REQUESTS = 8
PROMPT_LEN = (128, 512)     # uniform prompt lengths, tokens
GEN = 32                    # tokens generated per request

# The Pallas datapath against the XLA one, relative L2 over one decode
# step's logits.  Both compute in bf16 (8-bit mantissa, unit roundoff
# 2^-9 ~ 2e-3) but round in different places: the fused kernel keeps
# the expert hidden in fp32 VMEM, flash decode keeps probabilities in
# fp32 where the reference rounds them to bf16.  A few such roundings
# per layer over 4 layers give ~1e-2.  A near-tie in a router's top-k
# can also pick a different expert for one token in one layer; that
# moves the token's logits by ~0.1 relative, ~0.035 of the batch norm
# for 8 rows.  0.1 admits two such flips; a wrong kernel (wrong page,
# expert or tile) is O(1).
LOGIT_RTOL = 0.1
# The EP=4 layer against virtual EP, relative L2 over the layer output.
# Routing is computed redundantly from the same gathered tokens, so it
# is identical; only the combine differs: each rank's partial sum is
# rounded to bf16 before the cross-device reduction (prefill), or the
# f32 partials are summed in another order (decode).  Four bf16-rounded
# partials give <= ~4e-3; 1e-2 leaves margin.
EP_RTOL = 1e-2
# the Pallas kernels the kernel datapath's decode step must hold, as
# compiled custom calls (interpret mode would leave none of them)
KERNELS = ("fused_expert_ffn_pallas", "flash_decode_paged",
           "metro_route_pallas")


def compiled_kernels(hlo_text: str) -> set:
    """Which of :data:`KERNELS` a compiled program's HLO text holds as
    ``tpu_custom_call`` instructions.  (XLA's own ragged dot is a
    ``tpu_custom_call`` too, so the bare target name proves nothing.)"""
    return {k for line in hlo_text.splitlines()
            if "tpu_custom_call" in line for k in KERNELS
            if f"%{k}" in line.split("=", 1)[0]}


def smoke_config():
    """qwen3-30b-a3b at published widths, depth cut to LAYERS."""
    return dataclasses.replace(get_config(ARCH), num_layers=LAYERS)


def smoke_engine_config(max_prompt: int, gen: int) -> EngineConfig:
    """One prefill chunk covers the longest prompt, so all requests
    prefill in the first (mixed) step and decode together after it:
    two step shapes per engine.  Rebalances fire every 8 decode
    steps."""
    page = 16
    max_len = -(-(max_prompt + gen + 1) // page) * page
    return EngineConfig(max_batch=N_REQUESTS, max_len=max_len,
                        page_size=page, prefill_chunk=max_prompt,
                        rebalance_every=8)


def make_prompts(cfg, n: int, lo: int, hi: int, seed: int = 0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, cfg.vocab_size, int(rng.integers(lo, hi + 1)))
            for _ in range(n)]


def _count_rebalances(eng):
    box = {"n": 0}
    inner = eng.rebalance

    def counted(*a, **k):
        box["n"] += 1
        return inner(*a, **k)
    eng.rebalance = counted
    return box


def _check_served(cfg, eng, prompts, gen: int, summary: dict, rebal: dict,
                  label: str, t0: float):
    done = eng.completed
    assert len(done) == len(prompts) and not eng.has_work, \
        f"{label}: {len(done)} of {len(prompts)} requests completed"
    toks = np.concatenate([np.asarray(r.generated) for r in done.values()])
    assert all(len(r.generated) == gen for r in done.values()), label
    assert toks.min() >= 0 and toks.max() < cfg.vocab_size, label
    assert rebal["n"] >= 1, f"{label}: no rebalance fired"
    compiles = {k: summary[k] for k in ("mixed_compiles", "chunk_compiles",
                                        "decode_compiles")}
    print(f"{label}: completed {len(done)}/{len(prompts)} requests, "
          f"{toks.size} tokens, prompts {sum(len(p) for p in prompts)} "
          f"tokens, rebalances {rebal['n']}, step compiles {compiles}, "
          f"wall {time.perf_counter() - t0:.1f} s, peak device bytes so "
          f"far {_peak_bytes(jax.devices()[0])}", flush=True)


def _build(cfg, ecfg: EngineConfig, label: str):
    """Build an engine and report its weights and the device's peak
    bytes once they are in place (the set-up's share of the peak)."""
    t0 = time.perf_counter()
    eng = build_engine(cfg, ecfg)
    jax.block_until_ready(eng.params)
    weights = sum(a.nbytes for a in jax.tree.leaves(eng.params))
    print(f"{label}: engine built in {time.perf_counter() - t0:.1f} s, "
          f"weights {weights} bytes, peak device bytes so far "
          f"{_peak_bytes(jax.devices()[0])}", flush=True)
    return eng


def serve_phase(cfg, ecfg: EngineConfig, prompts, gen: int, label: str):
    """Phase (a): serve ``prompts`` on one engine; every request must
    complete and a rebalance must fire."""
    t0 = time.perf_counter()
    eng = _build(cfg, ecfg, label)
    rebal = _count_rebalances(eng)
    for p in prompts:
        eng.submit(p, gen)
    summary = eng.run()
    _check_served(cfg, eng, prompts, gen, summary, rebal, label, t0)


def _decode_logits_fn(cfg, dist, ecfg: EngineConfig):
    """One decode step's last-position logits on ``ecfg``'s datapath —
    the engine's decode step, returning logits instead of tokens."""
    @jax.jit
    def step(params, tokens, pos, slot_idx, page_table, cache, routing):
        logits, _, _ = LM.apply_lm(
            cfg, dist, params, tokens=tokens, pos=pos, cache=cache,
            routing=routing, mode="decode", algo=ecfg.decode_algo,
            moe_impl=ecfg.moe_impl, use_pallas_route=ecfg.use_pallas_route,
            slot_idx=slot_idx, page_table=page_table,
            row_valid=slot_idx < ecfg.max_batch,
            use_flash_kernel=ecfg.use_flash_kernel)
        return logits[:, -1].astype(jnp.float32)
    return step


def kernel_phase(cfg, ecfg: EngineConfig, prompts, gen: int) -> float:
    """Phase (b): serve on the Pallas datapath.  Once every request is
    decoding, one decode step's logits are compared with the XLA
    datapath's on the same weights and cache.  On a TPU the step must
    hold compiled kernels.  Returns the logits' relative L2 error."""
    t0 = time.perf_counter()
    kcfg = dataclasses.replace(ecfg, moe_impl="fused",
                               use_flash_kernel=True, use_pallas_route=True)
    eng = _build(cfg, kcfg, "pallas datapath")
    rebal = _count_rebalances(eng)
    for p in prompts:
        eng.submit(p, gen)
    while eng.queue or any(r.prefilling for r in eng.active.values()):
        eng.step()
    drows = sorted(eng.active.values(), key=lambda r: r.slot)
    bucket = 1 << max(0, (len(drows) - 1).bit_length())
    inputs = eng.exec.decode_inputs(drows, bucket, eng.kvman)
    args = (eng.params, *inputs, eng.cache, eng.routing)
    kernel = _decode_logits_fn(cfg, eng.dist, kcfg).lower(*args).compile()
    xla = _decode_logits_fn(cfg, eng.dist, ecfg).lower(*args).compile()
    if jax.default_backend() == "tpu":
        step_text = eng.exec.decode_fn(bucket).lower(*args).as_text()
        assert step_text.count("tpu_custom_call") >= len(KERNELS), \
            "engine decode step holds no compiled Pallas kernel"
        found = compiled_kernels(kernel.as_text())
        assert found == set(KERNELS), f"compiled kernels: {found}"
        print(f"compiled Pallas kernels in the decode step: "
              f"{sorted(found)}", flush=True)
    live = np.asarray(inputs[2]) < ecfg.max_batch
    got = np.asarray(kernel(*args))[live]
    want = np.asarray(xla(*args))[live]
    assert np.isfinite(got).all() and np.isfinite(want).all()
    rel = float(np.linalg.norm(got - want) / np.linalg.norm(want))
    agree = int((got.argmax(-1) == want.argmax(-1)).sum())
    print(f"pallas datapath: decode logits [{got.shape[0]}, "
          f"{got.shape[1]}] vs XLA datapath: relative L2 {rel:.3e} "
          f"(limit {LOGIT_RTOL}), argmax agrees on {agree}/{len(got)} "
          f"rows", flush=True)
    assert rel <= LOGIT_RTOL, f"logits differ: {rel} > {LOGIT_RTOL}"
    summary = eng.run()
    _check_served(cfg, eng, prompts, gen, summary, rebal,
                  "pallas datapath (fused + flash decode + routing kernel)",
                  t0)
    return rel


def ep_layer_phase(cfg, devices, seed: int = 0):
    """One MoE layer expert-parallel over a (1, 4) ("data", "model")
    mesh against the mesh-less virtual-EP layer: same bf16 weights,
    same routing tables, METRO and EPLB, decode (features) and prefill
    (tokens) modes.  Returns {(algo, mode): relative L2 error}."""
    from jax.sharding import Mesh

    from repro.core import build_placement, slots_for_ratio
    from repro.models import moe as MOE
    from repro.sharding.policy import make_dist, named_pspecs, param_pspecs

    ep = 4
    mesh = Mesh(np.asarray(devices[:ep]).reshape(1, ep), ("data", "model"))
    spd = slots_for_ratio(cfg.num_experts, ep, 1.25)
    dist_m = make_dist(mesh, slots_per_device=spd)
    dist_l = make_dist(None, ep_size=ep, slots_per_device=spd)
    placement = build_placement(cfg.num_experts, ep, spd)
    p = LM.cast_params(MOE.init_moe(cfg, jax.random.PRNGKey(seed), dist_l,
                                    placement.replica_expert,
                                    dtype=jnp.bfloat16))
    p_m = jax.device_put(p, named_pspecs(param_pspecs(p, dist_m), dist_m))
    tables = MOE.routing_tables(placement)
    kx, kt = jax.random.split(jax.random.PRNGKey(seed + 1))
    inputs = {
        "features": jax.random.normal(kx, (32, cfg.d_model), jnp.bfloat16),
        "tokens": jax.random.normal(kt, (4, 256, cfg.d_model), jnp.bfloat16),
    }
    out = {}
    for algo in ("metro", "eplb"):
        for mode, x in inputs.items():
            def layer(dist):
                return jax.jit(lambda pp, xx: MOE.moe_ffn(
                    cfg, dist, pp, tables, xx, algo=algo, mode=mode))
            want, st_l = layer(dist_l)(p, x)
            compiled = layer(dist_m).lower(p_m, x).compile()
            got, st_m = compiled(p_m, x)
            text = compiled.as_text()
            assert "all-gather" in text, f"{algo}/{mode}: no all-gather"
            got = np.asarray(got, np.float32)
            want = np.asarray(want, np.float32)
            assert got.shape == want.shape and np.isfinite(got).all()
            rel = float(np.linalg.norm(got - want) / np.linalg.norm(want))
            same_route = bool(np.array_equal(
                np.asarray(st_m["expert_hist"]),
                np.asarray(st_l["expert_hist"])))
            print(f"EP=4 {algo} {mode}: out {list(got.shape)} relative L2 "
                  f"{rel:.3e} vs virtual EP (limit {EP_RTOL}), "
                  f"max activated/device {float(st_m['max_activated'])} "
                  f"(virtual {float(st_l['max_activated'])}), expert "
                  f"histogram identical {same_route}", flush=True)
            assert same_route, f"{algo}/{mode}: routing differs"
            assert rel <= EP_RTOL, f"{algo}/{mode}: {rel} > {EP_RTOL}"
            out[(algo, mode)] = rel
    return out


def _peak_bytes(dev):
    stats = dev.memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the EP=4 MoE layer across four chips")
    args = ap.parse_args(argv)

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU; JAX found {dev.platform}",
              file=sys.stderr)
        return 1
    need = 4 if args.four_chips else 1
    if len(devices) < need:
        print(f"chip_smoke: needs {need} chips; JAX found {len(devices)}",
              file=sys.stderr)
        return 1
    use_compile_cache()
    cfg = smoke_config()
    depth = ("one MoE layer, expert-parallel over 4 chips"
             if args.four_chips else
             f"depth cut to {LAYERS} of {get_config(ARCH).num_layers} "
             f"layers to fit one chip")
    print(f"device: {dev.platform} {dev.device_kind} x{len(devices)}; "
          f"jax {jax.__version__}", flush=True)
    print(f"model: {ARCH} at published widths (d_model {cfg.d_model}, "
          f"{cfg.num_heads}/{cfg.num_kv_heads} heads of {cfg.head_dim}, "
          f"{cfg.num_experts} experts top-{cfg.num_experts_per_tok}, "
          f"expert hidden {cfg.expert_hidden}, vocab {cfg.vocab_size}); "
          f"{depth}; random weights from seed 0", flush=True)
    t0 = time.perf_counter()
    if args.four_chips:
        ep_layer_phase(cfg, devices)
    else:
        prompts = make_prompts(cfg, N_REQUESTS, *PROMPT_LEN)
        ecfg = smoke_engine_config(PROMPT_LEN[1], GEN)
        for algo in ("metro", "eplb"):
            serve_phase(cfg, dataclasses.replace(ecfg, decode_algo=algo),
                        prompts, GEN, f"{algo} (XLA datapath)")
            gc.collect()     # one engine's weights at a time
        kernel_phase(cfg, ecfg, prompts, GEN)
    print(f"total wall {time.perf_counter() - t0:.1f} s; peak device bytes "
          f"in use {_peak_bytes(dev)}", flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
