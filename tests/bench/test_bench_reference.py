"""The float32 reference (bench/reference.py) against the program, at
``.reduced()`` size on the CPU, for every configuration a cell serves.

The model's step functions with the engine's datapath (fused expert
kernel, paged flash decode, METRO routing kernel, all in interpret mode
here) prefill a prompt through the paged cache in one chunk and then
decode token by token; their logits at every position are held to the
reference's, under METRO and under EPLB in decode.  This drives
``apply_lm`` itself, feeding each position once; the whole engine, its
scheduler included, is checked by a run in test_bench_faults.py.
"""
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import reference, serve
from bench import weights as W
from bench.sizes import reduced, sizes
from repro.models import lm as LM
from repro.sharding.policy import make_dist

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
MODELS = ["qwen3-30b-a3b"]
SEEDS = [3, 4]
N_PROMPT, N_DECODE, PAGE = 24, 6, 16

# Per position, the largest logit error over the vocabulary, in units of
# that position's logit spread (std); the median over positions must
# stay under this.  bf16 rounding alone gives about 0.03 here.  A router
# near-tie that sends a token to another expert moves that position and,
# through attention, later ones: the worst seed reads 0.16.  The fp8
# control reads 0.36 or more at this size (test below).
LOGIT_TOL = 0.25


def _cfg(name):
    with open(os.path.join(ROOT, "bench", "configs", f"{name}.json")) as f:
        return reduced(json.load(f))


def _program_logits(cfg, seed, seqs, algo):
    """The engine's own step program path: ``apply_lm`` in
    ``chunk_prefill`` mode over the prompts, then ``decode`` steps, on
    the paged cache, with the cell's kernels."""
    s, mcfg = sizes(cfg), serve.model_config(cfg)
    placement, spd = serve.placement_for(cfg)
    ep = cfg["deployment"]["ep_size"]
    dist = make_dist(None, ep_size=ep, slots_per_device=spd)
    params = serve.program_params(W.root_key(seed), s,
                                  jnp.asarray(placement.replica_expert))
    width = max(min(dist.num_slots - s.experts + 1, 2 * ep),
                placement.max_replicas)
    routing = LM.build_lm_routing(mcfg, placement, width)
    b, pmax = len(seqs), -(-(N_PROMPT + N_DECODE) // PAGE)
    cache = LM.init_paged_cache(mcfg, dist, b * pmax, PAGE, b)
    pt = jnp.arange(b * pmax, dtype=jnp.int32).reshape(b, pmax)
    slot = jnp.arange(b, dtype=jnp.int32)
    kw = dict(moe_impl="fused", use_pallas_route=True, slot_idx=slot,
              page_table=pt)

    @jax.jit
    def prefill(tokens, cache):
        return LM.apply_lm(
            mcfg, dist, params, tokens=tokens, pos=jnp.zeros(b, jnp.int32),
            cache=cache, routing=routing, mode="chunk_prefill", algo="eplb",
            row_valid=jnp.ones(tokens.shape, bool), **kw)[:2]

    @jax.jit
    def decode(tokens, pos, cache):
        return LM.apply_lm(
            mcfg, dist, params, tokens=tokens, pos=pos, cache=cache,
            routing=routing, mode="decode", algo=algo, row_valid=slot < b,
            use_flash_kernel=True, **kw)[:2]

    out, cache = prefill(jnp.asarray(seqs[:, :N_PROMPT]), cache)
    outs = [np.asarray(out, np.float32)]
    for p in range(N_PROMPT, N_PROMPT + N_DECODE):
        out, cache = decode(jnp.asarray(seqs[:, p:p + 1]),
                            jnp.full(b, p, jnp.int32), cache)
        outs.append(np.asarray(out, np.float32))
    return np.concatenate(outs, axis=1)


def _error(got, want):
    """Median over positions of max |error| / logit std."""
    return float(np.median(np.abs(got - want).max(-1) / want.std(-1)))


def _seqs(cfg, seed):
    rng = np.random.default_rng(seed)
    return rng.integers(0, sizes(cfg).vocab,
                        (2, N_PROMPT + N_DECODE)).astype(np.int32)


@pytest.mark.parametrize("name", MODELS)
def test_program_weights_are_the_references(name):
    """Every replica slot holds its expert's draw, bit for bit."""
    cfg = _cfg(name)
    s = sizes(cfg)
    placement, _ = serve.placement_for(cfg)
    p = serve.program_params(W.root_key(5), s,
                             jnp.asarray(placement.replica_expert))
    moe = p["blocks"]["l0"]["moe"]
    for li in range(s.layers):
        ref = W.experts(W.root_key(5), s, "w_up", li,
                        jnp.arange(s.experts))
        got = moe["w_up"][li]
        assert got.dtype == jnp.bfloat16
        assert np.array_equal(np.asarray(got, np.float32), np.asarray(
            ref[placement.replica_expert], np.float32))
        assert np.array_equal(np.asarray(moe["w_router"][li]), np.asarray(
            W.tensor(W.root_key(5), s, "router", li)))


@pytest.mark.parametrize("algo", ["metro", "eplb"])
@pytest.mark.parametrize("name", MODELS)
def test_prefill_decode_logits_match_reference(name, algo):
    cfg = _cfg(name)
    s = sizes(cfg)
    for seed in SEEDS:
        seqs = _seqs(cfg, seed)
        got = _program_logits(cfg, seed, seqs, algo)
        for r in range(len(seqs)):
            want = reference.logits(s, seed, seqs[r])
            assert _error(got[r], want) < LOGIT_TOL, (seed, r)


@pytest.mark.parametrize("name", MODELS)
def test_fp8_control_fails_the_tolerance(name):
    cfg = _cfg(name)
    s = sizes(cfg)
    for seed in SEEDS:
        seq = _seqs(cfg, seed)[0]
        want = reference.logits(s, seed, seq)
        assert _error(reference.logits(s, seed, seq, control=True),
                      want) > LOGIT_TOL


def test_static_control_reads_incorrect():
    """The static cell's check at ``.reduced()`` size: over two batches
    of the launch steps, the served tokens' mean gap stays under the
    cell's limit on every seed, and the fp8 control's, read at the same
    positions, goes over it."""
    from bench import batch
    with open(os.path.join(ROOT, "bench", "traffic", "batch.json")) as f:
        limit = json.load(f)["check"]["mean_logit_gap"]
    cfg = _cfg("qwen3-30b-a3b")
    s = sizes(cfg)
    mix = {"loop": "static", "batch": 4, "prompt": 24, "output": 8}
    for seed in (2 ** 31 + 13, 5, 6):
        prog = batch.Batches(cfg, mix, seed)
        sent = [prog.send(i) for i in range(2)]
        seqs = [(batch.prompts(mix, seed, b.index, s.vocab)[r],
                 prog.served(b)[r]) for b in sent for r in range(4)]
        res = reference.served_gaps(s, seed, seqs, reference.pad_len(32),
                                    control=True)
        program = np.concatenate([r["gap"] for r in res]).mean()
        control = np.concatenate([r["control_gap"] for r in res]).mean()
        assert program <= limit < control, (seed, program, control)
