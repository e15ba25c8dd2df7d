"""Compile rehearsals for a TPU v5e that is described, not attached.

The TPU compiler is installed with JAX, and it compiles for a chip
described by ``jax.experimental.topologies``.  These tests compile every
Pallas kernel an ``EngineConfig`` option reaches at qwen3-30b-a3b's
published widths, and the engine's decode step at ``chip_smoke.py``'s
size, so that what the chip's compiler refuses (unaligned blocks, too
much VMEM, a program bigger than HBM) fails here, before a chip run.

Nothing here touches the TPU library while the module is imported: the
topology is described inside a fixture, and the tests skip when it
cannot be.  Keep every such test in this one file.
"""
import dataclasses
import re
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

import chip_smoke
from repro.core import build_placement, slots_for_ratio
from repro.kernels.flash_decode import (flash_decode_paged,
                                        flash_prefill_paged)
from repro.kernels.metro_route import metro_route_pallas
from repro.kernels.moe_ffn import (fused_expert_ffn_paged_pallas,
                                   fused_expert_ffn_pallas,
                                   grouped_ffn_pallas)
from repro.launch.steps import StepConfig, make_serve_step
from repro.models import init_lm
from repro.models import lm as LM
from repro.serving.executor import Executor
from repro.serving.kv import pages_for
from repro.sharding.policy import make_dist

HBM_BYTES = 15.75e9      # what XLA lets one v5e program use

CFG = chip_smoke.smoke_config()
D, FE, N_EXPERTS = CFG.d_model, CFG.expert_hidden, CFG.num_experts
KV, G, HD = CFG.num_kv_heads, CFG.num_heads // CFG.num_kv_heads, CFG.head_dim
SLOTS, TILE = 160, 8                # EP=4 x 1.25 replication; MoE tile
# a decode batch of 8 tokens at top-8 plus per-slot tile padding
CAP = -(-(8 * 8 + SLOTS * (TILE - 1)) // TILE) * TILE
PAGES, PAGE, PMAX, B = 4096, 16, 128, 32


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    # a described-chip compile is written to the persistent cache but
    # cannot be read back without a chip: keep the cache out of it
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _shape(one_chip, shape, dtype=jnp.int32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)


def _compile(fn, *args):
    return jax.jit(fn).lower(*args).compile().as_text()


def _has_kernel(text: str) -> bool:
    return 'custom_call_target="tpu_custom_call"' in text


@pytest.mark.parametrize("kv_dtype", [jnp.bfloat16, jnp.float8_e4m3fn])
def test_flash_decode_paged(one_chip, kv_dtype):
    s = lambda *a: _shape(one_chip, *a)
    text = _compile(
        lambda q, k, v, pos, pt: flash_decode_paged(
            q, k, v, pos, pt, interpret=False),
        s((B, KV, G, HD), jnp.bfloat16), s((PAGES, PAGE, KV, HD), kv_dtype),
        s((PAGES, PAGE, KV, HD), kv_dtype), s((B,)), s((B, PMAX)))
    assert _has_kernel(text)


@pytest.mark.parametrize("window", [0, 1024], ids=["full", "sliding"])
def test_flash_prefill_paged(one_chip, window):
    s = lambda *a: _shape(one_chip, *a)
    chunk = 256
    text = _compile(
        lambda q, k, v, start, pt: flash_prefill_paged(
            q, k, v, start, pt, window=window, interpret=False),
        s((8, KV, chunk, G, HD), jnp.bfloat16),
        s((PAGES, PAGE, KV, HD), jnp.bfloat16),
        s((PAGES, PAGE, KV, HD), jnp.bfloat16), s((8,)), s((8, PMAX)))
    assert _has_kernel(text)


def test_fused_expert_ffn(one_chip):
    s = lambda *a: _shape(one_chip, *a)
    text = _compile(
        lambda x, wu, wd, tg: fused_expert_ffn_pallas(
            x, wu, wd, tg, gated=True, interpret=False),
        s((CAP, D), jnp.bfloat16), s((SLOTS, D, 2, FE), jnp.bfloat16),
        s((SLOTS, FE, D), jnp.bfloat16), s((CAP // TILE,)))
    assert _has_kernel(text)


def test_fused_expert_ffn_paged(one_chip):
    s = lambda *a: _shape(one_chip, *a)
    text = _compile(
        lambda x, wu, wd, fm, tg: fused_expert_ffn_paged_pallas(
            x, wu, wd, fm, tg, gated=True, interpret=False),
        s((CAP, D), jnp.bfloat16), s((SLOTS, D, 2 * FE), jnp.bfloat16),
        s((SLOTS, FE, D), jnp.bfloat16), s((SLOTS,)), s((CAP // TILE,)))
    assert _has_kernel(text)


@pytest.mark.parametrize("k_in,f_out", [(D, 2 * FE), (FE, D)],
                         ids=["up", "down"])
def test_grouped_ffn(one_chip, k_in, f_out):
    s = lambda *a: _shape(one_chip, *a)
    text = _compile(
        lambda x, w, tg: grouped_ffn_pallas(x, w, tg, interpret=False),
        s((CAP, k_in), jnp.bfloat16), s((SLOTS, k_in, f_out), jnp.bfloat16),
        s((CAP // TILE,)))
    assert _has_kernel(text)


def test_metro_route(one_chip):
    s = lambda *a: _shape(one_chip, *a)
    text = _compile(
        lambda counts, slots: metro_route_pallas(
            counts, slots, num_devices=4, slots_per_device=SLOTS // 4,
            interpret=False),
        s((N_EXPERTS,)), s((N_EXPERTS, 8)))
    assert _has_kernel(text)


def _decode_step_args(one_chip, ecfg):
    """Shapes of the engine's decode-step arguments at the smoke's
    size: bf16 weights (from ``init_lm``'s own shapes), paged cache,
    routing tables and a full batch."""
    ep = 4
    spd = slots_for_ratio(CFG.num_experts, ep, ecfg.replication_ratio)
    dist = make_dist(None, ep_size=ep, slots_per_device=spd)
    placement = build_placement(CFG.num_experts, ep, spd)
    pmax = pages_for(ecfg.max_len, ecfg.page_size)
    on_chip = lambda tree: jax.tree.map(
        lambda a: _shape(one_chip, a.shape, a.dtype), tree)
    params = on_chip(jax.eval_shape(lambda k: init_lm(
        CFG, k, dist, replica_expert=placement.replica_expert,
        dtype=jnp.bfloat16), jax.random.PRNGKey(0)))
    cache = on_chip(jax.eval_shape(lambda: LM.init_paged_cache(
        CFG, dist, ecfg.max_batch * pmax, ecfg.page_size, ecfg.max_batch)))
    routing = on_chip(jax.eval_shape(lambda: LM.build_lm_routing(
        CFG, placement, min(dist.num_slots - CFG.num_experts + 1, 2 * ep))))
    b = ecfg.max_batch
    args = (params, _shape(one_chip, (b, 1)), _shape(one_chip, (b,)),
            _shape(one_chip, (b,)), _shape(one_chip, (b, pmax)), cache,
            routing)
    # the executor's own decode-step builder, without an executor: it
    # reads only these attributes
    exe = types.SimpleNamespace(cfg=CFG, dist=dist, ecfg=ecfg,
                                _get_fn=lambda kind, key, build: build())
    return Executor.decode_fn(exe, b), args


def test_decode_step_fits_one_chip(one_chip):
    ecfg = chip_smoke.smoke_engine_config(chip_smoke.PROMPT_LEN[1],
                                          chip_smoke.GEN)
    step, args = _decode_step_args(one_chip, ecfg)
    mem = step.lower(*args).compile().memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    assert mem.argument_size_in_bytes > 7e9      # the bf16 weights
    assert total < HBM_BYTES, total


def test_kernel_decode_step_compiles_kernels(one_chip, monkeypatch):
    """The smoke's Pallas datapath as one compiled decode step: the
    three kernels appear as compiled custom calls.  Kernel mode follows
    the backend, which here is the CPU, so the test reports a TPU."""
    ecfg = dataclasses.replace(
        chip_smoke.smoke_engine_config(chip_smoke.PROMPT_LEN[1],
                                       chip_smoke.GEN),
        moe_impl="fused", use_flash_kernel=True, use_pallas_route=True)
    step, args = _decode_step_args(one_chip, ecfg)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    text = step.lower(*args).compile().as_text()
    assert chip_smoke.compiled_kernels(text) == set(chip_smoke.KERNELS)


# ----------------------------------------------------------------------
# the serve step reads the expert weights where they are stored
# ----------------------------------------------------------------------

_INSTR = re.compile(r"\s*(?:ROOT )?%(\S+) = ")
_KERNEL = "fused_expert_ffn_pallas"


def _hlo(text: str):
    """Optimized HLO text -> ({computation: {name: (type, op, operands,
    line)}}, name of the entry computation, computations that fusions
    and reducers call)."""
    comps, entry, called, cur = {}, None, set(), None
    for line in text.splitlines():
        head = re.match(r"(ENTRY )?%(\S+) \(.*\{$", line)
        if head:
            cur = comps.setdefault(head.group(2), {})
            entry = head.group(2) if head.group(1) else entry
            continue
        m = _INSTR.match(line)
        if cur is None or not m:
            continue
        rest = line[m.end():]
        if rest.startswith("("):                 # tuple-typed result
            depth = 0
            for i, ch in enumerate(rest):
                depth += {"(": 1, ")": -1}.get(ch, 0)
                if depth == 0:
                    break
            typ, rest = rest[:i + 1], rest[i + 2:]
        else:
            typ, _, rest = rest.partition(" ")
        op, _, rest = rest.partition("(")
        depth, i = 1, 0
        while depth:
            depth += {"(": 1, ")": -1}.get(rest[i], 0)
            i += 1
        operands = re.findall(r"%([\w.\-]+)", rest[:i - 1])
        cur[m.group(1)] = (typ, op, operands, line)
        for key in ("calls", "to_apply"):
            called.update(re.findall(key + r"=%([\w.\-]+)", line))
    return comps, entry, called


def _trace_to_entry(comps, entry, comp, name, index=None):
    """Follow one value back through bitcasts, tuples and loop
    boundaries; returns the entry parameter it is, or the first
    instruction that makes it (which is then a copy of some kind)."""
    typ, op, operands, line = comps[comp][name]
    if op == "bitcast":
        return _trace_to_entry(comps, entry, comp, operands[0], index)
    if op == "get-tuple-element":
        i = int(re.search(r"index=(\d+)", line).group(1))
        return _trace_to_entry(comps, entry, comp, operands[0], i)
    if op == "tuple":
        return _trace_to_entry(comps, entry, comp, operands[index])
    if op == "while":
        return _trace_to_entry(comps, entry, comp, operands[0], index)
    if op == "parameter" and comp == entry:
        return name
    if op == "parameter":
        # a loop body's parameter: what the loop that runs it was given
        for outer, instrs in comps.items():
            for wname, (_, wop, wargs, wline) in instrs.items():
                if wop == "while" and f"body=%{comp}" in wline:
                    return _trace_to_entry(comps, entry, outer, wargs[0],
                                           index)
    return f"{op} {name}"


def _cell_serve_step_hlo(one_chip, monkeypatch):
    """The static cell's serve step (qwen3-30b-a3b at published widths,
    5 layers, a 4-rank virtual EP group at 1.25x: 160 slots, batch 32,
    KV cache 1024, METRO, the fused kernel) compiled for a v5e."""
    cfg = dataclasses.replace(CFG, num_layers=5)
    ep = 4
    spd = slots_for_ratio(cfg.num_experts, ep, 1.25)
    dist = make_dist(None, ep_size=ep, slots_per_device=spd)
    placement = build_placement(cfg.num_experts, ep, spd)
    on_chip = lambda tree: jax.tree.map(
        lambda a: _shape(one_chip, a.shape, a.dtype), tree)
    params = on_chip(jax.eval_shape(lambda k: init_lm(
        cfg, k, dist, replica_expert=placement.replica_expert,
        dtype=jnp.bfloat16), jax.random.PRNGKey(0)))
    cache = on_chip(jax.eval_shape(
        lambda: LM.init_cache(cfg, dist, B, 1024)))
    routing = on_chip(jax.eval_shape(
        lambda: LM.build_lm_routing(cfg, placement)))
    step = make_serve_step(StepConfig(cfg=cfg, dist=dist,
                                      algo_decode="metro",
                                      moe_impl="fused"))
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    return jax.jit(step, donate_argnums=(3,)).lower(
        params, _shape(one_chip, (B, 1)), _shape(one_chip, (B,)), cache,
        routing).compile().as_text()


def test_serve_step_reads_expert_stacks_in_place(one_chip, monkeypatch):
    """No copy of expert-weight size in the decode step: nothing but the
    entry parameters, the loop's plumbing and the fused kernel itself
    makes a bf16 array as large as one layer's ``w_down``, and the
    kernel's weight operands are the entry parameters themselves (no
    per-layer slice, no relayout of ``w_up``)."""
    comps, entry, called = _hlo(_cell_serve_step_hlo(one_chip,
                                                     monkeypatch))
    one_layer = SLOTS * FE * D
    plumbing = {"parameter", "get-tuple-element", "tuple", "while",
                "bitcast"}
    large, kernels = [], []
    for comp, instrs in comps.items():
        if comp in called:              # inside a fusion or a reducer
            continue
        for name, (typ, op, operands, line) in instrs.items():
            if op == "custom-call" and name.startswith(_KERNEL):
                kernels.append((comp, name, operands))
                continue
            sizes = [int(np.prod([int(v) for v in dims.split(",") if v]))
                     for dims in re.findall(r"bf16\[([\d,]*)\]", typ)]
            if op not in plumbing and any(n >= one_layer for n in sizes):
                large.append(f"{op} {name} {typ}")
    assert not large, large
    assert len(kernels) == 1, kernels
    comp, _, operands = kernels[0]
    weights = [_trace_to_entry(comps, entry, comp, w)
               for w in operands[-2:]]
    assert ["w_up" in weights[0], "w_down" in weights[1]] == [True, True], \
        weights
    assert all(comps[entry][w][1] == "parameter" for w in weights), weights
