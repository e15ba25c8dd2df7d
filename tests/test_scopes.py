"""The served forward's named scopes (``repro.models.scopes``) in the
compiled programs.

A profiler trace names each device operation by the ``op_name`` of the
HLO instruction it runs, which holds every enclosing
``jax.named_scope``; the benchmark reads device time by layer from
those paths.  These tests compile the steps at ``.reduced()`` size on
the CPU and check that every scope of the contract reaches the compiled
program, so that a refactor that drops one fails here instead of
silently emptying a layer's time.
"""
import re
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

from repro.configs import get_config
from repro.core import build_placement, slots_for_ratio
from repro.launch.steps import StepConfig, make_prefill_step, make_serve_step
from repro.models import init_lm
from repro.models import lm as LM
from repro.models import moe as MOE
from repro.models import scopes as SC
from repro.serving.engine import EngineConfig
from repro.serving.executor import Executor
from repro.serving.kv import pages_for
from repro.sharding.policy import make_dist

EP = 4
B, PROMPT, MAX_LEN = 4, 16, 32

# the scopes every step of an attention + MoE model holds
MODEL_SCOPES = {SC.EMBED, SC.LAYERS, SC.ATTENTION, SC.KV_WRITE, SC.MOE,
                SC.ROUTER, SC.ROUTE, SC.DISPATCH, SC.EXPERTS, SC.COMBINE,
                SC.HEAD}
STEP_SCOPE = {"serve": SC.SERVE_STEP, "prefill": SC.PREFILL_STEP,
              "decode": SC.DECODE_STEP}


def op_names(hlo_text: str) -> list[str]:
    """The ``op_name`` of every instruction that carries one."""
    return re.findall(r'op_name="([^"]*)"', hlo_text)


def program_paths(hlo_text: str) -> list[str]:
    """The op_names that are whole paths from the jitted function down
    (``jit(f)/...``); reducer bodies carry paths relative to their
    reduction and arguments carry their own names."""
    return [n for n in op_names(hlo_text) if n.startswith("jit(")]


def scopes_in(hlo_text: str) -> set[str]:
    return {c for n in op_names(hlo_text) for c in n.split("/")}


def _setup(cfg):
    spd = slots_for_ratio(cfg.num_experts, EP, 1.25)
    dist = make_dist(None, ep_size=EP, slots_per_device=spd)
    placement = build_placement(cfg.num_experts, EP, spd)
    params = jax.eval_shape(lambda k: init_lm(
        cfg, k, dist, replica_expert=placement.replica_expert,
        dtype=jnp.bfloat16), jax.random.PRNGKey(0))
    return dist, placement, params


def _launch_text(cfg, step: str, algo: str, impl: str) -> str:
    dist, placement, params = _setup(cfg)
    sc = StepConfig(cfg=cfg, dist=dist, algo_decode=algo, algo_train=algo,
                    moe_impl=impl)
    routing = LM.build_lm_routing(cfg, placement)
    cache = jax.eval_shape(lambda: LM.init_cache(cfg, dist, B, MAX_LEN))
    i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32)
    if step == "serve":
        fn, args = make_serve_step(sc), (params, i32(B, 1), i32(B), cache,
                                         routing)
    else:
        fn, args = make_prefill_step(sc), (params, {"tokens": i32(B, PROMPT)},
                                           cache, routing)
    return jax.jit(fn).lower(*args).compile().as_text()


def _engine_decode_text(cfg, algo: str, impl: str) -> str:
    """The engine's decode step (``Executor.decode_fn``), built without
    an executor: ``decode_fn`` reads only these attributes."""
    ecfg = EngineConfig(max_batch=B, max_len=MAX_LEN, decode_algo=algo,
                        moe_impl=impl)
    dist, placement, params = _setup(cfg)
    pmax = pages_for(ecfg.max_len, ecfg.page_size)
    cache = jax.eval_shape(lambda: LM.init_paged_cache(
        cfg, dist, ecfg.max_batch * pmax, ecfg.page_size, ecfg.max_batch))
    routing = LM.build_lm_routing(cfg, placement)
    exe = types.SimpleNamespace(cfg=cfg, dist=dist, ecfg=ecfg,
                                _get_fn=lambda kind, key, build: build())
    i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32)
    step = Executor.decode_fn(exe, B)
    return step.lower(params, i32(B, 1), i32(B), i32(B), i32(B, pmax),
                      cache, routing).compile().as_text()


def _text(cfg, step, algo, impl):
    if step == "decode":
        return _engine_decode_text(cfg, algo, impl)
    return _launch_text(cfg, step, algo, impl)


@pytest.mark.parametrize("impl", ["fused", "ragged"])
@pytest.mark.parametrize("algo", ["metro", "eplb"])
@pytest.mark.parametrize("step", ["serve", "prefill", "decode"])
def test_step_holds_every_contract_scope(step, algo, impl):
    text = _text(get_config("qwen3-30b-a3b").reduced(), step, algo, impl)
    missing = (MODEL_SCOPES | {STEP_SCOPE[step]}) - scopes_in(text)
    assert not missing, f"scopes missing from the compiled {step} step"
    # every operation of the program lies under its step scope
    paths = program_paths(text)
    assert paths
    outside = [p for p in paths
               if p.split("/")[1:2] != [STEP_SCOPE[step]]]
    assert not outside, outside[:5]


def test_scan_slicing_is_the_self_time_of_layers():
    """The scan's per-layer slices sit under ``layers`` and under no
    layer of the block: what ``layers``' self time reads."""
    text = _text(get_config("qwen3-30b-a3b").reduced(), "serve", "metro",
                 "ragged")
    own = [p for p in program_paths(text)
           if re.search(r"/layers/while/body/dynamic_slice$", p)]
    assert own


@pytest.mark.parametrize("arch,scopes", [
    ("jamba-1.5-large-398b", {SC.MAMBA, SC.MLP, SC.MOE, SC.ATTENTION}),
    ("qwen2-moe-a2.7b", {SC.SHARED_EXPERT, SC.MOE, SC.ATTENTION}),
])
def test_other_layers_are_named(arch, scopes):
    text = _launch_text(get_config(arch).reduced(), "serve", "metro",
                        "ragged")
    assert scopes <= scopes_in(text)


@pytest.mark.parametrize("mode", ["features", "tokens"])
def test_mesh_collectives_are_named(mode):
    """On an EP mesh (one device here: the collectives are traced all
    the same) the all-gathers and reductions lie under ``collective``."""
    cfg = get_config("qwen3-30b-a3b").reduced()
    mesh = Mesh(np.array(jax.devices()[:1]).reshape(1, 1),
                ("data", "model"))
    spd = slots_for_ratio(cfg.num_experts, 1, 1.25)
    dist = make_dist(mesh, slots_per_device=spd)
    placement = build_placement(cfg.num_experts, 1, spd)
    params = jax.eval_shape(lambda k: MOE.init_moe(
        cfg, k, dist, placement.replica_expert, jnp.bfloat16),
        jax.random.PRNGKey(0))
    tables = MOE.routing_tables(placement)
    shape = (B, cfg.d_model) if mode == "features" else (1, B, cfg.d_model)
    x = jax.ShapeDtypeStruct(shape, jnp.bfloat16)
    fn = lambda p, x: MOE.moe_ffn(cfg, dist, p, tables, x, algo="metro",
                                  mode=mode)
    text = jax.jit(fn).lower(params, x).as_text(dialect="hlo",
                                                 debug_info=True)
    named = {n.rsplit("/", 1)[-1] for n in op_names(text)
             if SC.COLLECTIVE in n.split("/")}
    assert {"all_gather", "reduce_scatter", "psum"} <= named
