"""Compile a cell's programs for a TPU v5e that is described, not
attached, and print each program's bytes: the weight generator, the
step shapes the warm-up reaches and the rebalance's regather (a static
cell: the cache's program and the prefill and serve steps).

  JAX_PLATFORMS=cpu python3 bench/rehearse.py <cell> [<cell> ...]
  python3 bench/rehearse.py --attached <cell> [<cell> ...]

A program bigger than the chip's memory, a kernel the chip's compiler
refuses, or a step without its compiled kernels fails here, before any
chip time is spent.  Nothing runs; the numbers are counts of bytes.
``--attached`` compiles for the chip this process holds instead, and
then reads the bytes in use, which compiling alone leaves near 0.
"""
from __future__ import annotations

import os
import sys
import types

os.environ.setdefault("TPU_LOG_DIR", "disabled")
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (os.path.join(ROOT, "src"), ROOT):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import SingleDeviceSharding  # noqa: E402

from bench import run as R  # noqa: E402
from bench import serve  # noqa: E402
from bench import weights as W  # noqa: E402
from bench.sizes import sizes  # noqa: E402

HBM_BYTES = 15.75e9         # what XLA lets one v5e program use
KERNELS = ("fused_expert_ffn_pallas", "flash_decode_paged",
           "metro_route_pallas")


def programs(cfg, mix, one_chip):
    """(name, jitted function, argument shapes) of the cell's programs:
    the weights' generator, every step shape the warm-up runs
    (``serve.step_shapes``) and the rebalance's regather."""
    from repro.models import lm as LM
    from repro.serving.executor import Executor, _regather_slots
    from repro.serving.kv import pages_for
    from repro.sharding.policy import make_dist

    if mix["loop"] == "static":
        return static_programs(cfg, mix, one_chip)
    mcfg = serve.model_config(cfg)
    ecfg = serve.engine_config(cfg, mix)
    placement, spd = serve.placement_for(cfg)
    ep = cfg["deployment"]["ep_size"]
    dist = make_dist(None, ep_size=ep, slots_per_device=spd)
    s = sizes(cfg)
    on_chip = lambda tree: jax.tree.map(            # noqa: E731
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                       sharding=one_chip), tree)
    ids = jnp.asarray(placement.replica_expert)
    params = on_chip(jax.eval_shape(
        lambda: serve._program_params(W.root_key(0), s, ids)))
    pmax = pages_for(ecfg.max_len, ecfg.page_size)
    cache = on_chip(jax.eval_shape(lambda: LM.init_paged_cache(
        mcfg, dist, ecfg.num_pages or ecfg.max_batch * pmax,
        ecfg.page_size, ecfg.max_batch)))
    width = max(min(dist.num_slots - s.experts + 1, 2 * ep),
                placement.max_replicas)
    routing = on_chip(jax.eval_shape(
        lambda: LM.build_lm_routing(mcfg, placement, width)))
    i32 = lambda *shape: jax.ShapeDtypeStruct(      # noqa: E731
        shape, jnp.int32, sharding=one_chip)
    b, c = ecfg.max_batch, ecfg.prefill_chunk
    exe = types.SimpleNamespace(cfg=mcfg, dist=dist, ecfg=ecfg,
                                _get_fn=lambda kind, key, build: build())
    dec = (i32(b, 1), i32(b), i32(b), i32(b, pmax))

    def chunk(bp):
        return (i32(bp, c), i32(bp), i32(bp), i32(bp), i32(bp, pmax))
    key = on_chip(jax.eval_shape(lambda: W.root_key(0)))
    shapes = serve.step_shapes(cfg, mix)
    return ([("weights", serve.program_params, (key, s, ids))]
            + [(f"chunk({bp})", Executor.chunk_fn(exe, bp),
                (params, *chunk(bp), cache, routing))
               for bp in shapes["chunk"]]
            + [(f"mixed({bp},{bd})", Executor.mixed_fn(exe, bp, bd),
                (params, *chunk(bp), *dec, cache, routing))
               for bp, bd in shapes["mixed"]]
            + [(f"decode({b})", Executor.decode_fn(exe, b),
                (params, *dec, cache, routing))]
            + [("regather w_up", _regather_slots,
                (params["blocks"]["l0"]["moe"]["w_up"],
                 i32(dist.num_slots)))])


def static_programs(cfg, mix, one_chip):
    """The weights' generator and a static batch's programs (the cache's,
    the prefill step and the serve step)."""
    from bench import batch
    from repro.models import lm as LM
    s = sizes(cfg)
    sc, placement = batch.step_config(cfg)
    on_chip = lambda tree: jax.tree.map(            # noqa: E731
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                       sharding=one_chip), tree)
    ids = jnp.asarray(placement.replica_expert)
    params = on_chip(jax.eval_shape(
        lambda: serve._program_params(W.root_key(0), s, ids)))
    routing = on_chip(jax.eval_shape(
        lambda: LM.build_lm_routing(sc.cfg, placement)))
    b, n = mix["batch"], mix["prompt"]
    prefill, decode, new_cache = batch.step_programs(sc)
    cache = on_chip(jax.eval_shape(lambda: new_cache(b, n + mix["output"])))
    i32 = lambda *shape: jax.ShapeDtypeStruct(      # noqa: E731
        shape, jnp.int32, sharding=one_chip)
    key = on_chip(jax.eval_shape(lambda: W.root_key(0)))
    return [("weights", serve.program_params, (key, s, ids)),
            (f"prefill({b},{n})", prefill, (params, i32(b, n), cache,
                                            routing)),
            (f"decode({b})", decode, (params, i32(b), i32(b), cache,
                                      routing))]


def main(argv) -> int:
    attached = argv[:1] == ["--attached"]
    if attached:
        argv = argv[1:]
        one_chip = SingleDeviceSharding(jax.devices()[0])
    else:
        from jax.experimental import topologies
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
        one_chip = SingleDeviceSharding(topo.devices[0])
        jax.default_backend = lambda: "tpu"     # compile kernels, not
                                                # the interpreter
    jax.config.update("jax_enable_compilation_cache", False)
    ok = True
    for name in argv:
        _, _, cfg, mix = R.load_cell(name)
        print(f"{name}: max_len {serve.max_len_for(cfg, mix)}", flush=True)
        for label, fn, args in programs(cfg, mix, one_chip):
            try:
                compiled = fn.lower(*args).compile()
            except Exception as e:      # noqa: BLE001 — report, go on
                ok = False
                print(f"  {label:14s} REFUSED: {str(e).splitlines()[0]}",
                      flush=True)
                continue
            m = compiled.memory_analysis()
            total = (m.argument_size_in_bytes + m.output_size_in_bytes
                     + m.temp_size_in_bytes - m.alias_size_in_bytes)
            text = compiled.as_text()
            found = [k for k in KERNELS if k in text]
            fits = total < HBM_BYTES
            ok &= fits
            print(f"  {label:14s} arguments {m.argument_size_in_bytes} "
                  f"outputs {m.output_size_in_bytes} temporaries "
                  f"{m.temp_size_in_bytes} aliased {m.alias_size_in_bytes}"
                  f" total {total} ({'fits' if fits else 'DOES NOT FIT'})"
                  f" kernels {found}", flush=True)
    if attached:
        print(f"memory in use: {jax.devices()[0].memory_stats()}",
              flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
