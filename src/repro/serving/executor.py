"""Execution layer: jitted step functions, input packing, and the
physical expert-weight substrate (placement, routing tables, reshuffle).

The executor owns everything that touches jax: the per-shape-signature
jit cache, the decode/prefill/chunk/mixed step builders, the numpy->jnp
input packers, the KV cache pytree, and the EPLB placement + routing
tables + compute-dtype weights the rebalance loop reshuffles on the
device.  It makes *no* scheduling decisions — the engine façade hands
it rows the scheduler already picked.

Step builders close over ``(cfg, dist, ecfg)`` only; params / cache /
routing enter as call arguments.  Engines built from identical configs
can therefore share one ``fn_cache`` (the cluster layer does this so N
replicas compile each signature once) — sharing across *different*
configs is invalid and the caller's responsibility to avoid.
"""
from __future__ import annotations

import functools
import time
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.core import build_placement
from repro.models import lm as LM
from repro.models import scopes as SC
from repro.serving.expert_pool import build_expert_pool
from repro.serving.kv import pages_for
from repro.serving.scheduler import _pow2
from repro.serving.state import Request
from repro.sharding.policy import Dist

# EngineConfig.kv_dtype -> pool dtype.  fp8 pools are dequantized to
# bf16 inside the paged read paths (gather reference and Pallas kernels
# both branch on itemsize == 1); writes quantize on the scatter's
# astype.  Parity vs an fp32 pool is tolerance-pinned in
# tests/test_prefix_cache.py.
KV_DTYPES = {
    "bf16": jnp.bfloat16,
    "fp32": jnp.float32,
    "fp8": jnp.float8_e4m3fn,
}


@functools.partial(jax.jit, donate_argnums=0)
def _regather_slots(w, src):
    """Reshuffle a stacked expert tensor ``w`` [n_blocks, R, ...] on the
    device: slot ``s`` of every layer takes old slot ``src[s]``.  One
    layer at a time into the donated stack, so the peak is one layer's
    slot set, not a second copy of every layer's experts."""
    def layer(i, w):
        return jax.lax.dynamic_update_index_in_dim(
            w, jax.lax.dynamic_index_in_dim(w, i, 0, keepdims=False)[src],
            i, 0)
    return jax.lax.fori_loop(0, w.shape[0], layer, w)


class Executor:
    def __init__(self, cfg: ModelConfig, dist: Dist, ecfg, params, slo,
                 routing_table_width: int = 0,
                 fn_cache: Optional[dict] = None):
        self.cfg = cfg
        self.dist = dist
        self.ecfg = ecfg
        # weights are held in the compute dtype, cast once here (the
        # step's own cast_params is then the identity).  The executor
        # owns them: rebalance rewrites the expert stacks in place.
        self.params = LM.cast_params(params)
        self.slo = slo
        self._table_width = routing_table_width

        if cfg.is_moe:
            self.placement = build_placement(
                cfg.num_experts, dist.ep_size, dist.slots_per_device,
                loads=np.ones(cfg.num_experts))
            if not self._table_width:
                self._table_width = min(
                    dist.num_slots - cfg.num_experts + 1, dist.ep_size * 2)
                self._table_width = max(self._table_width,
                                        self.placement.max_replicas)
            self.routing = LM.build_lm_routing(cfg, self.placement,
                                               self._table_width)
        else:
            self.placement, self.routing = None, {}

        # paged expert-weight pool (host <-> HBM, activation-aware
        # prefetch).  Host-side working-set bookkeeping: the step
        # functions always compute on the true weights (a fetch always
        # completes before use, so residency never changes the math);
        # the pool's fetch bytes feed virtual-time cost models and the
        # SLO's stall attribution.
        self.expert_pool = (
            build_expert_pool(cfg, ecfg, dist.num_slots)
            if cfg.is_moe and getattr(ecfg, "expert_pool", False)
            else None)

        kv_dtype = KV_DTYPES[getattr(ecfg, "kv_dtype", "bf16")]
        if ecfg.kv_layout == "paged":
            pmax = pages_for(ecfg.max_len, ecfg.page_size)
            num_pages = ecfg.num_pages or ecfg.max_batch * pmax
            self.cache = LM.init_paged_cache(
                cfg, dist, num_pages, ecfg.page_size, ecfg.max_batch,
                dtype=kv_dtype)
        else:
            self.cache = LM.init_cache(cfg, dist, ecfg.max_batch,
                                       ecfg.max_len)
        if fn_cache is None:
            fn_cache = {"decode": {}, "prefill": {}, "chunk": {},
                        "mixed": {}, "copy": {}}
        self._fns: dict[str, dict] = fn_cache

    # ------------------------------------------------------------------
    # weight reshuffling (EPLB rebalance)
    # ------------------------------------------------------------------
    def rebalance(self, loads: np.ndarray,
                  placement=None):
        """Install a new EPLB placement (recomputed from ``loads``
        unless the cluster hands down a shared one) and reshuffle the
        physical expert weights to it.  Replica choice moves compute,
        not math: every replica of an expert holds identical weights,
        so a reshuffle is bitwise invisible to in-flight requests."""
        if not self.cfg.is_moe:
            return
        if placement is None:
            placement = build_placement(
                self.cfg.num_experts, self.dist.ep_size,
                self.dist.slots_per_device, loads=loads)
        if self.expert_pool is not None:
            # the reshuffle rewrites any slot whose expert assignment
            # changed — its cached pages (every layer) are stale
            changed = np.nonzero(
                np.asarray(self.placement.replica_expert)
                != np.asarray(placement.replica_expert))[0]
            self.expert_pool.invalidate_slots(changed)
        # every expert keeps a replica, so each new slot copies its
        # expert's weights from one of the slots that hold it now
        src = jnp.asarray(self.placement.expert_slots[
            placement.replica_expert, 0], jnp.int32)
        self.placement = placement
        self.routing = LM.build_lm_routing(self.cfg, placement,
                                           self._table_width)

        def put(tree):
            for k, v in tree.items():
                if isinstance(v, dict):
                    put(v)
                elif k in ("w_up", "w_down") and v.ndim >= 4:
                    tree[k] = _regather_slots(v, src)
        put(self.params["blocks"])

    # ------------------------------------------------------------------
    # step functions (compiled once per shape signature)
    # ------------------------------------------------------------------
    def _get_fn(self, kind: str, key, builder):
        # setdefault: externally-supplied fn_caches predating a kind
        # (e.g. "copy") still work
        fns = self._fns.setdefault(kind, {})
        if key not in fns:
            fns[key] = builder()
            self.slo.compiled(kind, key)
        return fns[key]

    def compiled_buckets(self, kind: str):
        """Shape keys already built for ``kind`` (the scheduler's
        bucket-grace policy reads the decode set)."""
        return self._fns.setdefault(kind, {}).keys()

    def decode_fn(self, bucket: int):
        def build():
            cfg, dist, ecfg = self.cfg, self.dist, self.ecfg
            paged = ecfg.kv_layout == "paged"

            @jax.jit
            @jax.named_scope(SC.DECODE_STEP)
            def step(params, tokens, pos, slot_idx, page_table, cache,
                     routing):
                logits, new_cache, stats = LM.apply_lm(
                    cfg, dist, params, tokens=tokens, pos=pos, cache=cache,
                    routing=routing, mode="decode", algo=ecfg.decode_algo,
                    moe_impl=ecfg.moe_impl,
                    use_pallas_route=ecfg.use_pallas_route,
                    slot_idx=slot_idx,
                    page_table=page_table if paged else None,
                    row_valid=slot_idx < ecfg.max_batch,
                    use_flash_kernel=ecfg.use_flash_kernel)
                with jax.named_scope(SC.HEAD):
                    nxt = jnp.argmax(logits[:, -1], axis=-1).astype(
                        jnp.int32)
                return nxt, new_cache, stats
            return step
        return self._get_fn("decode", bucket, build)

    def prefill_fn(self, batch: int, length: int):
        def build():
            cfg, dist, ecfg = self.cfg, self.dist, self.ecfg
            paged = ecfg.kv_layout == "paged"

            @jax.jit
            @jax.named_scope(SC.WAVE_STEP)
            def step(params, tokens, lengths, slot_idx, page_table, cache,
                     routing):
                wave = LM.init_wave_cache(cfg, dist, batch, length)
                _, filled, stats = LM.apply_lm(
                    cfg, dist, params, tokens=tokens, cache=wave,
                    routing=routing, mode="prefill",
                    algo=ecfg.prefill_algo, moe_impl=ecfg.moe_impl,
                    use_pallas_route=ecfg.use_pallas_route,
                    chunk=ecfg.prefill_chunk,
                    row_valid=jnp.arange(length)[None, :]
                    < lengths[:, None])
                new_cache = LM.merge_wave_cache(
                    cfg, cache, filled, slot_idx, lengths,
                    page_table=page_table if paged else None,
                    page_size=ecfg.page_size)
                return new_cache, stats
            return step
        return self._get_fn("prefill", (batch, length), build)

    def chunk_fn(self, batch: int):
        """One resumable prefill chunk for ``batch`` rows: [B, C] tokens
        written straight into the paged serving cache (no wave scratch,
        no O(max_len) buffer — C = prefill_chunk is the only length)."""
        def build():
            cfg, dist, ecfg = self.cfg, self.dist, self.ecfg
            c = ecfg.prefill_chunk

            @jax.jit
            @jax.named_scope(SC.CHUNK_STEP)
            def step(params, tokens, start, n_tok, slot_idx, page_table,
                     cache, routing):
                _, new_cache, stats = LM.apply_lm(
                    cfg, dist, params, tokens=tokens, pos=start,
                    cache=cache, routing=routing, mode="chunk_prefill",
                    algo=ecfg.prefill_algo, moe_impl=ecfg.moe_impl,
                    use_pallas_route=ecfg.use_pallas_route,
                    slot_idx=slot_idx, page_table=page_table,
                    row_valid=jnp.arange(c)[None, :] < n_tok[:, None])
                return new_cache, stats
            return step
        return self._get_fn("chunk", batch, build)

    def mixed_fn(self, bp: int, bd: int):
        """Fused mixed step: ``bp`` prefill-chunk rows and ``bd`` decode
        rows in ONE jitted call — the chunk sub-graph writes its pages,
        then the decode sub-graph runs against the updated cache, exactly
        the pure-phase chunk-then-decode sequence (bitwise: the
        equivalence test), but decode no longer waits for a dispatch."""
        def build():
            cfg, dist, ecfg = self.cfg, self.dist, self.ecfg
            c = ecfg.prefill_chunk

            @jax.jit
            @jax.named_scope(SC.MIXED_STEP)
            def step(params, p_tokens, p_start, p_ntok, p_slot, p_pt,
                     d_tokens, d_pos, d_slot, d_pt, cache, routing):
                _, cache1, st_p = LM.apply_lm(
                    cfg, dist, params, tokens=p_tokens, pos=p_start,
                    cache=cache, routing=routing, mode="chunk_prefill",
                    algo=ecfg.prefill_algo, moe_impl=ecfg.moe_impl,
                    use_pallas_route=ecfg.use_pallas_route,
                    slot_idx=p_slot, page_table=p_pt,
                    row_valid=jnp.arange(c)[None, :] < p_ntok[:, None])
                logits, cache2, st_d = LM.apply_lm(
                    cfg, dist, params, tokens=d_tokens, pos=d_pos,
                    cache=cache1, routing=routing, mode="decode",
                    algo=ecfg.decode_algo, moe_impl=ecfg.moe_impl,
                    use_pallas_route=ecfg.use_pallas_route,
                    slot_idx=d_slot, page_table=d_pt,
                    row_valid=d_slot < ecfg.max_batch,
                    use_flash_kernel=ecfg.use_flash_kernel)
                with jax.named_scope(SC.HEAD):
                    nxt = jnp.argmax(logits[:, -1], axis=-1).astype(
                        jnp.int32)
                return nxt, cache2, st_p, st_d
            return step
        return self._get_fn("mixed", (bp, bd), build)

    def copy_fn(self):
        """Copy-on-write page copy: duplicate one physical page's K/V
        contents (every attention layer's pool) into a fresh page, so a
        prefix-hit request can write its own suffix into the boundary
        page without corrupting the shared original.  Only the first
        ``keep`` token offsets (the matched prefix tokens living in the
        boundary page) are copied; the rest of the destination page is
        zeroed — exactly the state a cold prefill would find, which is
        what makes a hit request's pages BITWISE equal to the cold
        run's (and keeps stale source bytes from ever entering the
        copy).  One jitted signature total — src/dst/keep are data, and
        per-slot (mamba) cache entries pass through untouched (the
        prefix cache is disabled for mamba-bearing archs; their state
        is not paged)."""
        def build():
            @jax.jit
            def fn(cache, src, dst, keep):
                out = {}
                for li, pool in cache.items():
                    if "k" not in pool:
                        out[li] = pool
                        continue
                    ps = pool["k"].shape[2]
                    mask = (jnp.arange(ps) < keep)[None, :, None, None]
                    out[li] = {kk: pool[kk].at[:, dst].set(
                        jnp.where(mask, pool[kk][:, src],
                                  jnp.zeros((), pool[kk].dtype)))
                        for kk in ("k", "v")}
                return out
            return fn
        return self._get_fn("copy", 0, build)

    def run_copy_pages(self, src: int, dst: int, keep: int):
        """Device copy of physical page ``src`` -> ``dst``: the first
        ``keep`` token offsets, rest zeroed (CoW boundary page)."""
        fn = self.copy_fn()
        self.cache = fn(self.cache, jnp.int32(src), jnp.int32(dst),
                        jnp.int32(keep))

    # ------------------------------------------------------------------
    # input packing (numpy host state -> padded jnp step inputs)
    # ------------------------------------------------------------------
    def chunk_inputs(self, pwork: list[tuple[Request, int]], b: int,
                     kvman):
        ecfg = self.ecfg
        c = ecfg.prefill_chunk
        pmax = pages_for(ecfg.max_len, ecfg.page_size)
        toks = np.zeros((b, c), np.int32)
        start = np.zeros((b,), np.int32)
        n_tok = np.zeros((b,), np.int32)
        slot_idx = np.full((b,), ecfg.max_batch, np.int32)
        pt = np.full((b, pmax), -1, np.int32)
        for i, (r, n) in enumerate(pwork):
            ctx = r.context_tokens()
            toks[i, :n] = ctx[r.pos:r.pos + n]
            start[i] = r.pos
            n_tok[i] = n
            slot_idx[i] = r.slot
        pt[:len(pwork)] = kvman.rows([r.slot for r, _ in pwork])
        return (jnp.asarray(toks), jnp.asarray(start), jnp.asarray(n_tok),
                jnp.asarray(slot_idx), jnp.asarray(pt))

    def decode_inputs(self, drows: list[Request], b: int, kvman):
        ecfg = self.ecfg
        pmax = pages_for(ecfg.max_len, ecfg.page_size)
        tokens = np.zeros((b, 1), np.int32)
        pos = np.zeros((b,), np.int32)
        slot_idx = np.full((b,), ecfg.max_batch, np.int32)
        pt = np.full((b, pmax), -1, np.int32)
        for i, r in enumerate(drows):
            tokens[i, 0] = (r.generated[-1] if r.generated
                            else int(r.context_tokens()[-1]))
            # a row finishing its prefill THIS iteration decodes at
            # n_ctx (its r.pos advances when the chunk completes); an
            # already-decoding row is simply at r.pos.  (n_ctx +
            # len(generated) would be wrong after a mid-decode
            # preemption: the re-prefilled n_ctx already contains the
            # generated tokens.)
            pos[i] = r.n_ctx if r.prefilling else r.pos
            slot_idx[i] = r.slot
        if kvman is not None:
            pt[:len(drows)] = kvman.rows([r.slot for r in drows])
        return (jnp.asarray(tokens), jnp.asarray(pos),
                jnp.asarray(slot_idx), jnp.asarray(pt))

    # ------------------------------------------------------------------
    # expert-pool accounting (host bookkeeping per executed call)
    # ------------------------------------------------------------------
    def _pool_step(self, stats, kind: str):
        """Replay one call's per-layer activated slots (the router's
        ``slot_hist``) through the expert pool: acquire/release each
        MoE layer's pages in sequence, exactly the order the forward
        pass touches them.  Returns (stats + pool counters, the
        accessed page ids in layer order — the next step's prefetch
        plan)."""
        pool = self.expert_pool
        if pool is None:
            return stats, []
        sh = np.asarray(stats["slot_hist"])
        assert sh.shape == (pool.n_layers, pool.n_slots), sh.shape
        hits = misses = planned = miss_b = 0
        accessed: list[int] = []
        for li in range(sh.shape[0]):
            pids = [pool.page_id(li, int(s))
                    for s in np.nonzero(sh[li] > 0)[0]]
            res = pool.acquire(pids, kind=kind)
            pool.release(pids)
            hits += res["hits"]
            misses += res["misses"]
            planned += res["planned_hits"]
            miss_b += res["miss_bytes"]
            accessed.extend(pids)
        stats = dict(stats)
        stats.update(pool_hits=float(hits), pool_misses=float(misses),
                     pool_planned_hits=float(planned),
                     pool_miss_bytes=float(miss_b))
        return stats, accessed

    def _pool_plan(self, stats, pids, kind: str):
        """Install step t's accessed pages as step t+1's prefetch plan
        and charge the overlapped fetch bytes to this call's stats."""
        pool = self.expert_pool
        if pool is None:
            return stats
        pref = pool.plan_prefetch(pids, kind=kind)
        stats["pool_prefetch_bytes"] = float(pref)
        return stats

    # ------------------------------------------------------------------
    # step execution (timed; SLO attribution stays in the façade)
    # ------------------------------------------------------------------
    def run_decode(self, drows: list[Request], bucket: int, kvman):
        tokens, pos, slot_idx, pt = self.decode_inputs(drows, bucket,
                                                       kvman)
        fn = self.decode_fn(bucket)
        t0 = time.perf_counter()
        nxt, self.cache, stats = fn(
            self.params, tokens, pos, slot_idx, pt, self.cache,
            self.routing)
        nxt = np.asarray(nxt)
        wall = time.perf_counter() - t0
        stats, pids = self._pool_step(stats, "decode")
        stats = self._pool_plan(stats, pids, "decode")
        return nxt, stats, wall

    def run_chunk(self, pwork: list[tuple[Request, int]], bp: int, kvman):
        toks, start, n_tok, slot_idx, pt = self.chunk_inputs(pwork, bp,
                                                             kvman)
        fn = self.chunk_fn(bp)
        t0 = time.perf_counter()
        self.cache, stats = fn(self.params, toks, start, n_tok,
                               slot_idx, pt, self.cache, self.routing)
        jax.block_until_ready(stats)
        wall = time.perf_counter() - t0
        stats, pids = self._pool_step(stats, "chunk")
        stats = self._pool_plan(stats, pids, "chunk")
        return stats, wall

    def run_mixed(self, pwork: list[tuple[Request, int]],
                  drows: list[Request], bp: int, bd: int, kvman):
        p_toks, p_start, p_ntok, p_slot, p_pt = \
            self.chunk_inputs(pwork, bp, kvman)
        # decode inputs are computed AFTER the chunk advances each
        # finishing row, so build them from the planned post-chunk state
        d_toks, d_pos, d_slot, d_pt = self.decode_inputs(drows, bd, kvman)
        fn = self.mixed_fn(bp, bd)
        t0 = time.perf_counter()
        nxt, self.cache, st_p, st_d = fn(
            self.params, p_toks, p_start, p_ntok, p_slot, p_pt,
            d_toks, d_pos, d_slot, d_pt, self.cache, self.routing)
        nxt = np.asarray(nxt)
        wall = time.perf_counter() - t0
        st_p, pids_p = self._pool_step(st_p, "chunk")
        st_d, pids_d = self._pool_step(st_d, "decode")
        # plan: decode pages first (they gate the next decode step),
        # then the chunk's, deduplicated preserving order
        plan = list(dict.fromkeys(pids_d + pids_p))
        st_d = self._pool_plan(st_d, plan, "decode")
        return nxt, st_p, st_d, wall

    def run_wave(self, group: list[Request], lens: list[int], kvman):
        ecfg = self.ecfg
        ctxs = [r.context_tokens() for r in group]
        b = _pow2(len(group))
        l_pad = min(max(_pow2(max(lens)), 8), ecfg.max_len)
        pmax = pages_for(ecfg.max_len, ecfg.page_size)
        toks = np.zeros((b, l_pad), np.int32)
        lengths = np.zeros((b,), np.int32)
        slot_idx = np.full((b,), ecfg.max_batch, np.int32)  # OOB = pad row
        pt = np.full((b, pmax), -1, np.int32)
        for i, r in enumerate(group):
            toks[i, :lens[i]] = ctxs[i][:lens[i]]
            lengths[i] = lens[i]
            slot_idx[i] = r.slot
        if kvman is not None:
            pt[:len(group)] = kvman.rows([r.slot for r in group])
        fn = self.prefill_fn(b, l_pad)
        t0 = time.perf_counter()
        self.cache, stats = fn(
            self.params, jnp.asarray(toks), jnp.asarray(lengths),
            jnp.asarray(slot_idx), jnp.asarray(pt), self.cache,
            self.routing)
        jax.block_until_ready(stats)
        wall = time.perf_counter() - t0
        stats, pids = self._pool_step(stats, "prefill")
        stats = self._pool_plan(stats, pids, "prefill")
        return stats, wall
