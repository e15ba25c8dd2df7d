"""Continuous-batching serving engine: thin façade over the layered
serving stack.

The paper's real-system setting (§VI-A): prefill and decode co-deployed,
EPLB expert placement/replication as the fixed substrate, token routing
selectable per phase — METRO for the memory-bound decode phase, EPLB's
round-robin for prefill (exactly the paper's deployment).

The engine is decomposed into three layers this module only wires
together (see serving/README.md for the full diagram):

  * :mod:`repro.serving.state`      — ``Request`` + ``EngineState``:
    admission queue, slot/page residency, expert-load EWMA.  No policy.
  * :mod:`repro.serving.scheduler`  — ``Scheduler``: admission with
    skip-ahead, chunk planning under the token budget, preemption,
    pow2 bucket policy with compile grace, and the rebalance window
    (deferred while chunked prefills are in flight).  No jax.
  * :mod:`repro.serving.executor`   — ``Executor``: the jit cache and
    decode/prefill/chunk/mixed step builders, input packing, the KV
    cache pytree (``kv_dtype``: bf16/fp32/fp8 paged pools), the CoW
    page copy, and the EPLB placement + routing tables + compute-dtype
    weights the rebalance loop reshuffles on the device.  No scheduling.

Below state sits the paged-KV substrate: :mod:`repro.serving.kv`
(refcounted pages) and :mod:`repro.serving.prefix` (the shared-prefix
radix cache — ``enable_prefix_cache``): admission starts a request's
prefill at its longest cached prefix, sharing full pages read-only and
copy-on-writing the boundary page; a prefix-hit request's tokens and
logical KV are bitwise the cold run's (tests/test_prefix_cache.py).

:class:`ServingEngine` keeps the public surface of the former monolith
(``submit`` / ``step`` / ``run``, plus ``queue`` / ``active`` /
``completed`` / ``kvman`` / ``cache`` / ``free_slots`` delegations), so
every PR-2 equivalence suite runs unmodified against the refactor.  One
engine is one replica; :mod:`repro.serving.cluster` runs N of them
behind a router with a shared EPLB placement.

Engine loop per iteration (vLLM/sarathi-style):
  1. admit waiting requests into free slots (skip-ahead past a
     page-blocked head request under chunked prefill).
  2. plan this iteration's prefill chunks (``prefill_chunk`` per row,
     ``mixed_prefill_budget`` global token cap).
  3. run the step: ONE fused mixed call when both phases have rows and
     ``mixed_steps``; otherwise chunk call + bucketed decode call
     back-to-back with the chunk time attributed as decode stall.
  4. retire finished requests; when the rebalance window fires (and no
     chunked prefill is in flight), recompute EPLB placement from the
     observed expert-load EWMA and reshuffle the physical weights.

Every equivalence is pinned bit-for-bit by the test harness:
  * any chunk split == one monolithic chunk call (logits + KV pages),
    tests/test_chunked_prefill.py;
  * mixed fused step == pure-phase chunk-then-decode sequence
    (tokens + per-call expert_hist), tests/test_mixed_steps.py;
  * preempt-between-chunks + readmission == never-preempted run,
    tests/test_mixed_steps.py;
  * rebalance mid-prefill == no rebalance at all (tokens + hist),
    tests/test_cluster.py;
  * single-replica ClusterEngine == bare ServingEngine,
    tests/test_cluster.py.

Timing is injectable for cluster simulation: pass a
:class:`repro.serving.slo.VirtualClock` plus a ``step_cost(kind,
n_tokens, stats) -> seconds`` model and every step advances virtual
time by the modeled cost (decode cost driven by ``max_activated`` — the
paper's memory-bound quantity) instead of wall time, making
multi-replica SLO sweeps bit-reproducible on CPU.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Optional

import numpy as np

from repro.configs.base import ModelConfig
from repro.serving.executor import Executor
from repro.serving.scheduler import Scheduler, _pow2
from repro.serving.slo import SLOTracker, VirtualClock
from repro.serving.state import EngineState, Request
from repro.sharding.policy import Dist

__all__ = ["EngineConfig", "ServingEngine", "Request"]


@dataclasses.dataclass
class EngineConfig:
    max_batch: int = 8          # decode slots
    max_len: int = 256          # KV capacity per sequence
    replication_ratio: float = 1.25
    decode_algo: str = "metro"  # the paper's technique
    prefill_algo: str = "eplb"
    rebalance_every: int = 64   # decode steps between EPLB rebalances
    rebalance_defer_prefill: bool = True    # hold a due rebalance until
                                # no chunked prefill is in flight
                                # (bounded: forced after one extra
                                # window so load can't starve it)
    load_ewma: float = 0.9
    prefill_chunk: int = 64     # tokens per prefill chunk
    greedy: bool = True
    seed: int = 0
    # --- scheduling ---
    bucket_mode: str = "pow2"   # "pow2" | "fixed" (seed: pad to max_batch)
    batch_prefill: bool = True  # (wave mode) pack the wave into one call
    max_wave: int = 0           # prefill wave cap; 0 -> max_batch
    bucket_compile_grace: int = 4   # steps a cold bucket rounds up to a
                                    # compiled one before earning its own
                                    # compile (0 = always compile exact)
    # --- chunked / mixed prefill ---
    prefill_mode: str = "chunked"   # "chunked" | "wave" (seed monolith)
    mixed_prefill_budget: int = 0   # max prefill tokens per iteration
                                    # (0 = every prefilling row advances
                                    # one full chunk per iteration)
    mixed_steps: bool = True        # fuse prefill chunks + decode into
                                    # one call when both phases have rows
    # --- KV layout ---
    kv_layout: str = "paged"    # "paged" | "dense" (seed layout)
    page_size: int = 16         # tokens per KV page
    num_pages: int = 0          # pool size; 0 -> full residency
                                #   (max_batch * ceil(max_len/page_size))
    kv_dtype: str = "bf16"      # paged pool element type: "bf16" |
                                # "fp32" | "fp8" (fp8 halves KV residency;
                                # paged reads dequantize in-path — paged
                                # layout only)
    # --- prefix cache (shared-prefix KV reuse) ---
    enable_prefix_cache: bool = False   # radix prefix index over the
                                # paged pool + copy-on-write boundary
                                # pages (chunked+paged only; mamba-
                                # bearing archs auto-disable — SSM state
                                # is not paged)
    prefix_min_tokens: int = 1  # shortest cached match worth taking
                                # (a 1-token hit still costs a CoW copy)
    admit_reserve_frac: float = 0.0     # page-aware admission headroom:
                                # fraction of a request's future page
                                # demand held back, decayed by queue
                                # depth (0 = PR-2's plain first-chunk
                                # gate)
    # --- kernels ---
    use_flash_kernel: bool = False  # paged decode attention through the
                                    # Pallas flash_decode_paged kernel
                                    # (full-attention layers; SWA keeps
                                    # the gather reference)
    moe_impl: str = "ragged"    # grouped expert-FFN datapath:
                                # "ragged" | "scan_tiles" | "onehot" |
                                # "pallas" (two-pass Pallas kernel) |
                                # "fused" (one-pass up→act→down Pallas
                                # megakernel, hidden stays in VMEM) |
                                # "fused_paged" (fused + explicit
                                # double-buffered weight DMA from a
                                # frame pool) — see kernels/README.md
    use_pallas_route: bool = False  # METRO Alg. 1 greedy routing on the
                                    # Pallas scalar-core kernel instead
                                    # of the lax.scan reference
    # --- expert-weight paging (MoE models bigger than HBM) ---
    expert_pool: bool = False   # page per-(layer, slot) expert weights
                                # between a host backing store and a
                                # bounded HBM frame pool, with
                                # activation-aware prefetch from the
                                # router's previous step (MoE archs
                                # only; ignored otherwise)
    hbm_budget_bytes: int = 0   # expert-weight HBM budget per replica;
                                # 0 = every page resident (compulsory
                                # misses only).  Floored at one layer's
                                # slot set — the activated working set
                                # a single layer pins
    prefetch_depth: int = 8     # pages the prefetcher may fetch
                                # overlapped per step; the rest of the
                                # plan waits for the decode residency
                                # gate (attributed as decode stall)
    pool_h2d_bw: float = 1.6e10     # modeled host->HBM bandwidth
                                # (bytes/s) for miss/gate stall
                                # attribution and the roofline model


class ServingEngine:
    def __init__(self, cfg: ModelConfig, dist: Dist, params,
                 ecfg: EngineConfig, routing_table_width: int = 0,
                 clock: Optional[VirtualClock] = None,
                 step_cost: Optional[Callable] = None,
                 fn_cache: Optional[dict] = None):
        assert ecfg.bucket_mode in ("pow2", "fixed"), ecfg.bucket_mode
        assert ecfg.kv_layout in ("paged", "dense"), ecfg.kv_layout
        assert ecfg.prefill_mode in ("chunked", "wave"), ecfg.prefill_mode
        assert ecfg.kv_dtype in ("bf16", "fp32", "fp8"), ecfg.kv_dtype
        assert ecfg.moe_impl in ("ragged", "scan_tiles", "onehot",
                                 "pallas", "fused",
                                 "fused_paged"), ecfg.moe_impl
        assert ecfg.hbm_budget_bytes >= 0 and ecfg.prefetch_depth >= 0
        assert ecfg.kv_dtype == "bf16" or ecfg.kv_layout == "paged", \
            "kv_dtype plumbing is paged-path only"
        self.cfg = cfg
        self.dist = dist
        self.ecfg = ecfg
        self._vclock = clock
        self.step_cost = step_cost
        assert step_cost is None or clock is not None, \
            "a step_cost model needs a VirtualClock to advance"
        self.slo = SLOTracker(clock=clock.now if clock else None)
        # chunked prefill needs the paged pool (attention chunks resume
        # against already-written pages); dense layout keeps the seed's
        # monolithic wave path.
        self.chunked = (ecfg.prefill_mode == "chunked"
                        and ecfg.kv_layout == "paged")
        # prefix reuse needs resumable chunked prefill over the paged
        # pool, and every mixer's state must live in pages — mamba's
        # per-slot SSM state can't be rejoined at an arbitrary match
        # point, so mamba-bearing archs auto-disable (documented in
        # serving/prefix.py)
        self.prefix_enabled = bool(
            ecfg.enable_prefix_cache and self.chunked
            and cfg.family != "encdec"
            and all(mixer != "mamba" for mixer, _ in cfg.layer_kinds()))
        self.state = EngineState(ecfg, cfg.num_experts,
                                 prefix_enabled=self.prefix_enabled)
        self.exec = Executor(cfg, dist, ecfg, params, self.slo,
                             routing_table_width, fn_cache=fn_cache)
        self.sched = Scheduler(ecfg, self.state, self.slo, self.chunked,
                               copy_pages=self.exec.run_copy_pages)

    # ------------------------------------------------------------------
    # state / executor delegation (the monolith's public surface)
    # ------------------------------------------------------------------
    @property
    def queue(self):
        return self.state.queue

    @property
    def active(self):
        return self.state.active

    @property
    def completed(self):
        return self.state.completed

    @property
    def free_slots(self):
        return self.state.free_slots

    @property
    def kvman(self):
        return self.state.kvman

    @property
    def prefix_index(self):
        return self.state.prefix

    @property
    def expert_pool(self):
        return self.exec.expert_pool

    @property
    def decode_steps(self):
        return self.state.decode_steps

    @property
    def expert_loads(self):
        return self.state.expert_loads

    @property
    def expert_hist_log(self):
        return self.state.expert_hist_log

    @property
    def _next_rid(self):
        return self.state.next_rid

    @property
    def cache(self):
        return self.exec.cache

    @property
    def params(self):
        return self.exec.params

    @property
    def routing(self):
        return self.exec.routing

    @property
    def placement(self):
        return self.exec.placement

    @property
    def _fns(self):
        return self.exec._fns

    @property
    def has_work(self) -> bool:
        return self.state.has_work

    def _admit(self):
        return self.sched.admit()

    def prefix_match_len(self, prompt: np.ndarray) -> int:
        """Longest *takeable* cached prefix of ``prompt`` (0 when the
        cache is off or the match is below admission's eligibility bar
        — one shared definition, ``Scheduler.eligible_match``, so
        dispatch can never chase a match admission would refuse) — the
        cluster's prefix-affinity signal.  Pure peek: no LRU update."""
        m = self.sched.eligible_match(prompt)
        return m.m if m is not None else 0

    def _preempt_one(self, protect_rid: int) -> bool:
        return self.sched.preempt_one(protect_rid)

    # ------------------------------------------------------------------
    # virtual time
    # ------------------------------------------------------------------
    def advance_clock_to(self, t: float):
        """Jump an idle replica's virtual clock forward (a server that
        sat idle until an arrival starts working at the arrival time)."""
        if self._vclock is not None:
            self._vclock.t = max(self._vclock.t, t)

    def _charge(self, parts, wall_dt: float) -> float:
        """Convert one engine call into seconds.  Wall time by default;
        under a VirtualClock + step_cost model, the modeled cost of each
        (kind, n_tokens, stats) component, with the clock advanced."""
        if self._vclock is None or self.step_cost is None:
            return wall_dt
        dt = 0.0
        for kind, n_tok, stats in parts:
            dt += self.step_cost(kind, n_tok, {
                k: float(np.asarray(stats.get(k, 0.0)))
                for k in ("max_activated", "mean_activated",
                          "max_tokens", "pool_miss_bytes",
                          "pool_prefetch_bytes", "pool_gate_bytes")})
        self._vclock.advance(dt)
        return dt

    def _pool_slo(self, stats, decode: bool):
        """Fold one engine call's expert-pool hit/miss split into the
        SLO tracker.  Demand-miss bytes on a decode-carrying call are
        a decode stall (the step waited for the fetch); prefetch bytes
        are overlapped and gate bytes were already attributed by the
        scheduler's residency gate."""
        pool = self.exec.expert_pool
        if pool is None or "pool_hits" not in stats:
            return
        miss_b = float(stats.get("pool_miss_bytes", 0.0))
        self.slo.expert_pool_access(
            hits=int(stats["pool_hits"]),
            misses=int(stats["pool_misses"]),
            planned_hits=int(stats["pool_planned_hits"]),
            stall_s=(pool.stall_seconds(miss_b)
                     if decode and miss_b else 0.0))

    # ------------------------------------------------------------------
    # rebalance (EPLB placement + physical weight reshuffle)
    # ------------------------------------------------------------------
    def rebalance(self, placement=None):
        """Recompute EPLB placement from observed loads + reshuffle —
        or install a cluster-shared ``placement`` as-is."""
        self.exec.rebalance(self.state.expert_loads, placement=placement)

    # ------------------------------------------------------------------
    # submission
    # ------------------------------------------------------------------
    def submit(self, prompt: np.ndarray, max_new_tokens: int,
               arrival: Optional[float] = None) -> int:
        """Queue a request.  ``arrival`` back-stamps the arrival time on
        the SLO timeline (virtual-time cluster replay submits at the
        trace arrival, which may precede the replica's local clock)."""
        r = self.state.new_request(prompt, max_new_tokens)
        self.slo.arrive(r.rid, len(r.prompt), at=arrival)
        return r.rid

    # ------------------------------------------------------------------
    # engine iteration
    # ------------------------------------------------------------------
    def step(self):
        """One engine iteration."""
        self.slo.queue_depth(len(self.state.queue))
        admitted = self.sched.admit()
        if not self.chunked:
            # seed scheduler: monolithic wave prefill, then decode all
            if admitted:
                self._prefill_wave(admitted)
            self.sched.reserve(
                [(r, min(r.pos + 1, self.ecfg.max_len))
                 for r in self.state.active.values()])
            self._decode_rows(sorted(self.state.active.values(),
                                     key=lambda r: r.slot))
            return
        self._step_chunked()

    def _step_chunked(self):
        st = self.state
        pwork = self.sched.plan_chunks()
        # decode set: rows already decoding, plus rows whose prefill
        # completes with this iteration's chunk (they re-feed their last
        # context token at position n_ctx, same as the wave scheduler)
        finishing = {r.rid for r, n in pwork if r.pos + n >= r.n_ctx}
        targets = [(r, r.pos + n + (1 if r.rid in finishing else 0))
                   for r, n in pwork]
        targets += [(r, r.pos + 1) for r in st.active.values()
                    if not r.prefilling]
        self.sched.reserve(targets)    # may preempt scheduled rows: filter
        pwork = [(r, n) for r, n in pwork if r.rid in st.active]
        finishing = {r.rid for r, n in pwork if r.pos + n >= r.n_ctx}
        drows = [r for r in st.active.values()
                 if not r.prefilling or r.rid in finishing]
        drows.sort(key=lambda r: r.slot)

        if pwork and drows and self.ecfg.mixed_steps:
            self._mixed_step(pwork, drows)
            return
        if pwork:
            bp = _pow2(len(pwork))
            self._start_chunks(pwork)
            stats, wall = self.exec.run_chunk(pwork, bp, st.kvman)
            dt = self._charge(
                [("chunk", sum(n for _, n in pwork), stats)], wall)
            self.slo.step("chunk", dt)
            if any(r.rid not in finishing for r in drows):
                # pure-phase mode: PRE-EXISTING decode rows sat out the
                # chunk call (rows finishing prefill in this very call
                # were not waiting on anything)
                self.slo.stall("chunk", dt)
            self._update_loads(stats)
            self._pool_slo(stats, decode=False)
            self._finish_chunks(pwork)
        self._decode_rows(drows)

    def _mixed_step(self, pwork: list[tuple[Request, int]],
                    drows: list[Request]):
        """Sarathi-style piggybacked iteration: ONE call runs the chunk
        tokens and the decode tokens, so decode rows never stall behind
        prefill (no ``slo.stall`` is recorded — there is nothing to
        wait for)."""
        bp = _pow2(len(pwork))
        bd = self.sched.bucket(len(drows),
                               self.exec.compiled_buckets("decode"))
        gate_b = self.sched.gate_decode(self.exec.expert_pool)
        self._start_chunks(pwork)
        nxt, st_p, st_d, wall = self.exec.run_mixed(
            pwork, drows, bp, bd, self.state.kvman)
        if gate_b:
            st_d = dict(st_d, pool_gate_bytes=float(gate_b))
        dt = self._charge(
            [("chunk", sum(n for _, n in pwork), st_p),
             ("decode", len(drows), st_d)], wall)
        self.slo.step("mixed", dt)
        # same update order as the pure-phase sequence it replaces
        self._update_loads(st_p)
        self._update_loads(st_d)
        self._pool_slo(st_p, decode=False)
        self._pool_slo(st_d, decode=True)
        self._finish_chunks(pwork)
        self._postprocess_decode(drows, nxt)

    def _decode_rows(self, drows: list[Request]):
        if not drows:
            return
        b = self.sched.bucket(len(drows),
                              self.exec.compiled_buckets("decode"))
        gate_b = self.sched.gate_decode(self.exec.expert_pool)
        nxt, stats, wall = self.exec.run_decode(drows, b,
                                                self.state.kvman)
        if gate_b:
            stats = dict(stats, pool_gate_bytes=float(gate_b))
        dt = self._charge([("decode", len(drows), stats)], wall)
        self.slo.step("decode", dt)
        self._update_loads(stats)
        self._pool_slo(stats, decode=True)
        self._postprocess_decode(drows, nxt)

    # ------------------------------------------------------------------
    # prefill — monolithic wave path (prefill_mode="wave" / dense KV)
    # ------------------------------------------------------------------
    def _prefill_wave(self, wave: list[Request]):
        group_cap = (self.ecfg.max_wave or self.ecfg.max_batch) \
            if self.ecfg.batch_prefill else 1
        for i in range(0, len(wave), group_cap):
            self._prefill_group(wave[i:i + group_cap])

    def _prefill_group(self, group: list[Request]):
        lens = [min(len(r.context_tokens()), self.ecfg.max_len - 1)
                for r in group]
        for r in group:
            self.slo.prefill_started(r.rid)
        stats, wall = self.exec.run_wave(group, lens, self.state.kvman)
        dt = self._charge([("prefill", sum(lens), stats)], wall)
        self.slo.step("prefill", dt)
        gids = {r.rid for r in group}
        if any(not r.prefilling for r in self.state.active.values()
               if r.rid not in gids):
            self.slo.stall("prefill", dt)
        for r, n in zip(group, lens):
            r.pos = n
            self.slo.chunk_done(r.rid)
            self.slo.prefill_done(r.rid)
        self._update_loads(stats)
        self._pool_slo(stats, decode=False)

    # ------------------------------------------------------------------
    # chunk bookkeeping
    # ------------------------------------------------------------------
    def _start_chunks(self, pwork: list[tuple[Request, int]]):
        """Stamp prefill_start BEFORE the chunk-carrying call is issued
        (the wave path does the same), so the first chunk's time lands
        in the TTFT prefill span, not the queue wait.  A prefix-hit
        request starts its first chunk at the match point
        (``admit_pos``), not 0 — the skipped tokens belong to no span."""
        for r, _ in pwork:
            if r.pos == r.admit_pos:
                self.slo.prefill_started(r.rid)

    def _finish_chunks(self, pwork: list[tuple[Request, int]]):
        for r, n in pwork:
            r.pos += n
            self.slo.chunk_done(r.rid)
            if not r.prefilling:
                self.slo.prefill_done(r.rid)

    def _postprocess_decode(self, drows: list[Request], nxt: np.ndarray):
        for i, r in enumerate(drows):
            tok = int(nxt[i])
            if not r.generated:
                self.slo.first_token(r.rid)
            else:
                self.slo.token(r.rid)
            r.generated.append(tok)
            r.pos += 1
            if (len(r.generated) >= r.max_new_tokens
                    or r.pos >= self.ecfg.max_len - 1):
                self.slo.finish(r.rid)
                self.state.retire(r)
        self.state.decode_steps += 1
        if self.cfg.is_moe and self.sched.rebalance_due():
            self.rebalance()

    def _update_loads(self, stats):
        if not self.cfg.is_moe:
            return
        h = np.asarray(stats["expert_hist"])
        if h.shape[0] == self.cfg.num_experts:
            self.state.record_hist(h, self.ecfg.load_ewma)

    # ------------------------------------------------------------------
    def run(self, max_iters: int = 10_000):
        """Run until queue + active drain (or max_iters)."""
        it = 0
        while self.has_work and it < max_iters:
            self.step()
            it += 1
        return self.slo.summary()

    def finished_requests(self):
        return {rid: t for rid, t in self.slo.timings.items()
                if t.finished > 0}
