"""Multi-replica serving: N ``ServingEngine`` replicas behind a router,
with one shared EPLB placement (paper Fig. 9–12 scale).

The paper's cluster deployment co-locates prefill and decode on every
replica, keeps ONE EPLB expert placement/replication substrate for the
whole fleet (recomputed from aggregate load on a common rebalance
window), and lets each replica route tokens per phase (METRO decode /
EPLB prefill).  This module reproduces that shape on simulated
replicas:

  * **Router** — ``dispatch="rr"`` round-robin, ``dispatch="low"``
    least-outstanding-work (queued + active tokens remaining, the
    natural unit for a token-serving fleet), or ``dispatch="prefix"``
    prefix-affinity: the replica whose radix prefix index holds the
    longest cached match of the prompt wins (cache reuse beats load
    balance when a match exists), falling back to least-outstanding
    work.  Deterministic: ties break toward the lowest replica id.
  * **Shared placement** — per-replica expert-load EWMAs are aggregated
    (:func:`repro.core.placement.aggregate_expert_loads`) into one
    cluster signal; one :func:`build_placement` runs; every replica
    reshuffles its physical expert weights to the SAME placement.
    Replica choice moves compute, not math, so the reshuffle is bitwise
    invisible to in-flight requests (pinned by the mid-prefill
    rebalance regression test) — the fleet can reshuffle on a common
    window without draining.
  * **Virtual time** — pass ``step_cost`` and every replica runs on its
    own :class:`~repro.serving.slo.VirtualClock` advanced by the
    modeled cost of each step (decode cost driven by ``max_activated``,
    the paper's memory-bound quantity).  Replica timelines are
    independent — N replicas genuinely serve in parallel — and every
    latency percentile is bit-reproducible on CPU, which is what lets
    ``benchmarks/bench_pareto_slo.py`` binary-search arrival rates.
  * **Compile sharing** — replicas are identical configs, so they share
    one step-function cache: N replicas compile each shape signature
    once, not N times.

A single-replica cluster is *exactly* a bare engine: same tokens, same
per-call expert_hist (tests/test_cluster.py pins this for METRO and
EPLB) — the cluster layer adds dispatch and placement sharing, never
numerics.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.core import aggregate_expert_loads, build_placement
from repro.serving.engine import EngineConfig, ServingEngine
from repro.serving.slo import VirtualClock, aggregate_cluster_summary
from repro.serving.traffic import SyntheticRequest
from repro.sharding.policy import Dist


@dataclasses.dataclass
class ClusterConfig:
    num_replicas: int = 2
    dispatch: str = "low"       # "low" (least outstanding work) | "rr"
                                # | "prefix" (longest cached prefix
                                # match wins; falls back to "low")
    rebalance_every: int = 0    # cluster-wide decode steps between shared
                                # EPLB reshuffles (0 = never)


def default_step_cost(kind: str, n_tokens: int, stats: dict) -> float:
    """Deterministic per-call cost model for virtual-time simulation.

    Decode is the memory-bound phase: per-step latency is dominated by
    streaming the *activated* expert weights from HBM, so the model
    charges the per-device max activated-expert count the step actually
    produced (``stats["max_activated"]``) — exactly the quantity METRO
    minimizes, so the METRO-vs-EPLB gap the Pareto harness measures
    comes from the routing algorithms' real activation decisions, not
    from an assumed constant.  Prefill-carrying calls are modeled
    compute-bound: cost scales with the tokens processed.

    Units are virtual seconds; absolute scale is arbitrary (only
    METRO/EPLB and rate-sweep *comparisons* are claims), chosen so a
    reduced-model replica saturates at O(1e2–1e3) req/s.
    """
    if kind == "decode":
        return 2e-4 + 1.5e-4 * stats["max_activated"] + 1e-5 * n_tokens
    return 2e-4 + 2e-5 * n_tokens


class ClusterEngine:
    def __init__(self, cfg: ModelConfig, dist: Dist, params,
                 ecfg: EngineConfig, ccfg: ClusterConfig,
                 step_cost: Optional[Callable] = default_step_cost,
                 routing_table_width: int = 0,
                 fn_cache: Optional[dict] = None):
        assert ccfg.num_replicas >= 1
        assert ccfg.dispatch in ("low", "rr", "prefix"), ccfg.dispatch
        self.cfg, self.dist = cfg, dist
        self.ccfg = ccfg
        self.step_cost = step_cost
        # the cluster owns the rebalance window; replicas never
        # rebalance locally (they would diverge from the shared
        # placement between windows)
        recfg = dataclasses.replace(ecfg, rebalance_every=0)
        # one jit cache for the whole fleet (identical configs); an
        # external cache may be passed to reuse compiles across
        # clusters of the same config (the Pareto sweep's rate probes)
        if fn_cache is None:
            fn_cache = {"decode": {}, "prefill": {}, "chunk": {},
                        "mixed": {}}
        self.replicas: list[ServingEngine] = []
        for _ in range(ccfg.num_replicas):
            # each replica owns its weights: rebalance rewrites the
            # expert stacks in place (donated buffers), and replicas
            # must be able to hold different physical layouts between
            # cluster windows without aliasing each other
            p_i = jax.tree.map(jnp.copy, params)
            clock = VirtualClock() if step_cost is not None else None
            self.replicas.append(ServingEngine(
                cfg, dist, p_i, recfg, routing_table_width,
                clock=clock, step_cost=step_cost, fn_cache=fn_cache))
        self._rr = 0
        self._rid_map: dict[int, tuple[int, int]] = {}
        self._next_crid = 0
        self._rebalances = 0
        self._last_window = 0
        self.steps = 0

    # ------------------------------------------------------------------
    # router
    # ------------------------------------------------------------------
    def _pick_replica(self, prompt=None) -> int:
        if self.ccfg.dispatch == "rr":
            i = self._rr % len(self.replicas)
            self._rr += 1
            return i
        if self.ccfg.dispatch == "prefix" and prompt is not None:
            # prefix affinity: the replica whose radix index holds the
            # longest cached prefix of this prompt serves it — reuse
            # beats balance when a match exists (the skipped prefill is
            # work no other replica can avoid).  Ties, and the no-match
            # case, fall back to least outstanding work; all ties break
            # to the lowest replica id (deterministic).
            matches = [r.prefix_match_len(prompt) for r in self.replicas]
            best = max(matches)
            if best > 0:
                cand = [i for i, m in enumerate(matches) if m == best]
                return min(cand, key=lambda i: (
                    self.replicas[i].state.outstanding_tokens(), i))
        # least outstanding work; deterministic tie-break on replica id
        return int(np.argmin([r.state.outstanding_tokens()
                              for r in self.replicas]))

    def submit(self, prompt: np.ndarray, max_new_tokens: int,
               arrival: Optional[float] = None) -> int:
        ri = self._pick_replica(prompt)
        rep = self.replicas[ri]
        if arrival is not None and not rep.has_work:
            # an idle server starts working when the request arrives
            rep.advance_clock_to(arrival)
        lrid = rep.submit(prompt, max_new_tokens, arrival=arrival)
        crid = self._next_crid
        self._next_crid += 1
        self._rid_map[crid] = (ri, lrid)
        return crid

    # ------------------------------------------------------------------
    # aggregate views
    # ------------------------------------------------------------------
    @property
    def has_work(self) -> bool:
        return any(r.has_work for r in self.replicas)

    @property
    def completed(self):
        """Completed requests keyed by *cluster* rid."""
        out = {}
        for crid, (ri, lrid) in self._rid_map.items():
            r = self.replicas[ri].completed.get(lrid)
            if r is not None:
                out[crid] = r
        return out

    @property
    def rebalances(self) -> int:
        return self._rebalances

    def replica_of(self, crid: int) -> int:
        return self._rid_map[crid][0]

    def summary(self) -> dict:
        s = aggregate_cluster_summary([r.slo for r in self.replicas])
        s["cluster_rebalances"] = self._rebalances
        return s

    # ------------------------------------------------------------------
    # shared EPLB placement
    # ------------------------------------------------------------------
    def rebalance(self):
        """Aggregate every replica's expert-load EWMA, compute ONE EPLB
        placement from the cluster-wide signal, and reshuffle every
        replica's physical weights to it (the common window)."""
        if not self.cfg.is_moe:
            return
        loads = aggregate_expert_loads(
            [r.expert_loads for r in self.replicas])
        placement = build_placement(
            self.cfg.num_experts, self.dist.ep_size,
            self.dist.slots_per_device, loads=loads)
        for r in self.replicas:
            r.rebalance(placement=placement)
        self._rebalances += 1

    def _maybe_rebalance(self):
        every = self.ccfg.rebalance_every
        if not every or not self.cfg.is_moe:
            return
        total = sum(r.decode_steps for r in self.replicas)
        if total // every > self._last_window:
            self._last_window = total // every
            self.rebalance()

    # ------------------------------------------------------------------
    # stepping
    # ------------------------------------------------------------------
    def step(self):
        """One cluster round: every replica with work runs one engine
        iteration (replicas serve in parallel — under virtual time each
        advances its own clock)."""
        for r in self.replicas:
            if r.has_work:
                r.step()
        self.steps += 1
        self._maybe_rebalance()

    def run(self, max_iters: int = 100_000) -> dict:
        it = 0
        while self.has_work and it < max_iters:
            self.step()
            it += 1
        return self.summary()

    # ------------------------------------------------------------------
    # open-loop replay (the Pareto harness's load loop)
    # ------------------------------------------------------------------
    def replay_open_loop(self, trace: list[SyntheticRequest], *,
                         max_iters: int = 200_000,
                         on_iteration: Optional[Callable] = None) -> dict:
        """Submit each trace request at its arrival time and step the
        cluster in between (virtual time only — for wall-clock single-
        engine replay use :func:`repro.serving.traffic.replay_open_loop`).

        The global frontier is the slowest *busy* replica's clock: a
        request is dispatched once every busy replica has reached its
        arrival (so no replica observes an arrival from its own
        future), idle replicas jump forward to the arrival, and TTFT
        is measured from the back-stamped trace arrival.  The frontier
        is recomputed after every submit — a submit can wake an idle
        replica at the arrival time, which may become the new minimum,
        and later arrivals must not land on a replica whose clock is
        still behind them.

        ``on_iteration(cluster)`` runs after every loop iteration — a
        gauge hook (e.g. the prefix benchmark's pages-in-use peak) so
        callers never have to clone this frontier logic.
        """
        assert self.step_cost is not None, (
            "cluster replay_open_loop needs the virtual-time cost "
            "model (step_cost); wall-clock open-loop replay is the "
            "single-engine repro.serving.traffic.replay_open_loop")
        i, it = 0, 0
        while (i < len(trace) or self.has_work) and it < max_iters:
            while i < len(trace):
                busy = [r for r in self.replicas if r.has_work]
                t = (min(r._vclock.t for r in busy) if busy
                     else trace[i].arrival)
                if trace[i].arrival > t:
                    break
                self.submit(trace[i].prompt, trace[i].max_new_tokens,
                            arrival=trace[i].arrival)
                i += 1
            if self.has_work:
                self.step()
            if on_iteration is not None:
                on_iteration(self)
            it += 1
        return self.summary()
