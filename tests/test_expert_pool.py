"""Expert-weight pool lockdown: allocator discipline, activation-aware
prefetch, the paged megakernel, and engine-level bit-identity.

The subsystem's one non-negotiable claim mirrors the prefix cache's:
paging expert weights through a capacity-limited HBM frame pool
changes *nothing* observable about a serve — generated tokens are
bitwise identical to the all-resident run.  The pool is bookkeeping
plus virtual-time cost (a fetch always completes before use), so
residency may never leak into the math.

Fast half: page/frame allocator mechanics (LRU eviction order, pin
blocks eviction, release-keeps-resident, rebalance invalidation,
capacity floor), prefetch plan/depth/gate split, a hypothesis fuzz of
the acquire/release/plan/flush/invalidate lifecycle with
``check_consistent`` after every op, the one-step-ahead prefetch
oracle (coverage == 1.0), and the paged double-buffered megakernel's
numerics (permuted frame maps, interior dead tiles, all-dead grids).

Slow half: engine-level parity (capacity-limited pool vs no pool,
moe_impl="fused_paged" vs "ragged") through the real serving engine.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.serving.expert_pool import (ExpertPagePool, build_expert_pool,
                                       expert_page_bytes, moe_layer_count)

PB = 64          # page_bytes used by the unit-test pools


def _pool(n_layers=2, n_slots=4, num_frames=6, depth=8):
    return ExpertPagePool(n_layers=n_layers, n_slots=n_slots,
                          page_bytes=PB, num_frames=num_frames,
                          prefetch_depth=depth)


# ======================================================================
# fast: allocator mechanics
# ======================================================================


@pytest.mark.fast
class TestPoolAllocator:
    def test_page_geometry_from_config(self):
        cfg = get_config("mixtral-8x22b").reduced()
        n_up = 2 if cfg.gated_mlp else 1
        want = (cfg.d_model * n_up * cfg.expert_hidden
                + cfg.expert_hidden * cfg.d_model) * 2
        assert expert_page_bytes(cfg) == want
        kinds = cfg.layer_kinds()
        n_moe = sum(1 for _, f in kinds if f == "moe")
        assert moe_layer_count(cfg) == \
            (cfg.num_layers // len(kinds)) * n_moe

    def test_acquire_release_keeps_page_resident(self):
        pool = _pool()
        pid = pool.page_id(0, 1)
        res = pool.acquire([pid])
        assert res == {"hits": 0, "misses": 1, "planned_hits": 0,
                       "miss_bytes": PB}
        pool.release([pid])
        # unlike KV release, the page stays cached until eviction
        assert pool.resident(pid)
        assert pool.acquire([pid])["hits"] == 1
        pool.release([pid])
        pool.check_consistent()

    def test_lru_evicts_least_recently_touched(self):
        pool = _pool(n_layers=2, n_slots=4, num_frames=4)
        pids = [pool.page_id(0, s) for s in range(4)]
        pool.acquire(pids)
        pool.release(pids)
        # retouch everything except pids[1] -> it is the LRU victim
        pool.acquire([pids[0], pids[2], pids[3]])
        pool.release([pids[0], pids[2], pids[3]])
        # the pool is full: fetching a layer-1 page must evict pids[1]
        other = pool.page_id(1, 0)
        pool.acquire([other])
        pool.release([other])
        assert pool.evictions == 1
        assert not pool.resident(pids[1])
        assert all(pool.resident(p) for p in (pids[0], pids[2],
                                              pids[3], other))
        pool.check_consistent()

    def test_eviction_skips_pinned_frames(self):
        pool = _pool(n_layers=2, n_slots=3, num_frames=3)
        pids = [pool.page_id(0, s) for s in range(3)]
        pool.acquire(pids)                  # all three frames pinned
        other = pool.page_id(1, 0)
        with pytest.raises(RuntimeError):
            pool.acquire([other])           # nothing evictable
        pool.release([pids[0]])             # one unpinned
        pool.acquire([other])               # evicts exactly pids[0]
        assert not pool.resident(pids[0])
        assert pool.resident(pids[1]) and pool.resident(pids[2])
        assert pool.evictions == 1
        pool.release([other, pids[1], pids[2]])
        pool.check_consistent()

    def test_capacity_floor_one_layer_slot_set(self):
        with pytest.raises(AssertionError):
            ExpertPagePool(n_layers=2, n_slots=4, page_bytes=PB,
                           num_frames=3)
        # build_expert_pool floors a too-small budget at n_slots frames
        cfg = get_config("mixtral-8x22b").reduced()

        class ECfg:
            hbm_budget_bytes = 1            # absurdly small
            pool_h2d_bw = 1.6e10
            prefetch_depth = 8

        pool = build_expert_pool(cfg, ECfg, n_slots=12)
        assert pool.num_frames == 12

    def test_all_resident_when_budget_zero(self):
        cfg = get_config("mixtral-8x22b").reduced()

        class ECfg:
            hbm_budget_bytes = 0
            pool_h2d_bw = 1.6e10
            prefetch_depth = 8

        pool = build_expert_pool(cfg, ECfg, n_slots=12)
        assert pool.num_frames == pool.total_pages
        # every page fetches once (compulsory) and never evicts
        all_pids = list(range(pool.total_pages))
        pool.acquire(all_pids)
        pool.release(all_pids)
        assert pool.acquire(all_pids)["misses"] == 0
        pool.release(all_pids)
        assert pool.evictions == 0
        pool.check_consistent()

    def test_invalidate_slots_drops_all_layers(self):
        pool = _pool(n_layers=2, n_slots=4, num_frames=8)
        pids = [pool.page_id(li, s) for li in range(2) for s in range(4)]
        pool.acquire(pids)
        pool.release(pids)
        dropped = pool.invalidate_slots([1, 3])
        assert dropped == 4                 # 2 slots x 2 layers
        for li in range(2):
            assert not pool.resident(pool.page_id(li, 1))
            assert not pool.resident(pool.page_id(li, 3))
            assert pool.resident(pool.page_id(li, 0))
        assert pool.invalidations == 4
        pool.check_consistent()

    def test_invalidate_pinned_page_asserts(self):
        pool = _pool(n_layers=1, n_slots=2, num_frames=2)
        pid = pool.page_id(0, 0)
        pool.acquire([pid])
        with pytest.raises(AssertionError):
            pool.invalidate_slots([0])


@pytest.mark.fast
class TestPrefetchPlan:
    def test_depth_splits_prefetch_and_gate(self):
        pool = _pool(n_layers=2, n_slots=4, num_frames=8, depth=2)
        pids = [pool.page_id(0, s) for s in range(4)]
        issued = pool.plan_prefetch(pids)
        assert issued == 2 * PB             # depth caps overlapped DMA
        assert pool.prefetch_bytes == 2 * PB
        gate = pool.flush_pending()
        assert gate == 2 * PB               # the deferred remainder
        assert pool.gate_bytes == 2 * PB
        assert all(pool.resident(p) for p in pids)
        # a second flush is a no-op
        assert pool.flush_pending() == 0
        pool.check_consistent()

    def test_planned_hit_counts_even_when_not_resident(self):
        pool = _pool(n_layers=1, n_slots=4, num_frames=4, depth=1)
        pids = [pool.page_id(0, s) for s in range(3)]
        pool.plan_prefetch(pids)            # only pids[0] fetched
        res = pool.acquire(pids)
        # all three were planned (coverage), two still missed
        assert res["planned_hits"] == 3
        assert res["misses"] == 2
        pool.release(pids)
        assert pool.prefetch_coverage == 1.0
        assert pool.hit_rate == pytest.approx(1 / 3)
        pool.check_consistent()

    def test_depth_zero_disables_planning(self):
        pool = _pool(depth=0)
        pids = [pool.page_id(0, s) for s in range(4)]
        assert pool.plan_prefetch(pids) == 0
        assert pool.flush_pending() == 0
        assert pool.prefetch_bytes == 0 and pool.gate_bytes == 0
        res = pool.acquire(pids)            # everything demand-misses
        assert res["misses"] == 4 and res["planned_hits"] == 0
        pool.release(pids)
        pool.check_consistent()

    def test_oracle_router_one_step_ahead(self):
        """When step t's plan names exactly step t+1's accesses and
        depth is ample, coverage is 1.0 and nothing misses or gates
        after the warmup step."""
        rng = np.random.default_rng(0)
        pool = _pool(n_layers=2, n_slots=6, num_frames=12, depth=64)
        trace = [sorted(rng.choice(12, size=4, replace=False))
                 for _ in range(20)]
        # warmup: step 0 has no plan yet
        pool.acquire(trace[0])
        pool.release(trace[0])
        pool.plan_prefetch(trace[1])
        warm_misses = pool.misses
        for t in range(1, len(trace)):
            assert pool.flush_pending() == 0, "ample depth never gates"
            res = pool.acquire(trace[t])
            assert res["misses"] == 0, f"step {t} missed under oracle"
            assert res["planned_hits"] == len(trace[t])
            pool.release(trace[t])
            if t + 1 < len(trace):
                pool.plan_prefetch(trace[t + 1])
            pool.check_consistent()
        assert pool.misses == warm_misses
        assert pool.prefetch_coverage == pytest.approx(
            (pool.accesses - len(trace[0])) / pool.accesses)
        assert pool.gate_bytes == 0

    def test_bytes_by_kind_ledger(self):
        pool = _pool(n_layers=1, n_slots=4, num_frames=4, depth=1)
        pool.acquire([0], kind="chunk")
        pool.release([0])
        pool.plan_prefetch([1, 2], kind="decode")
        pool.flush_pending(kind="decode")
        pool.acquire([3], kind="decode")
        pool.release([3])
        c = pool.counters()
        assert c["bytes_by_kind"]["chunk"]["miss"] == PB
        assert c["bytes_by_kind"]["decode"]["prefetch"] == PB
        assert c["bytes_by_kind"]["decode"]["gate"] == PB
        assert c["bytes_by_kind"]["decode"]["miss"] == PB
        assert c["h2d_bytes"] == 4 * PB


# ======================================================================
# fast: hypothesis fuzz of the page lifecycle
# ======================================================================


@pytest.mark.fast
class TestPoolLifecycleFuzz:
    def test_invariants_hold_under_random_ops(self):
        pytest.importorskip("hypothesis")
        from hypothesis import given, settings, strategies as st

        @given(st.data())
        @settings(deadline=None)
        def prop(data):
            n_layers = data.draw(st.integers(1, 3))
            n_slots = data.draw(st.integers(2, 5))
            total = n_layers * n_slots
            frames = data.draw(st.integers(n_slots, total))
            pool = ExpertPagePool(n_layers=n_layers, n_slots=n_slots,
                                  page_bytes=8, num_frames=frames,
                                  prefetch_depth=data.draw(
                                      st.integers(0, 4)))
            pinned = []                     # acquired, not yet released
            n_ops = data.draw(st.integers(5, 40))
            for _ in range(n_ops):
                ops = ["step", "plan", "flush"]
                if not pinned:
                    ops.append("invalidate")
                op = data.draw(st.sampled_from(ops))
                if op == "step":
                    # one layer's access set, like the executor: at most
                    # n_slots pages pinned at once, released same step
                    li = data.draw(st.integers(0, n_layers - 1))
                    k = data.draw(st.integers(0, n_slots))
                    slots = data.draw(st.permutations(range(n_slots)))
                    pids = [pool.page_id(li, s) for s in slots[:k]]
                    res = pool.acquire(pids)
                    assert res["hits"] + res["misses"] == len(pids)
                    pool.check_consistent()
                    pool.release(pids)
                elif op == "plan":
                    k = data.draw(st.integers(0, total))
                    pids = data.draw(st.permutations(range(total)))[:k]
                    pool.plan_prefetch(list(pids))
                elif op == "flush":
                    pool.flush_pending()
                elif op == "invalidate":
                    k = data.draw(st.integers(0, n_slots))
                    slots = data.draw(
                        st.permutations(range(n_slots)))[:k]
                    pool.invalidate_slots(list(slots))
                pool.check_consistent()
            # ledger closes: every fetched byte is accounted to a kind
            c = pool.counters()
            by_kind = sum(sum(v.values())
                          for v in c["bytes_by_kind"].values())
            assert by_kind == c["h2d_bytes"]
            assert (pool.refcount == 0).all()

        prop()


# ======================================================================
# fast: paged double-buffered megakernel numerics
# ======================================================================


def _ffn_oracle(x, wu, wd, tile_group, tile, fe, gated):
    out = np.zeros((len(tile_group) * tile, wd.shape[2]), np.float32)
    for i, g in enumerate(tile_group):
        if g < 0:
            continue
        xt = np.asarray(x[i * tile:(i + 1) * tile])
        h = (xt @ np.asarray(wu[g])).astype(np.float32)
        if gated:
            act = np.asarray(jax.nn.silu(h[:, :fe])) * h[:, fe:]
        else:
            act = np.asarray(jax.nn.gelu(h))
        out[i * tile:(i + 1) * tile] = \
            act.astype(np.float32) @ np.asarray(wd[g])
    return out


@pytest.mark.fast
class TestPagedKernel:
    D, FE, S, TILE = 16, 24, 5, 4

    def _weights(self, gated, seed=3):
        rng = np.random.default_rng(seed)
        n_up = 2 if gated else 1
        wu = jnp.asarray(rng.normal(size=(self.S, self.D, n_up * self.FE))
                         * 0.1, jnp.float32)
        wd = jnp.asarray(rng.normal(size=(self.S, self.FE, self.D))
                         * 0.1, jnp.float32)
        return rng, wu, wd

    def test_matches_oracle_arbitrary_dead_patterns(self):
        from repro.kernels.moe_ffn import fused_expert_ffn_paged_pallas
        for gated in (True, False):
            rng, wu, wd = self._weights(gated)
            fm = jnp.arange(self.S, dtype=jnp.int32)
            for tg_l in ([0, 3, 3, -1, 1], [2], [0, 1, 2, 3, 4],
                         [1, -1, 1], [-1, 0]):
                tg = jnp.asarray(tg_l, jnp.int32)
                x = jnp.asarray(rng.normal(
                    size=(len(tg_l) * self.TILE, self.D)), jnp.float32)
                got = fused_expert_ffn_paged_pallas(
                    x, wu, wd, fm, tg, gated=gated)
                want = _ffn_oracle(x, wu, wd, tg_l, self.TILE, self.FE,
                                   gated)
                np.testing.assert_allclose(np.asarray(got), want,
                                           rtol=1e-5, atol=1e-5)
                dead = np.repeat(np.asarray(tg_l) < 0, self.TILE)
                assert np.all(np.asarray(got)[dead] == 0)

    def test_permuted_frame_map(self):
        """Physical frame placement is the pool's business: permuting
        the frames and inverting the map must not change the output."""
        from repro.kernels.moe_ffn import fused_expert_ffn_paged_pallas
        rng, wu, wd = self._weights(True)
        tg = jnp.asarray([0, 4, 2, -1], jnp.int32)
        x = jnp.asarray(rng.normal(size=(4 * self.TILE, self.D)),
                        jnp.float32)
        ident = fused_expert_ffn_paged_pallas(
            x, wu, wd, jnp.arange(self.S, dtype=jnp.int32), tg,
            gated=True)
        perm = rng.permutation(self.S)
        fm = jnp.asarray(np.argsort(perm), jnp.int32)  # slot -> frame
        got = fused_expert_ffn_paged_pallas(x, wu[perm], wd[perm], fm,
                                            tg, gated=True)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(ident))

    def test_all_dead_grid_is_exact_zeros(self):
        from repro.kernels.moe_ffn import fused_expert_ffn_paged_pallas
        rng, wu, wd = self._weights(True)
        tg = jnp.asarray([-1, -1, -1], jnp.int32)
        x = jnp.asarray(rng.normal(size=(3 * self.TILE, self.D)),
                        jnp.float32)
        got = fused_expert_ffn_paged_pallas(
            x, wu, wd, jnp.arange(self.S, dtype=jnp.int32), tg,
            gated=True)
        assert not np.asarray(got).any()

    def test_trailing_dead_matches_automatic_pipeline(self):
        """On the layouts build_pair_buffer guarantees (trailing dead
        tiles), the paged kernel is bitwise-equal to the automatic-
        pipeline fused kernel."""
        from repro.kernels.moe_ffn import (fused_expert_ffn_paged_pallas,
                                           fused_expert_ffn_pallas)
        for gated in (True, False):
            rng, wu, wd = self._weights(gated)
            for tg_l in ([0, 2, 2, -1, -1], [3, -1], [1, 4, 0]):
                tg = jnp.asarray(tg_l, jnp.int32)
                x = jnp.asarray(rng.normal(
                    size=(len(tg_l) * self.TILE, self.D)), jnp.float32)
                a = fused_expert_ffn_pallas(
                    x, wu.reshape(self.S, self.D, -1, self.FE), wd, tg,
                    gated=gated)
                b = fused_expert_ffn_paged_pallas(
                    x, wu, wd, jnp.arange(self.S, dtype=jnp.int32), tg,
                    gated=gated)
                np.testing.assert_array_equal(np.asarray(a),
                                              np.asarray(b))


# ======================================================================
# slow: engine-level bit-identity through the real serving stack
# ======================================================================


@pytest.mark.slow
class TestEnginePoolParity:
    def _serve(self, **kw):
        from benchmarks.bench_moe_kernels import serve_tokens
        return serve_tokens(**kw)

    def test_capacity_limited_pool_tokens_identical(self):
        """A pool holding one layer's slot set (full thrash, constant
        eviction) must serve bit-identical tokens to no pool at all."""
        from repro.serving import EngineConfig, ServingEngine
        from repro.core import build_placement, slots_for_ratio
        from repro.models import init_lm
        from repro.sharding.policy import make_dist
        cfg = get_config("mixtral-8x22b").reduced()
        ep = 4
        spd = slots_for_ratio(cfg.num_experts, ep, 1.25)
        dist = make_dist(None, ep_size=ep, slots_per_device=spd)
        placement = build_placement(cfg.num_experts, ep, spd)
        params = init_lm(cfg, jax.random.PRNGKey(0), dist,
                         replica_expert=placement.replica_expert)

        def serve(**pool_kw):
            eng = ServingEngine(cfg, dist, params, EngineConfig(
                max_batch=4, max_len=64, moe_impl="ragged",
                decode_algo="metro", rebalance_every=0, **pool_kw))
            rng = np.random.default_rng(7)
            for n in (5, 9, 3):
                eng.submit(rng.integers(0, cfg.vocab_size, n), 4)
            eng.run()
            return ({r: tuple(q.generated)
                     for r, q in eng.completed.items()}, eng)

        base, _ = serve()
        tight = expert_page_bytes(cfg) * dist.num_slots
        toks, eng = serve(expert_pool=True, hbm_budget_bytes=tight,
                          prefetch_depth=4)
        assert toks == base
        pool = eng.expert_pool
        assert pool.num_frames < pool.total_pages  # capacity-limited
        assert pool.evictions > 0                  # it really thrashed
        pool.check_consistent()
        s = eng.slo.summary()
        assert s["expert_pool_hits"] == pool.hits
        assert s["expert_pool_misses"] == pool.misses
        assert 0.0 < s["expert_pool_hit_rate"] < 1.0

    def test_fused_paged_datapath_token_parity(self):
        """moe_impl="fused_paged" (the double-buffered DMA megakernel)
        serves the same tokens as the ragged reference datapath."""
        a = self._serve(impl="ragged", prompt_lens=(5, 9))
        b = self._serve(impl="fused_paged", prompt_lens=(5, 9))
        assert a == b
