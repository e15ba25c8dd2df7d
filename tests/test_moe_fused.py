"""Fused expert-FFN megakernel: parity sweeps, dead-tile skip contract,
HBM-traffic/DMA accounting, backend-derived interpret mode, and the
engine-level moe_impl="fused" serve equivalence.

The fused kernel (kernels/moe_ffn.fused_expert_ffn_pallas) runs
up→act→down in one pass with the hidden resident in VMEM; its output
must match the ref.py oracle and the two-pass datapath on live rows and
be exact zeros on dead tiles."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import interpret_mode
from repro.kernels import ref
from repro.kernels.moe_ffn import (_activate, fused_expert_ffn_pallas,
                                   grouped_ffn_pallas)
from repro.models.moe import build_pair_buffer, grouped_matmul
from repro.sim.roofline import expert_ffn_traffic, fused_weight_dma_tiles

try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:
    HAVE_HYPOTHESIS = False


def _build(rng, t, k, s_loc, tile, *, short_capacity=False,
           all_remote=False):
    """Random routing -> pair buffer (optionally capacity-dropping or
    with zero local pairs)."""
    lo_draw = -1 if not all_remote else s_loc
    hi_draw = s_loc + 2
    slots = rng.integers(lo_draw, hi_draw, (t, k)).astype(np.int32)
    if all_remote:
        assert ((slots < 0) | (slots >= s_loc)).all()
    n_local = int(((slots >= 0) & (slots < s_loc)).sum())
    if short_capacity:
        capacity = max(tile, (max(n_local // 2, 1) // tile) * tile)
    else:
        capacity = ((n_local + s_loc * (tile - 1)) // tile + 2) * tile
    bp, gp, tg, nl = jax.jit(
        build_pair_buffer, static_argnames=("s_loc", "capacity", "tile")
    )(jnp.asarray(slots), 0, s_loc=s_loc, capacity=capacity, tile=tile)
    return (np.asarray(bp), np.asarray(gp), np.asarray(tg), int(nl),
            capacity)


def _stored(wu, n_up):
    """[S, d, n_up*fe] -> the stored [S, d, n_up, fe] the kernel reads."""
    s, d, f = wu.shape
    return wu.reshape(s, d, n_up, f // n_up)


def _two_pass_ref(x, wu, wd, tile_group, *, gated):
    """Composite oracle: two grouped_matmul_ref passes + gating, dead
    rows zeroed (grouped_matmul_ref predates the -1 convention)."""
    tile = x.shape[0] // len(tile_group)
    tg = np.maximum(tile_group, 0)
    h = ref.grouped_matmul_ref(x, wu, tg)
    fe = wd.shape[1]
    if gated:
        g, u = h[:, :fe], h[:, fe:]
        h = g / (1.0 + np.exp(-g)) * u
    else:
        h = 0.5 * h * (1.0 + np.tanh(
            np.sqrt(2.0 / np.pi) * (h + 0.044715 * h ** 3)))
    y = ref.grouped_matmul_ref(h, wd, tg)
    y[np.repeat(tile_group, tile) < 0] = 0.0
    return y


class TestFusedKernelParity:
    @pytest.mark.parametrize("gated", [True, False])
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_matches_oracles(self, gated, dtype):
        """Fused == ref oracle == two-pass ref == ragged/onehot impls
        on live rows; exact zeros on dead tiles."""
        rng = np.random.default_rng(0)
        for seed in range(4):
            rng = np.random.default_rng(seed)
            t, k, s_loc = 11, 2, 3
            tile = int(rng.choice([2, 4, 8]))
            bp, gp, tg, nl, capacity = _build(rng, t, k, s_loc, tile)
            d, fe = 16, 24
            n_up = 2 if gated else 1
            x = jnp.asarray(rng.normal(size=(capacity, d)), dtype)
            wu = jnp.asarray(
                rng.normal(size=(s_loc, d, n_up * fe)) * 0.2, dtype)
            wd = jnp.asarray(
                rng.normal(size=(s_loc, fe, d)) * 0.2, dtype)
            got = np.asarray(fused_expert_ffn_pallas(
                x, _stored(wu, n_up), wd, jnp.asarray(tg), gated=gated,
                tile_k_up=8, tile_k_dn=8), np.float32)
            xf, uf, df = (np.asarray(a, np.float32) for a in (x, wu, wd))
            want = ref.fused_expert_ffn_ref(xf, uf, df, tg, gated=gated)
            want2 = _two_pass_ref(xf, uf, df, tg, gated=gated)
            tol = dict(rtol=5e-2, atol=5e-2) \
                if dtype == jnp.bfloat16 else dict(rtol=2e-5, atol=2e-5)
            np.testing.assert_allclose(got, want, **tol)
            np.testing.assert_allclose(want2, want, rtol=2e-4, atol=2e-4)
            # the ragged two-pass datapath (the layer's default impl)
            fe_ = fe
            h = grouped_matmul(x, wu, jnp.asarray(gp), jnp.asarray(tg),
                               "ragged")
            if gated:
                h = jax.nn.silu(h[:, :fe_]) * h[:, fe_:]
            else:
                h = jax.nn.gelu(h)
            ragged = np.asarray(grouped_matmul(
                h.astype(dtype), wd, jnp.asarray(gp), jnp.asarray(tg),
                "ragged"), np.float32)
            live_rows = bp >= 0
            np.testing.assert_allclose(got[live_rows], ragged[live_rows],
                                       **tol)
            # dead tiles: exact zeros (not merely small)
            dead_rows = np.repeat(tg, tile) < 0
            assert np.all(got[dead_rows] == 0)

    @pytest.mark.skipif(not HAVE_HYPOTHESIS, reason="hypothesis")
    def test_hypothesis_sweep(self):
        @settings(deadline=None)
        @given(st.integers(0, 2**31 - 1), st.booleans(), st.booleans(),
               st.booleans())
        def inner(seed, gated, bf16, short_capacity):
            rng = np.random.default_rng(seed)
            t = int(rng.integers(1, 14))
            k = int(rng.integers(1, 4))
            s_loc = int(rng.integers(1, 5))
            tile = int(rng.choice([2, 4, 8]))
            bp, gp, tg, nl, capacity = _build(
                rng, t, k, s_loc, tile, short_capacity=short_capacity)
            d, fe = 8, 12
            n_up = 2 if gated else 1
            dtype = jnp.bfloat16 if bf16 else jnp.float32
            x = jnp.asarray(rng.normal(size=(capacity, d)), dtype)
            wu = jnp.asarray(
                rng.normal(size=(s_loc, d, n_up * fe)) * 0.2, dtype)
            wd = jnp.asarray(
                rng.normal(size=(s_loc, fe, d)) * 0.2, dtype)
            got = np.asarray(fused_expert_ffn_pallas(
                x, _stored(wu, n_up), wd, jnp.asarray(tg), gated=gated),
                np.float32)
            xf, uf, df = (np.asarray(a, np.float32)
                          for a in (x, wu, wd))
            want = ref.fused_expert_ffn_ref(xf, uf, df, tg, gated=gated)
            tol = dict(rtol=6e-2, atol=6e-2) if bf16 \
                else dict(rtol=2e-5, atol=2e-5)
            np.testing.assert_allclose(got, want, **tol)
            assert np.all(got[np.repeat(tg, tile) < 0] == 0)
        inner()

    def test_all_dead_batch(self):
        """Zero local pairs: every tile dead, output all-zero, and the
        traffic model charges the fused path nothing."""
        rng = np.random.default_rng(5)
        bp, gp, tg, nl, capacity = _build(rng, 9, 2, 3, 4,
                                          all_remote=True)
        assert nl == 0 and (tg == -1).all()
        d, fe = 8, 12
        x = jnp.asarray(rng.normal(size=(capacity, d)), jnp.float32)
        wu = jnp.asarray(np.full((3, d, 2, fe), np.nan), jnp.float32)
        wd = jnp.asarray(np.full((3, fe, d), np.nan), jnp.float32)
        got = np.asarray(fused_expert_ffn_pallas(
            x, wu, wd, jnp.asarray(tg), gated=True))
        assert np.all(got == 0)
        tr = expert_ffn_traffic("fused", d=d, fe=fe, n_up=2, tile_m=4,
                                n_tiles=len(tg), live_tiles=0)
        assert tr["total"] == 0.0

    def test_etp_sharded_fe_partials_sum(self):
        """ETP shards fe: running the fused kernel per fe-shard and
        psum-ing the partial outputs == the unsharded kernel (the
        features-mode decode datapath)."""
        rng = np.random.default_rng(6)
        bp, gp, tg, nl, capacity = _build(rng, 10, 2, 3, 4)
        d, fe, shards = 8, 24, 2
        fs = fe // shards
        x = jnp.asarray(rng.normal(size=(capacity, d)), jnp.float32)
        wu = jnp.asarray(rng.normal(size=(3, d, 2, fe)) * 0.2,
                         jnp.float32)
        wd = jnp.asarray(rng.normal(size=(3, fe, d)) * 0.2, jnp.float32)
        full = np.asarray(fused_expert_ffn_pallas(
            x, wu, wd, jnp.asarray(tg), gated=True))
        partial = np.zeros_like(full)
        for s in range(shards):
            # gate and up are each fe wide: take shard s of both
            wu_s = wu[..., s * fs:(s + 1) * fs]
            wd_s = wd[:, s * fs:(s + 1) * fs, :]
            partial += np.asarray(fused_expert_ffn_pallas(
                x, wu_s, wd_s, jnp.asarray(tg), gated=True))
        np.testing.assert_allclose(partial, full, rtol=2e-5, atol=2e-5)

    def test_cold_and_dead_expert_weights_never_touched(self):
        """Poisoning every expert no live tile references (including
        the groups dead tiles would have used) must not change the
        output — the kernel never DMAs them."""
        rng = np.random.default_rng(7)
        d, fe, s_loc, tile = 8, 12, 6, 4
        capacity = 6 * tile
        x = jnp.asarray(rng.normal(size=(capacity, d)), jnp.float32)
        wu = np.asarray(rng.normal(size=(s_loc, d, 2, fe)) * 0.2,
                        np.float32)
        wd = np.asarray(rng.normal(size=(s_loc, fe, d)) * 0.2,
                        np.float32)
        tg = jnp.asarray([0, 0, 3, 3, -1, -1], jnp.int32)
        base = np.asarray(fused_expert_ffn_pallas(
            x, jnp.asarray(wu), jnp.asarray(wd), tg, gated=True))
        for cold in (1, 2, 4, 5):
            wu[cold] = np.nan
            wd[cold] = np.nan
        poisoned = np.asarray(fused_expert_ffn_pallas(
            x, jnp.asarray(wu), jnp.asarray(wd), tg, gated=True))
        np.testing.assert_array_equal(base, poisoned)


class TestGroupedImplsWithDeadTiles:
    def test_ragged_residual_not_charged_to_last_group(self):
        """The ragged impl must route residual capacity to the
        dead-tile path: poisoning EVERY expert's weights cannot leak
        into the residual rows (they belong to no group).  Regression
        for the seed impl's ``group_pad.at[s_loc-1].add(...)``."""
        rng = np.random.default_rng(0)
        s_loc, tile, d, f = 3, 4, 8, 8
        gs = np.array([4, 8, 4], np.int32)
        c = int(gs.sum()) + 2 * tile               # 2 dead slack tiles
        x = jnp.asarray(rng.normal(size=(c, d)), jnp.float32)
        w = jnp.asarray(np.full((s_loc, d, f), np.nan), jnp.float32)
        tg = np.array([0, 1, 1, 2, -1, -1], np.int32)
        out = np.asarray(grouped_matmul(x, w, jnp.asarray(gs),
                                        jnp.asarray(tg), "ragged"))
        assert np.all(out[int(gs.sum()):] == 0), \
            "residual rows must be zeros, not last-expert garbage"

    def test_all_impls_agree_and_zero_dead(self):
        rng = np.random.default_rng(1)
        bp, gp, tg, nl, capacity = _build(rng, 12, 2, 3, 4)
        d, f = 16, 24
        x = jnp.asarray(rng.normal(size=(capacity, d)), jnp.float32)
        w = jnp.asarray(rng.normal(size=(3, d, f)) * 0.2, jnp.float32)
        outs = {impl: np.asarray(grouped_matmul(
            x, w, jnp.asarray(gp), jnp.asarray(tg), impl))
            for impl in ("ragged", "scan_tiles", "onehot", "pallas")}
        live = bp >= 0
        for impl, out in outs.items():
            np.testing.assert_allclose(out[live], outs["onehot"][live],
                                       rtol=1e-4, atol=1e-4, err_msg=impl)
            assert np.all(out[np.repeat(tg, 4) < 0] == 0), impl


class TestTrafficAndDmaModel:
    def test_fused_strictly_below_two_pass(self):
        for live, n_tiles in ((1, 1), (1, 4), (3, 4), (8, 8), (0, 2)):
            kw = dict(d=64, fe=96, n_up=2, tile_m=8, n_tiles=n_tiles,
                      live_tiles=live)
            fused = expert_ffn_traffic("fused", **kw)["total"]
            two = expert_ffn_traffic("two_pass", **kw)["total"]
            legacy = expert_ffn_traffic("two_pass_legacy", **kw)["total"]
            assert fused < two <= legacy, (live, n_tiles)
        assert expert_ffn_traffic("fused", d=8, fe=8, n_up=1, tile_m=4,
                                  n_tiles=2, live_tiles=0)["total"] == 0

    def test_dma_count_equals_live_tiles(self):
        cases = [
            np.array([0, 1, 2, -1, -1]),
            np.array([0, 0, 2, 2, 2, -1]),
            np.array([1]),
        ]
        for tg in cases:
            k_up, k_dn = 2, 3
            got = fused_weight_dma_tiles(tg, k_up, k_dn)
            live = tg[tg >= 0]
            stripped = fused_weight_dma_tiles(live, k_up, k_dn)
            # trailing dead tiles contribute zero fetches: they park on
            # the last live tile's already-resident blocks
            assert got["dma_tiles"] == stripped["dma_tiles"]
            assert got["m_tiles"] == got["live_tiles"] == len(live)
            assert got["dma_tiles"] == len(live) * (k_up + k_dn)

    def test_all_dead_grid_still_fetches_parked_block(self):
        """A non-empty all-dead grid has no prior live tile to park on:
        the index maps name group 0's first up/down blocks and the
        pipeline physically prefetches each once.  The marginal-cost
        traffic model stays at zero; the DMA count does not."""
        got = fused_weight_dma_tiles(np.array([-1, -1]), 2, 3)
        assert got == {"dma_tiles": 2, "m_tiles": 1, "live_tiles": 0}
        # longer all-dead grids keep parking on the same block
        got4 = fused_weight_dma_tiles(np.array([-1] * 4), 1, 1)
        assert got4 == {"dma_tiles": 2, "m_tiles": 1, "live_tiles": 0}
        # an empty grid runs no pipeline at all
        empty = fused_weight_dma_tiles(np.array([], np.int64), 2, 3)
        assert empty == {"dma_tiles": 0, "m_tiles": 0, "live_tiles": 0}

    def test_single_k_tile_adjacent_group_reuse(self):
        """k_up == k_dn == 1 and a repeated group: the second tile's
        weight indices repeat the first's -> fewer fetches than
        live * phases (revisit-skip upper bound)."""
        got = fused_weight_dma_tiles(np.array([2, 2, 2]), 1, 1)
        assert got["dma_tiles"] == 2            # one up + one down fetch
        assert got["m_tiles"] == 1


class TestOpsInterpretPerCall:
    def test_env_read_per_call(self, monkeypatch):
        """The mode follows the backend, read per call: interpreter off
        the TPU, compiled on it."""
        assert interpret_mode() is (jax.default_backend() != "tpu")
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        assert interpret_mode() is False
        monkeypatch.setattr(jax, "default_backend", lambda: "cpu")
        assert interpret_mode() is True

    def test_explicit_override_beats_env(self, monkeypatch):
        """An explicit ``interpret=`` beats the backend rule, and the
        kernels resolve their default through the same rule: with the
        backend reported as a TPU, interpret=True still runs (no TPU
        here: compiled mode would fail)."""
        assert interpret_mode(True) is True
        assert interpret_mode(False) is False
        rng = np.random.default_rng(2)
        x = jnp.asarray(rng.normal(size=(8, 8)), jnp.float32)
        w = jnp.asarray(rng.normal(size=(2, 8, 8)) * 0.2, jnp.float32)
        tg = jnp.asarray([0, 1], jnp.int32)
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        out = np.asarray(grouped_ffn_pallas(x, w, tg, interpret=True))
        want = ref.grouped_matmul_ref(np.asarray(x), np.asarray(w),
                                      np.asarray(tg))
        np.testing.assert_allclose(out, want, rtol=1e-5, atol=1e-5)


def _per_layer_single_dot(x, wu, wd, tg, *, gated, tile_k_up,
                          tile_k_dn):
    """One layer's expert FFN as the kernel computed it from a per-layer
    [S, d, n_up*fe] up operand: one dot over all n_up*fe columns per up
    k-tile (the two-pass Pallas kernel with the fused kernel's tiles),
    the fp32 hidden cast once, gated in fp32 (``_activate``), then the
    down k-tiles.  wu: [S, d, n_up, fe] as stored."""
    s, d, n_up, fe = wu.shape
    h = grouped_ffn_pallas(x, wu.reshape(s, d, n_up * fe), tg,
                           tile_k=tile_k_up, tile_f=n_up * fe)
    return grouped_ffn_pallas(_activate(h, fe, gated), wd, tg,
                              tile_k=tile_k_dn, tile_f=d)


# token-tile layouts build_pair_buffer emits: every tile live, live
# tiles then dead ones, capacity-dropped rows, and no local pair at all
_LAYOUTS = {
    "all_live": [0, 1, 1, 3, 4],
    "trailing_dead": [1, 4, -1, -1, -1],
    "dead_tiles": None,              # drawn by _build
    "all_dead": [-1, -1, -1, -1],
}


class TestStackedOperand:
    """The kernel reads the layer-stacked [L, S, d, n_up, fe] /
    [L, S, fe, d] weights in place at a scalar-prefetched layer index;
    each hidden column sums the same products over d as the per-layer
    [S, d, n_up*fe] single dot did, so the output is bitwise the
    same."""

    @pytest.mark.parametrize("layout", list(_LAYOUTS))
    @pytest.mark.parametrize("gated", [True, False])
    @pytest.mark.parametrize("fe", [16, 256])
    def test_stacked_equals_per_layer_single_dot(self, fe, gated, layout):
        """fe=256 takes the packed gate/up words apart as the chip does
        (whole 128-lane blocks); fe=16 indexes the block."""
        rng = np.random.default_rng(11)
        n_layers, s_loc, d, tile = 3, 5, 32, 4
        n_up = 2 if gated else 1
        tg = _LAYOUTS[layout]
        if tg is None:
            _, _, tg, _, _ = _build(rng, 7, 2, s_loc, tile,
                                    short_capacity=True)
        tg = jnp.asarray(tg, jnp.int32)
        x = jnp.asarray(rng.normal(size=(len(tg) * tile, d)),
                        jnp.bfloat16)
        wu = jnp.asarray(rng.normal(size=(n_layers, s_loc, d, n_up, fe))
                         * 0.2, jnp.bfloat16)
        wd = jnp.asarray(rng.normal(size=(n_layers, s_loc, fe, d)) * 0.2,
                         jnp.bfloat16)
        tiles = dict(tile_k_up=16, tile_k_dn=8)
        for layer in range(n_layers):
            got = fused_expert_ffn_pallas(x, wu, wd, tg, layer,
                                          gated=gated, **tiles)
            want = _per_layer_single_dot(x, wu[layer], wd[layer], tg,
                                         gated=gated, **tiles)
            np.testing.assert_array_equal(np.asarray(got),
                                          np.asarray(want))
            assert not np.asarray(got)[np.repeat(tg, tile) < 0].any()

    @pytest.mark.parametrize("algo", ["metro", "eplb"])
    def test_apply_lm_matches_per_layer_form(self, algo, monkeypatch):
        """Prefill and decode through ``apply_lm`` with the fused kernel
        reading the stacks give the tokens and logits of the per-layer
        form, where each MoE layer's weights are sliced from the stack
        before the kernel."""
        from repro.configs import get_config
        from repro.core import build_placement, slots_for_ratio
        from repro.models import init_lm
        from repro.models import lm as LM
        from repro.models import moe as MOE
        from repro.sharding.policy import make_dist

        cfg = get_config("qwen3-30b-a3b").reduced()
        ep = 2
        spd = slots_for_ratio(cfg.num_experts, ep, 1.25)
        dist = make_dist(None, ep_size=ep, slots_per_device=spd)
        placement = build_placement(cfg.num_experts, ep, spd)
        params = init_lm(cfg, jax.random.PRNGKey(0), dist,
                         replica_expert=placement.replica_expert,
                         dtype=jnp.bfloat16)
        routing = LM.build_lm_routing(cfg, placement)
        b, n, steps = 3, 6, 3
        prompts = jax.random.randint(jax.random.PRNGKey(1), (b, n), 0,
                                     cfg.vocab_size)

        def serve():
            kw = dict(routing=routing, moe_impl="fused", algo=algo)
            cache = LM.init_cache(cfg, dist, b, n + steps)
            logits, cache, _ = LM.apply_lm(
                cfg, dist, params, tokens=prompts, cache=cache,
                mode="prefill", **kw)
            outs = [logits]
            tok = jnp.argmax(logits[:, -1:], axis=-1)
            for t in range(steps):
                logits, cache, _ = LM.apply_lm(
                    cfg, dist, params, tokens=tok,
                    pos=jnp.full((b,), n + t, jnp.int32), cache=cache,
                    mode="decode", **kw)
                outs.append(logits)
                tok = jnp.argmax(logits[:, -1:], axis=-1)
            return [np.asarray(o, np.float32) for o in outs]

        stacked = serve()
        moe_ffn = MOE.moe_ffn

        def per_layer(cfg, dist, p, *a, layer=None, **kw):
            if layer is not None:
                p = dict(p, w_up=p["w_up"][layer],
                         w_down=p["w_down"][layer])
            return moe_ffn(cfg, dist, p, *a, **kw)

        monkeypatch.setattr(MOE, "moe_ffn", per_layer)
        for got, want in zip(stacked, serve()):
            np.testing.assert_array_equal(got, want)
            np.testing.assert_array_equal(got.argmax(-1), want.argmax(-1))


@pytest.mark.slow
class TestFusedEngineParity:
    """moe_impl="fused" through the real serving engine must generate
    the SAME tokens as "ragged" — routing is identical (replicated
    router, same algo); only the expert datapath changes.  The serve
    harness is the bench's (one copy to keep in sync)."""

    def _serve(self, impl, algo, use_pallas_route=False):
        from benchmarks.bench_moe_kernels import serve_tokens
        return serve_tokens(impl, algo=algo,
                            use_pallas_route=use_pallas_route)

    @pytest.mark.parametrize("algo", ["metro", "eplb"])
    def test_fused_token_identical_to_ragged(self, algo):
        assert self._serve("fused", algo) == self._serve("ragged", algo)

    def test_pallas_route_token_identical(self):
        """EngineConfig.use_pallas_route moves METRO's Alg. 1 onto the
        scalar-core kernel — routing decisions (and therefore tokens)
        must not change."""
        assert (self._serve("fused", "metro", use_pallas_route=True)
                == self._serve("fused", "metro"))
