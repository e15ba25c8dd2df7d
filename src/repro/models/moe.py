"""Expert-parallel MoE FFN with METRO / EPLB token routing.

Datapath (paper §IV-C, Fig. 7), per MoE layer inside `shard_map`:

  1. all-gather token activations over the EP group (the paper's
     all-gather *dispatch*; replaces all-to-all).
  2. redundant router: logits -> top-k -> global histogram T[1..N]
     (identical on every EP rank — deterministic, so no routing-table
     exchange is ever needed; this is also the straggler story).
  3. routing: METRO greedy / EPLB round-robin -> replica slot per
     (token, k) pair.
  4. local grouped FFN over *activated local experts only* on the pairs
     whose slot is local (sorted, tile-padded buffer).
  5. combine: psum_scatter back over the EP axis (+ shared-expert
     contribution fused into the same collective).

Weight layout and parallelism (beyond-paper, required at TPU scale):
  physical expert weights are [R, d, n_up, fe] / [R, fe, d] with the
  slot dim R sharded over the EP axis ("model") and the expert-hidden
  dim fe sharded over the data axis (**intra-expert TP / ETP** — a
  604MB mixtral expert never fits a single v5e chip's share otherwise).

  * tokens mode (train/prefill): tokens stay within their data row;
    the body FSDP-gathers the fe shards over "data" per layer, then
    runs the paper's row-local EP datapath over "model".
  * features mode (decode): full-mesh EP x ETP — tokens are
    all-gathered over ("data","model") (decode batches are tiny: this
    is latency-dominated exactly as the paper argues for all-gather
    dispatch), each chip computes its (slot-column, fe-row) shard with
    **zero expert-weight movement** — the memory-bound regime keeps
    weights pinned — and one psum_scatter over the full mesh combines.
Local (mesh-less) mode emulates a virtual EP group for CPU tests.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.ad_checkpoint
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.core import routing as core_routing
from repro.core.types import Placement
from repro.models import scopes as SC
from repro.sharding.policy import Dist

_INT = jnp.int32


# ----------------------------------------------------------------------
# params & routing tables
# ----------------------------------------------------------------------


def init_moe(cfg: ModelConfig, key, dist: Dist, replica_expert: np.ndarray,
             dtype=jnp.float32):
    """Physical expert weights in ``dtype``, slot-major ([R, ...],
    sharded on R over the EP axis; fe sharded over the data axis).

    Values are drawn in fp32 and cast before the replica gather, so a
    bf16 layer equals the cast fp32 one.  Each expert tensor is waited
    for before the next is drawn: with asynchronous dispatch the device
    would otherwise hold every fp32 draw of a layer at once, several GB
    at published widths."""
    d, fe = cfg.d_model, cfg.expert_hidden
    n = cfg.num_experts
    k1, k2, k3, k4 = jax.random.split(key, 4)
    s_in, s_out = 1.0 / np.sqrt(d), 1.0 / np.sqrt(fe)
    n_up = 2 if cfg.gated_mlp else 1
    f32 = jnp.float32
    # logical init then physical gather so replicas start identical
    idx = jnp.asarray(replica_expert)

    def experts(k, shape, scale):
        return jax.block_until_ready(
            (jax.random.normal(k, shape, f32) * scale).astype(dtype))[idx]

    p = {
        "w_router": jax.random.normal(k3, (d, n), f32) * s_in,
        "w_up": experts(k1, (n, d, n_up, fe), s_in),  # [R, d, n_up, fe]
        "w_down": experts(k2, (n, fe, d), s_out),     # [R, fe, d]
    }
    if cfg.num_shared_experts:
        f_sh = cfg.num_shared_experts * fe
        k5, k6 = jax.random.split(k4)
        p["shared_up"] = jax.random.normal(
            k5, (d, n_up, f_sh), f32) * s_in
        p["shared_down"] = jax.random.normal(k6, (f_sh, d), f32) * s_out
    return p


def routing_tables(placement: Placement, table_width: Optional[int] = None):
    """Device-array routing tables for one MoE layer (step inputs)."""
    w = table_width or placement.max_replicas
    es = placement.expert_slots
    if es.shape[1] < w:
        es = np.pad(es, ((0, 0), (0, w - es.shape[1])), constant_values=-1)
    elif es.shape[1] > w:
        raise ValueError("table_width smaller than max replicas")
    return {
        "expert_slots": jnp.asarray(es, _INT),
        "num_replicas": jnp.asarray(placement.expert_num_replicas, _INT),
    }


# ----------------------------------------------------------------------
# router (top-k gating)
# ----------------------------------------------------------------------


def gating(cfg: ModelConfig, w_router, x):
    """x: [T, d] -> (expert_ids [T,k], gates [T,k] f32, probs [T,N] f32)."""
    logits = x.astype(jnp.float32) @ w_router.astype(jnp.float32)
    k = cfg.num_experts_per_tok
    if cfg.norm_topk_prob:
        vals, ids = jax.lax.top_k(logits, k)
        gates = jax.nn.softmax(vals, axis=-1)
    else:
        probs = jax.nn.softmax(logits, axis=-1)
        gates, ids = jax.lax.top_k(probs, k)
    return ids.astype(_INT), gates, jax.nn.softmax(logits, axis=-1)


def load_balance_loss(probs, ids, num_experts: int):
    """Switch-transformer auxiliary loss: N * sum_i f_i * p_i."""
    t = ids.shape[0] * ids.shape[1]
    f = core_routing.topk_histogram(ids, num_experts).astype(jnp.float32) / t
    p = probs.mean(axis=0)
    return num_experts * jnp.sum(f * p)


# ----------------------------------------------------------------------
# sorted, tile-padded pair buffer
# ----------------------------------------------------------------------


def build_pair_buffer(slots, lo, s_loc: int, capacity: int, tile: int):
    """Pack (token,k) pairs whose slot is in [lo, lo+s_loc) into a sorted,
    tile-aligned buffer.

    slots: [T, k] physical slot per pair (-1 pad). Returns:
      buf_pair:   [C] flat pair index per buffer row (-1 = padding row)
      group_pad:  [S_loc] tile-padded group sizes (sum <= C)
      tile_group: [C // tile] local-slot id per tile (for weight
                  streaming); **-1 marks dead tiles** — tiles with zero
                  live rows (the region past the last live group, and
                  any tile whose rows were all dropped by capacity).
                  Dead tiles are always *trailing* (live rows fill each
                  group's segment from the front, segments are packed
                  in slot order), which is what lets the kernels park
                  their DMA indices on the last live tile.
      n_live:     [] int32 count of live tiles (scalar-prefetch operand
                  for the Pallas kernels and the DMA accounting in
                  sim/roofline).
    Rows beyond a group's true size (padding) and rows dropped by
    capacity are marked -1.  The dead-tile contract every grouped-
    matmul impl honors: dead tiles cost no weight DMA and no FLOPs and
    their output rows are exact zeros.
    """
    t, k = slots.shape
    flat = slots.reshape(-1)
    npairs = t * k
    ls = flat - lo
    local = (ls >= 0) & (ls < s_loc)
    key = jnp.where(local, ls, s_loc)                 # invalid sorted last
    order = jnp.argsort(key, stable=True)
    key_sorted = key[order]
    # true group sizes + tile padding
    gs = jnp.zeros(s_loc, _INT).at[jnp.where(local, ls, 0)].add(
        local.astype(_INT))
    group_pad = ((gs + tile - 1) // tile) * tile
    pad_off = jnp.concatenate(
        [jnp.zeros(1, _INT), jnp.cumsum(group_pad)[:-1].astype(_INT)])
    # rank within group (over the sorted ordering)
    seg_start = jnp.searchsorted(key_sorted, key_sorted, side="left")
    rank = jnp.arange(npairs, dtype=_INT) - seg_start.astype(_INT)
    valid_sorted = key_sorted < s_loc
    dest = jnp.where(
        valid_sorted,
        pad_off[jnp.minimum(key_sorted, s_loc - 1)] + rank,
        capacity)                                      # OOB -> dropped
    buf_pair = jnp.full(capacity, -1, _INT).at[dest].set(
        order.astype(_INT), mode="drop")
    n_tiles = capacity // tile
    tile_start = jnp.arange(n_tiles, dtype=_INT) * tile
    bounds = jnp.cumsum(group_pad)
    tile_group = jnp.searchsorted(bounds, tile_start, side="right").astype(_INT)
    tile_group = jnp.minimum(tile_group, s_loc - 1)
    tile_live = jnp.any((buf_pair >= 0).reshape(n_tiles, tile), axis=1)
    tile_group = jnp.where(tile_live, tile_group, -1)
    n_live = jnp.sum(tile_live).astype(_INT)
    return buf_pair, group_pad, tile_group, n_live


# ----------------------------------------------------------------------
# grouped matmul implementations
# ----------------------------------------------------------------------


def grouped_matmul(x, w, group_pad, tile_group, impl: str):
    """x: [C, d] tile-aligned sorted buffer; w: [S_loc, d, f].

    Rows within group_pad ranges use that group's weights.  Dead tiles
    (``tile_group == -1``: rows past the last live group, including the
    residual capacity slack) take the dead-tile path — no weight
    streaming, no FLOPs where the impl can express it, exact-zero
    output rows.  Live tiles' intra-group pad rows still compute
    garbage the caller masks (they share a tile with real rows).
    """
    c, d = x.shape
    s_loc, _, f = w.shape
    if impl == "ragged":
        # segment g occupies [pad_off[g], pad_off[g] + group_pad[g])
        # clipped to the buffer; rows beyond the last clipped segment
        # (residual capacity slack) belong to NO group, and ragged_dot
        # zero-fills them — the dead-tile path.  (The seed impl dumped
        # that residual into the last local expert via
        # ``group_pad.at[s_loc-1].add(c - sum)``, making it stream the
        # last expert's weights over pure padding.)
        pad_off = jnp.concatenate(
            [jnp.zeros(1, _INT), jnp.cumsum(group_pad)[:-1].astype(_INT)])
        gs = jnp.clip(c - pad_off, 0, group_pad)
        out = jax.lax.ragged_dot(x, w, gs.astype(jnp.int32))
        # rows past the last segment belong to no group; ragged_dot
        # zero-fills them on XLA:CPU but that is not a documented
        # contract — mask explicitly so the exact-zero dead-tile
        # guarantee holds on every backend
        residual = jnp.arange(c) >= jnp.sum(gs)
        return jnp.where(residual[:, None], 0.0, out)
    if impl == "scan_tiles":
        tile = c // tile_group.shape[0]
        xt = x.reshape(-1, tile, d)

        def body(_, args):
            xi, g = args
            # lax.cond: dead tiles skip the matmul entirely
            yi = jax.lax.cond(
                g >= 0,
                lambda: xi @ w[jnp.maximum(g, 0)],
                lambda: jnp.zeros((tile, f), x.dtype))
            return None, yi

        _, yt = jax.lax.scan(body, None, (xt, tile_group))
        return yt.reshape(c, f)
    if impl == "onehot":  # oracle; O(C * S_loc * d * f)
        tile = c // tile_group.shape[0]
        row_group = jnp.repeat(tile_group, tile)
        # one_hot(-1) is the all-zero row: dead tiles select no expert
        sel = jax.nn.one_hot(row_group, s_loc, dtype=x.dtype)
        return jnp.einsum("cs,cd,sdf->cf", sel, x, w)
    if impl == "pallas":
        from repro.kernels.moe_ffn import grouped_ffn_pallas
        return grouped_ffn_pallas(x, w, tile_group)
    if impl == "fused":
        raise ValueError(
            "impl='fused' is the one-pass up→act→down megakernel — it "
            "has no single-matmul form; _expert_compute dispatches it")
    raise ValueError(f"unknown grouped_matmul impl {impl!r}")


# ----------------------------------------------------------------------
# local expert compute (runs identically in EP-shard and virtual mode)
# ----------------------------------------------------------------------


def _expert_compute(cfg: ModelConfig, w_up, w_down, x, ids, gates, slots,
                    *, lo, s_loc: int, capacity: int, tile: int,
                    impl: str):
    """Grouped FFN over local slots; returns partial output [T, d] f32.

    w_up: [S_loc, d, n_up, fe_shard]; w_down: [S_loc, fe_shard, d] —
    fe_shard may be a proper shard (ETP); the caller psums over the ETP
    axis.

    ``impl="fused"`` collapses the two grouped matmuls + gating into
    ONE Pallas megakernel call (kernels/moe_ffn.fused_expert_ffn_pallas):
    the ``[C, n_up*fe]`` hidden never materializes in HBM and each
    activated expert's weights stream exactly once per resident token
    tile (no ``moe_h`` remat point exists on this path — there is no
    hidden to save)."""
    t, d = x.shape
    k = ids.shape[-1]
    s_l, _, n_up, fe = w_up.shape
    with jax.named_scope(SC.DISPATCH):
        buf_pair, group_pad, tile_group, _n_live = build_pair_buffer(
            slots, lo, s_loc, capacity, tile)
        row_valid = buf_pair >= 0
        tok = jnp.where(row_valid, buf_pair // k, 0)
        xg = jnp.where(row_valid[:, None], x[tok], 0).astype(x.dtype)

    with jax.named_scope(SC.EXPERTS):
        w_up2 = w_up.reshape(s_l, d, n_up * fe).astype(x.dtype)
        w_down2 = w_down.astype(x.dtype)
        if impl == "fused":
            from repro.kernels.moe_ffn import fused_expert_ffn_pallas
            y = fused_expert_ffn_pallas(xg, w_up2, w_down2, tile_group,
                                        gated=cfg.gated_mlp)
            y = jax.ad_checkpoint.checkpoint_name(y, "moe_y")
        elif impl == "fused_paged":
            # the double-buffered paged megakernel, driven here with the
            # identity slot->frame map (all local slots resident in
            # order); the expert-pool bench exercises permuted maps
            from repro.kernels.moe_ffn import fused_expert_ffn_paged_pallas
            y = fused_expert_ffn_paged_pallas(
                xg, w_up2, w_down2, jnp.arange(s_l, dtype=jnp.int32),
                tile_group, gated=cfg.gated_mlp)
            y = jax.ad_checkpoint.checkpoint_name(y, "moe_y")
        else:
            h = grouped_matmul(xg, w_up2, group_pad, tile_group, impl)
            if cfg.gated_mlp:
                g, u = jnp.split(h, 2, axis=-1)
                h = jax.nn.silu(g) * u
            else:
                h = jax.nn.gelu(h)
            # named for the save_moe remat policy: saving just these two
            # grouped matmuls avoids recomputing the dominant expert
            # FLOPs in backward while attention still remats (perf
            # iteration, EXPERIMENTS.md §Perf)
            h = jax.ad_checkpoint.checkpoint_name(h, "moe_h")
            y = grouped_matmul(h.astype(x.dtype), w_down2, group_pad,
                               tile_group, impl)
            y = jax.ad_checkpoint.checkpoint_name(y, "moe_y")

    with jax.named_scope(SC.COMBINE):
        gate = jnp.where(row_valid,
                         gates.reshape(-1)[jnp.maximum(buf_pair, 0)], 0.0)
        y = y.astype(jnp.float32) * gate[:, None]
        out = jnp.zeros((t, d), jnp.float32).at[tok].add(
            jnp.where(row_valid[:, None], y, 0.0))
    return out


def _shared_expert(cfg: ModelConfig, params, x):
    """Always-active shared experts on (possibly ETP-sharded) weights;
    the partial contribution joins the MoE combine psum for free."""
    up, down = params["shared_up"], params["shared_down"]
    d, n_up, f_sh = up.shape
    h = x @ up.reshape(d, n_up * f_sh).astype(x.dtype)
    if cfg.gated_mlp:
        a, b = jnp.split(h, 2, axis=-1)
        h = jax.nn.silu(a) * b
    else:
        h = jax.nn.gelu(h)
    return (h @ down.astype(x.dtype)).astype(jnp.float32)


# ----------------------------------------------------------------------
# the MoE layer
# ----------------------------------------------------------------------


def _moe_inner(cfg: ModelConfig, params, tables, x, *, algo, lo, s_loc,
               capacity, tile, impl, ep_size, slots_per_device,
               use_pallas_route=False, with_stats=True, row_valid=None):
    """Router + routing + local grouped FFN. x: [T, d] (full EP-group
    tokens). Returns (partial_out [T, d] f32, stats).

    ``row_valid`` [T] masks padding rows out of routing entirely: their
    top-k choices become -1 pads, so they never skew the histogram,
    EPLB round-robin ranks, METRO's activation decisions, or the
    expert-load stats that drive rebalancing — and routing becomes
    bitwise-invariant to how much a serving batch was padded."""
    with jax.named_scope(SC.ROUTER):
        ids, gates, probs = gating(cfg, params["w_router"], x)
        if row_valid is not None:
            ids = jnp.where(row_valid[:, None], ids, -1)
        hist = core_routing.topk_histogram(ids, cfg.num_experts)
    with jax.named_scope(SC.ROUTE):
        slots = core_routing.route(
            algo, ids, hist, tables["expert_slots"],
            tables["num_replicas"], num_devices=ep_size,
            slots_per_device=slots_per_device, use_pallas=use_pallas_route)
    out = _expert_compute(
        cfg, params["w_up"], params["w_down"], x, ids, gates, slots,
        lo=lo, s_loc=s_loc, capacity=capacity, tile=tile, impl=impl)
    if cfg.num_shared_experts:
        with jax.named_scope(SC.SHARED_EXPERT):
            out = out + _shared_expert(cfg, params, x)
    from repro.core import metrics as m
    act = m.activated_per_device(slots, ep_size, slots_per_device)
    stats = {
        "aux_loss": load_balance_loss(probs, ids, cfg.num_experts),
        "max_activated": jnp.max(act).astype(jnp.float32),
        "mean_activated": jnp.mean(act.astype(jnp.float32)),
        "max_tokens": jnp.max(
            m.tokens_per_device(slots, ep_size, slots_per_device)
        ).astype(jnp.float32),
        # per-expert token loads (drives EPLB rebalancing in the engine)
        "expert_hist": hist.astype(jnp.float32),
        # per-physical-slot activation (drives expert-weight paging:
        # METRO and EPLB pick the same logical experts but different
        # replica slots, and slots are what the pool pages)
        "slot_hist": _slot_histogram(slots, ep_size * slots_per_device),
    }
    return out, stats


def _slot_histogram(slots, n_slots: int):
    """[T, k] global physical-slot choices (-1 pads) -> [n_slots] f32
    activation counts.  Deterministically identical on every rank
    (redundant routing), like ``expert_hist``."""
    valid = slots >= 0
    return jnp.zeros((n_slots,), jnp.float32).at[
        jnp.where(valid, slots, 0)].add(valid.astype(jnp.float32))


def _capacity(t_group: int, k: int, *, algo: str, mode: str, ep: int,
              s_loc: int, tile: int, capacity_factor: float) -> int:
    pairs = t_group * k
    if algo == "metro" or mode in ("features", "local") or ep == 1:
        c = pairs                         # no-drop: worst case all local
    else:
        c = int(np.ceil(pairs * capacity_factor / ep))
    c = c + s_loc * (tile - 1)            # tile-padding slack
    return int(np.ceil(max(c, tile) / tile)) * tile


def moe_ffn(cfg: ModelConfig, dist: Dist, params, tables, x, *,
            algo: str = "eplb", capacity_factor: float = 1.25,
            impl: str = "ragged", tile: int = 8, mode: str = "tokens",
            use_pallas_route: bool = False, row_valid=None):
    """MoE FFN over x: [B, S, d] (tokens mode) or [T, d] (features mode).

    tokens mode: x sequence-sharded over EP axis -> paper's all-gather
    dispatch on tokens (per data row; fe shards FSDP-gathered per layer).
    features mode (decode): full-mesh EP x ETP, weights never move.
    Virtual-EP local fallback when no mesh is active.

    ``row_valid`` (bool, x's token shape — [B, S] or [T]) excludes
    padding tokens from routing (see :func:`_moe_inner`).
    """
    squeeze = x.ndim == 3
    d = x.shape[-1]
    ep, spd = dist.ep_size, dist.slots_per_device
    k = cfg.num_experts_per_tok

    if dist.mesh is None or dist.tp_axis is None:
        # virtual EP: all slots local, same math, no collectives
        x2 = x.reshape(-1, d) if squeeze else x
        rv = row_valid.reshape(-1) if row_valid is not None else None
        capacity = _capacity(x2.shape[0], k, algo=algo, mode="local", ep=ep,
                             s_loc=ep * spd, tile=tile,
                             capacity_factor=capacity_factor)
        out, stats = _moe_inner(
            cfg, params, tables, x2, algo=algo, lo=0, s_loc=ep * spd,
            capacity=capacity, tile=tile, impl=impl, ep_size=ep,
            slots_per_device=spd, use_pallas_route=use_pallas_route,
            row_valid=rv)
        out = out.astype(x.dtype)
        return (out.reshape(x.shape) if squeeze else out), stats

    mesh, ax = dist.mesh, dist.tp_axis
    from jax.sharding import PartitionSpec as P
    dp = dist.dp_axes
    all_axes = tuple(mesh.axis_names)
    # the ETP axis: fe sharding over the in-pod data axis
    etp = "data" if "data" in mesh.axis_names else None
    etp_size = mesh.shape[etp] if etp else 1

    def _reduce_stats(stats, axes):
        return {
            "aux_loss": jax.lax.pmean(stats["aux_loss"], axes),
            "max_activated": jax.lax.pmax(stats["max_activated"], axes),
            "mean_activated": jax.lax.pmean(stats["mean_activated"], axes),
            "max_tokens": jax.lax.pmax(stats["max_tokens"], axes),
            # identical within an EP group; distinct across data rows
            "expert_hist": jax.lax.psum(stats["expert_hist"], axes) / ep,
            "slot_hist": jax.lax.psum(stats["slot_hist"], axes) / ep,
        }

    has_shared = bool(cfg.num_shared_experts)
    shared = ((params["shared_up"], params["shared_down"])
              if has_shared else None)

    # weight specs: slots over EP axis, fe over ETP axis
    fe = cfg.expert_hidden
    etp_w = etp if (etp and fe % etp_size == 0) else None
    wup_spec = P(ax, None, None, etp_w)
    wdn_spec = P(ax, etp_w, None)
    f_sh = cfg.num_shared_experts * fe
    sh_ok = etp and f_sh % (etp_size * ep) == 0
    shup_spec = P(None, None, (etp, ax) if sh_ok else ax)
    shdn_spec = P((etp, ax) if sh_ok else ax, None)
    shared_spec = (shup_spec, shdn_spec) if has_shared else None

    if mode == "tokens":
        b, s, _ = x.shape
        rv_full = (row_valid if row_valid is not None
                   else jnp.ones((b, s), bool))
        # sequence sharded over EP axis when divisible (paper's SP
        # dispatch); otherwise x enters replicated and gather is a no-op.
        gather = s % ep == 0
        dp_ok = b % dist.dp_size == 0
        b_l = b // dist.dp_size if dp_ok else b
        t_group = b_l * s
        capacity = _capacity(t_group, k, algo=algo, mode="tokens", ep=ep,
                             s_loc=spd, tile=tile,
                             capacity_factor=capacity_factor)
        x_spec = P(dp if dp_ok else None, ax if gather else None, None)
        rv_spec = P(dp if dp_ok else None, ax if gather else None)

        def body(xb, rvb, w_up, w_down, w_router, shared, es, nr):
            rank = jax.lax.axis_index(ax)
            with jax.named_scope(SC.COLLECTIVE):
                # FSDP-gather the fe shards within the data row (cast to
                # the compute dtype first: halves the gather traffic)
                w_up = w_up.astype(xb.dtype)
                w_down = w_down.astype(xb.dtype)
                if etp_w:
                    w_up = jax.lax.all_gather(w_up, etp_w, axis=3,
                                              tiled=True)
                    w_down = jax.lax.all_gather(w_down, etp_w, axis=1,
                                                tiled=True)
                xg = (jax.lax.all_gather(xb, ax, axis=1, tiled=True)
                      if gather else xb)
                rvg = (jax.lax.all_gather(rvb, ax, axis=1, tiled=True)
                       if gather else rvb)
                su = sd = None
                if shared is not None:
                    su, sd = shared
                    if sh_ok:  # gather the data-axis part; keep EP shard
                        su = jax.lax.all_gather(su, etp, axis=2, tiled=True)
                        sd = jax.lax.all_gather(sd, etp, axis=0, tiled=True)
            bl = xg.shape[0]
            x2 = xg.reshape(-1, d)
            p = {"w_router": w_router, "w_up": w_up, "w_down": w_down}
            if shared is not None:
                p["shared_up"], p["shared_down"] = su, sd
            out, stats = _moe_inner(
                cfg, p, {"expert_slots": es, "num_replicas": nr}, x2,
                algo=algo, lo=rank * spd, s_loc=spd, capacity=capacity,
                tile=tile, impl=impl, ep_size=ep, slots_per_device=spd,
                use_pallas_route=use_pallas_route,
                row_valid=rvg.reshape(-1))
            out = out.astype(xb.dtype).reshape(bl, -1, d)
            with jax.named_scope(SC.COLLECTIVE):
                if gather:
                    out = jax.lax.psum_scatter(out, ax, scatter_dimension=1,
                                               tiled=True)
                else:
                    out = jax.lax.psum(out, ax)
                return out, _reduce_stats(stats, all_axes)

        out, stats = jax.shard_map(
            body, mesh=mesh,
            in_specs=(x_spec, rv_spec, wup_spec, wdn_spec, P(),
                      shared_spec, P(), P()),
            out_specs=(x_spec, P()),
            check_vma=False,
        )(x, rv_full, params["w_up"], params["w_down"],
          params["w_router"], shared,
          tables["expert_slots"], tables["num_replicas"])
        return out, stats

    # ------------------------------------------------------------------
    # features mode (decode): full-mesh EP x ETP
    #   x: [T, d], d sharded over every in-pod axis; tokens replicated
    #   over "pod"... sharded over pod when divisible.
    # ------------------------------------------------------------------
    t = x.shape[0]
    rv_full = row_valid if row_valid is not None else jnp.ones((t,), bool)
    pod = tuple(a for a in dp if a != etp)         # ("pod",) or ()
    pod_size = int(np.prod([mesh.shape[a] for a in pod])) if pod else 1
    pod_ok = pod and t % pod_size == 0
    t_group = t // pod_size if pod_ok else t
    capacity = _capacity(t_group, k, algo=algo, mode="features", ep=ep,
                         s_loc=spd, tile=tile,
                         capacity_factor=capacity_factor)
    gather_axes = tuple(a for a in (etp, ax) if a)
    gx = int(np.prod([mesh.shape[a] for a in gather_axes]))
    gather = d % gx == 0
    x_spec = P(pod if pod_ok else None, gather_axes if gather else None)
    rv_spec = P(pod if pod_ok else None)

    def body_f(xb, rvb, w_up, w_down, w_router, shared, es, nr):
        rank = jax.lax.axis_index(ax)
        with jax.named_scope(SC.COLLECTIVE):
            xg = (jax.lax.all_gather(xb, gather_axes, axis=1, tiled=True)
                  if gather else xb)
        p = {"w_router": w_router, "w_up": w_up, "w_down": w_down}
        if shared is not None:
            p["shared_up"], p["shared_down"] = shared
        out, stats = _moe_inner(
            cfg, p, {"expert_slots": es, "num_replicas": nr}, xg,
            algo=algo, lo=rank * spd, s_loc=spd, capacity=capacity,
            tile=tile, impl=impl, ep_size=ep, slots_per_device=spd,
            use_pallas_route=use_pallas_route, row_valid=rvb)
        # combine over slots (EP axis) AND fe shards (ETP axis) in one
        # collective; weights never moved.
        with jax.named_scope(SC.COLLECTIVE):
            if gather:
                out = jax.lax.psum_scatter(out, gather_axes,
                                           scatter_dimension=1, tiled=True)
            else:
                out = jax.lax.psum(out, gather_axes)
            return out.astype(xb.dtype), _reduce_stats(stats, all_axes)

    out, stats = jax.shard_map(
        body_f, mesh=mesh,
        in_specs=(x_spec, rv_spec, wup_spec, wdn_spec, P(), shared_spec,
                  P(), P()),
        out_specs=(x_spec, P()),
        check_vma=False,
    )(x, rv_full, params["w_up"], params["w_down"], params["w_router"],
      shared, tables["expert_slots"], tables["num_replicas"])
    return out, stats
