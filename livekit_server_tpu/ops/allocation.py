"""Batched forwarder bandwidth-allocation algebra.

Reference parity: pkg/sfu/forwarder.go allocation family — AllocateOptimal
(:591), ProvisionalAllocate/ProvisionalAllocateMute/ProvisionalAllocateGetCooperativeTransition
(:727-1105), AllocateNextHigher (:1107), Pause (:1308), DistanceToDesired
(:569) — and the cooperative cross-track allocation loop in
pkg/sfu/streamallocator/streamallocator.go (allocateAllTracks).

TPU-first re-design: per track a `[4, 4]` (spatial × temporal) bitrate
matrix (the reference's `Bitrates` [4][4] — receiver.go:49); allocation is
mask algebra + argmax/scan over layer matrices, vmapped over subscribers.
The cross-track greedy loop is a `lax.scan` over the (static) track axis
carrying the remaining-budget register — the per-tick "allocation matmul"
named in the north star.

Layer encoding: flat index l = spatial*MAX_T + temporal, -1 = paused.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from livekit_server_tpu.ops.backend import want_pallas

MAX_SPATIAL = 4
MAX_TEMPORAL = 4
NUM_LAYERS = MAX_SPATIAL * MAX_TEMPORAL  # 16 flat layers


def flat_layer(spatial, temporal):
    return jnp.asarray(spatial, jnp.int32) * MAX_TEMPORAL + jnp.asarray(temporal, jnp.int32)


def spatial_of(flat):
    return jnp.where(flat < 0, -1, flat // MAX_TEMPORAL)


def temporal_of(flat):
    return jnp.where(flat < 0, -1, flat % MAX_TEMPORAL)


def allowed_mask(bitrates, max_spatial, max_temporal):
    """[..., 4, 4] bool — layers that exist (bitrate > 0) and satisfy the
    subscriber's max-layer settings (reference maxLayer in forwarder.go).

    bitrates: [..., 4, 4] float32/int32 bps; max_spatial/max_temporal: [...]
    """
    s_idx = jnp.arange(MAX_SPATIAL, dtype=jnp.int32)[:, None]
    t_idx = jnp.arange(MAX_TEMPORAL, dtype=jnp.int32)[None, :]
    avail = jnp.asarray(bitrates) > 0
    cap = (s_idx <= jnp.asarray(max_spatial, jnp.int32)[..., None, None]) & (
        t_idx <= jnp.asarray(max_temporal, jnp.int32)[..., None, None]
    )
    return avail & cap


def optimal_layer(bitrates, max_spatial, max_temporal):
    """Highest allowed layer per element — reference AllocateOptimal (:591).

    Returns flat layer index [...], -1 where nothing is allowed.
    """
    mask = allowed_mask(bitrates, max_spatial, max_temporal)
    flat = mask.reshape(*mask.shape[:-2], NUM_LAYERS)
    idx = jnp.arange(NUM_LAYERS, dtype=jnp.int32)
    best = jnp.max(jnp.where(flat, idx, -1), axis=-1)
    return best


def lowest_layer(bitrates, max_spatial, max_temporal):
    """Lowest allowed layer per element (minimal allocation seed)."""
    mask = allowed_mask(bitrates, max_spatial, max_temporal)
    flat = mask.reshape(*mask.shape[:-2], NUM_LAYERS)
    idx = jnp.arange(NUM_LAYERS, dtype=jnp.int32)
    best = jnp.min(jnp.where(flat, idx, NUM_LAYERS), axis=-1)
    return jnp.where(best >= NUM_LAYERS, -1, best)


def layer_bitrate(bitrates, flat):
    """Bitrate of a flat layer index; 0 for -1. bitrates [..., 4, 4]."""
    b = bitrates.reshape(*bitrates.shape[:-2], NUM_LAYERS)
    safe = jnp.clip(flat, 0, NUM_LAYERS - 1)
    val = jnp.take_along_axis(b, safe[..., None], axis=-1)[..., 0]
    return jnp.where(flat < 0, 0, val)


def allocate_budget(bitrates, max_spatial, max_temporal, muted, budget,
                    allow_pause: bool = True):
    """Cooperative constrained allocation across one subscriber's tracks.

    Reference parity: streamallocator.go allocateAllTracks — two passes over
    tracks sorted by priority: (1) give every audible/visible track its
    minimal layer, (2) upgrade tracks in order to the best layer that fits
    the remaining budget. Tracks the reference marks "deficient" are those
    whose target < optimal.

    Args (leading axes vmap over subscribers):
      bitrates      [T, 4, 4] float32 bps
      max_spatial   [T] int32, max_temporal [T] int32 — subscriber caps
      muted         [T] bool — pub/sub muted (ProvisionalAllocateMute)
      budget        scalar float32 — available channel capacity (bps)
      allow_pause   static — config rtc.congestion_control.allow_pause. False
                    (the reference's default): pass 1 gives every audible/
                    visible track its minimal layer whatever the budget
                    (streamallocator.go: "allocate minimal to all tracks
                    irrespective of channel capacity"), so a low estimate
                    degrades video to its lowest layer and never pauses it;
                    `used` may then exceed `budget`.

    Returns (target_flat [T] int32, used_bps scalar, deficient [T] bool).
    """
    lo = lowest_layer(bitrates, max_spatial, max_temporal)
    hi = optimal_layer(bitrates, max_spatial, max_temporal)
    lo = jnp.where(muted, -1, lo)
    hi = jnp.where(muted, -1, hi)
    lo_cost = layer_bitrate(bitrates, lo)

    # Pass 1: minimal layers, in track order, while budget lasts.
    def p1(budget_left, xs):
        cost, valid = xs
        take = valid & (cost <= budget_left) if allow_pause else valid
        budget_left = jnp.where(take, budget_left - cost, budget_left)
        return budget_left, take

    # Full unroll: T is small and static; an unrolled scan fuses into one
    # kernel instead of a 16-iteration while loop (TPU loop overhead
    # dominates the tiny per-step vector work).
    budget_left, got_min = jax.lax.scan(
        p1, jnp.asarray(budget, jnp.float32), (lo_cost, lo >= 0),
        unroll=True,
    )

    # Pass 2: upgrade each track (in order) to the best layer that fits
    # budget_left + its own minimal cost.
    b_flat = bitrates.reshape(-1, NUM_LAYERS).astype(jnp.float32)
    mask_flat = allowed_mask(bitrates, max_spatial, max_temporal).reshape(-1, NUM_LAYERS)
    idx = jnp.arange(NUM_LAYERS, dtype=jnp.int32)

    def p2(budget_left, xs):
        costs, mask, min_l, min_cost, valid = xs
        avail = jnp.where(valid, budget_left + min_cost, 0.0)
        fits = mask & (costs <= avail)
        best = jnp.max(jnp.where(fits, idx, -1))
        best = jnp.where(valid, jnp.maximum(best, min_l), -1)
        cost = jnp.where(best >= 0, costs[jnp.clip(best, 0, NUM_LAYERS - 1)], 0.0)
        budget_left = jnp.where(valid, avail - cost, budget_left)
        return budget_left, best

    budget_left, target = jax.lax.scan(
        p2, budget_left,
        (b_flat, mask_flat, lo, jnp.where(got_min, lo_cost, 0.0), got_min),
        unroll=True,
    )
    used = jnp.asarray(budget, jnp.float32) - budget_left
    deficient = (hi >= 0) & (target < hi)
    return target, used, deficient


def allocate_budget_batch(bitrates, max_spatial, max_temporal, muted, budget,
                          allow_pause: bool = True):
    """One room's allocation for ALL subscribers at once — the scan
    formulation (the spec). The production TPU path is the room-batched
    `allocate_budget_rooms` kernel, pinned bit-identical to this by
    tests/test_allocation.py.

    Args:
      bitrates      [T, 4, 4] float32
      max_spatial   [S, T] int32, max_temporal [S, T] int32
      muted         [S, T] bool
      budget        [S] float32
    Returns (target [S, T] int32, used [S] float32, deficient [S, T] bool).
    """
    return jax.vmap(
        lambda m1, m2, m3, b: allocate_budget(
            bitrates, m1, m2, m3, b, allow_pause)
    )(max_spatial, max_temporal, muted, budget)


# ---------------------------------------------------------------------------
# Room-batched kernel: rooms on the vector lanes (see ops/selector.py's
# room-batched twin for the rationale — the vmapped per-room grid pays
# per-step fixed costs at ~8% lane occupancy).
# ---------------------------------------------------------------------------


def _budget_rooms_kernel(bit_ref, ms_ref, mt_ref, muted_ref, budget_ref,
                         target_ref, used_ref, defc_ref, *, allow_pause):
    """Two-pass cooperative allocation for a ROOM BLOCK: bit_ref
    [T, L, RB]; ms/mt/muted [T, S, RB]; budget [1, S, RB]; outputs
    target/defc [T, S, RB], used [1, S, RB]."""
    T, L, RB = bit_ref.shape
    S = ms_ref.shape[1]
    l_sp = jax.lax.broadcasted_iota(jnp.int32, (L, S, RB), 0) // MAX_TEMPORAL
    l_tp = jax.lax.broadcasted_iota(jnp.int32, (L, S, RB), 0) % MAX_TEMPORAL
    l_ix = jax.lax.broadcasted_iota(jnp.int32, (L, S, RB), 0)

    allowed, lo, hi, locost = [], [], [], []
    for t in range(T):
        bt = bit_ref[t, :, :][:, None, :]                           # [L,1,RB]
        a = (
            (bt > 0.0)
            & (l_sp <= ms_ref[t, :, :][None, :, :])
            & (l_tp <= mt_ref[t, :, :][None, :, :])
            & (muted_ref[t, :, :][None, :, :] == 0)
        )                                                           # [L,S,RB]
        lo_t = jnp.min(jnp.where(a, l_ix, L), axis=0)               # [S,RB]
        lo_t = jnp.where(lo_t >= L, -1, lo_t)
        hi_t = jnp.max(jnp.where(a, l_ix, -1), axis=0)
        lc = jnp.sum(jnp.where(l_ix == lo_t[None, :, :], bt, 0.0), axis=0)
        allowed.append(a); lo.append(lo_t); hi.append(hi_t); locost.append(lc)

    bl = budget_ref[0, :, :]                                        # [S,RB]
    got = []
    for t in range(T):                                              # pass 1
        take = lo[t] >= 0
        if allow_pause:
            take &= locost[t] <= bl
        bl = jnp.where(take, bl - locost[t], bl)
        got.append(take)
    for t in range(T):                                              # pass 2
        bt = bit_ref[t, :, :][:, None, :]
        avail = jnp.where(got[t], bl + locost[t], 0.0)
        fits = allowed[t] & (bt <= avail[None, :, :])
        best = jnp.max(jnp.where(fits, l_ix, -1), axis=0)
        best = jnp.where(got[t], jnp.maximum(best, lo[t]), -1)
        cost = jnp.sum(jnp.where(l_ix == best[None, :, :], bt, 0.0), axis=0)
        cost = jnp.where(best >= 0, cost, 0.0)
        bl = jnp.where(got[t], avail - cost, bl)
        target_ref[t, :, :] = best
        defc_ref[t, :, :] = ((hi[t] >= 0) & (best < hi[t])).astype(jnp.int32)
    used_ref[0, :, :] = budget_ref[0, :, :] - bl


def allocate_budget_rooms(bitrates, max_spatial, max_temporal, muted, budget,
                          use_pallas: bool | None = None,
                          interpret: bool = False,
                          allow_pause: bool = True):
    """All rooms' allocation at once.

    Args:
      bitrates      [R, T, 4, 4] float32
      max_spatial   [R, S, T] int32, max_temporal [R, S, T] int32
      muted         [R, S, T] bool
      budget        [R, S] float32
    Returns (target [R, S, T] int32, used [R, S] float32,
    deficient [R, S, T] bool).
    """
    use_pallas = want_pallas(
        use_pallas, interpret, "allocation.allocate_budget_rooms"
    )
    if not (use_pallas or interpret):
        return jax.vmap(
            functools.partial(allocate_budget_batch, allow_pause=allow_pause)
        )(bitrates, max_spatial, max_temporal, muted, budget)

    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    # Renamed upstream: TPUCompilerParams (<=0.4.x) -> CompilerParams.
    _CompilerParams = getattr(pltpu, "CompilerParams", None) or (
        pltpu.TPUCompilerParams
    )

    R, T = bitrates.shape[:2]
    S = budget.shape[-1]
    from livekit_server_tpu.ops.selector import pick_room_block

    # Working set: bitrates [T,L,RB] + five [T,S,RB] blocks + two [1,S,RB].
    RB = pick_room_block(R, 4 * (T * NUM_LAYERS + 5 * T * S + 2 * S))
    f32 = lambda x: jnp.asarray(x, jnp.float32)  # noqa: E731
    i32 = lambda x: jnp.asarray(x, jnp.int32)    # noqa: E731

    bit_spec = pl.BlockSpec((T, NUM_LAYERS, RB), lambda i: (0, 0, i),
                            memory_space=pltpu.VMEM)
    st_spec = pl.BlockSpec((T, S, RB), lambda i: (0, 0, i),
                           memory_space=pltpu.VMEM)
    bud_spec = pl.BlockSpec((1, S, RB), lambda i: (0, 0, i),
                            memory_space=pltpu.VMEM)
    target, used, defc = pl.pallas_call(
        functools.partial(_budget_rooms_kernel, allow_pause=allow_pause),
        grid=(R // RB,),
        out_shape=(
            jax.ShapeDtypeStruct((T, S, R), jnp.int32),
            jax.ShapeDtypeStruct((1, S, R), jnp.float32),
            jax.ShapeDtypeStruct((T, S, R), jnp.int32),
        ),
        in_specs=[bit_spec, st_spec, st_spec, st_spec, bud_spec],
        out_specs=(st_spec, bud_spec, st_spec),
        compiler_params=_CompilerParams(
            vmem_limit_bytes=48 * 1024 * 1024
        ),
        interpret=interpret,
    )(
        f32(bitrates).reshape(R, T, NUM_LAYERS).transpose(1, 2, 0),
        i32(max_spatial).transpose(2, 1, 0),
        i32(max_temporal).transpose(2, 1, 0),
        i32(muted).transpose(2, 1, 0),
        f32(budget).transpose(1, 0)[None],
    )
    return (
        target.transpose(2, 1, 0),
        used[0].transpose(1, 0),
        defc.transpose(2, 1, 0).astype(bool),
    )


def next_higher(bitrates, max_spatial, max_temporal, current_flat):
    """Next layer above current and its incremental cost — reference
    AllocateNextHigher (:1107), used when probing succeeds.

    Returns (next_flat [...], delta_bps [...]); next == current where no
    higher layer exists.
    """
    mask = allowed_mask(bitrates, max_spatial, max_temporal)
    flat_mask = mask.reshape(*mask.shape[:-2], NUM_LAYERS)
    idx = jnp.arange(NUM_LAYERS, dtype=jnp.int32)
    above = flat_mask & (idx > current_flat[..., None])
    nxt = jnp.min(jnp.where(above, idx, NUM_LAYERS), axis=-1)
    has = nxt < NUM_LAYERS
    nxt = jnp.where(has, nxt, current_flat)
    delta = jnp.where(
        has, layer_bitrate(bitrates, nxt) - layer_bitrate(bitrates, current_flat), 0
    )
    return nxt, delta


def distance_to_desired(target_flat, optimal_flat):
    """Layer distance between allocation and optimum — reference
    DistanceToDesired (:569); >0 means deficient, drives probing and
    connection-quality penalties.
    """
    t = jnp.where(target_flat < 0, -1, target_flat)
    o = jnp.where(optimal_flat < 0, -1, optimal_flat)
    return (o - t).astype(jnp.float32) / MAX_TEMPORAL
