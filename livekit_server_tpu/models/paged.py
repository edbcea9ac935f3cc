"""Paged media plane: the dense tick re-based onto pooled HBM pages.

The dense plane (models/plane.py) is `[R, T, K, S]` — every room pays
the configured worst case. Here the device state is ONE pool of P
fixed-shape PAGES, each a `[tpage, K, spage]` block of some room's
(track × subscriber) plane, plus a device-resident page table the tick
indirects through (runtime/pager.py owns the host allocator and the
canonical table mirrors). A 2-person room holds one page; the 50-sub
north star holds its full grid — rooms/chip follows the actual room-size
distribution (pooled-page layout per Ragged Paged Attention, PAPERS.md).

The trick that makes this nearly free: the dense tick is already almost
everywhere PER-(track, sub)-ELEMENT or separable per track / per sub, so
a page is just a small dense room and the pooled tick IS the dense tick
at dims `[P, TP, K, SP]`. Exactly two couplings cross pages, and both
are row-granular gathers through `tmembers` (the page ids of one room's
sub column across its track pages):

  1. per-subscriber send totals (BWE/pacer input): summed over the
     room's track pages — disjoint (track, pkt) blocks, so integer sums
     are exact;
  2. phase-2 cross-track allocation: each page gathers its room's FULL
     track axis (bitrates + ctrl, `MT·TP == T` entries, missing rows
     filled with the dense init values) so the budget algebra sees the
     same operands as the dense plane, then keeps its own-tp slice of
     the targets.

Cross-page consistency is by construction — DUPLICATE EVERYWHERE, READ
FROM ONE: the host stages a track's packets into every sp-page of its
track group and a sub's feedback into every tp-page of its sub group, so
per-track state (stats/tracker/audio/RED) computes identically in all
sp-duplicates (read back from sp==0) and per-sub state (BWE/pacer)
identically in all tp-duplicates (read back from tp==0). Free pages get
zeroed inputs and init ctrl, hence no sends — and the tick PINS their
state to its pre-tick values (a zero-input tick would still advance
pacer tokens / BWE sample age / tracker windows), so a free page always
holds pristine init state. That enforced invariant is what lets the
live-extent fused path (`paged_plane_tick_live` + ops/paged_kernel.py)
skip dead pages entirely: their state needs no writes and their outputs
are one shared constant computed from the init template.

This module also owns the host-side layout translation (pooled ↔ logical
numpy) used by checkpoints, integrity repair, the express mirror, and
the dense-vs-paged parity tests: every PlaneState leaf is one of three
KINDS — "track" `[R, T·m, …]`, "sub" `[R, S, …]`, "track_sub"
`[R, T, S, …]` — and each kind is a pure index-arithmetic reshape +
fancy-index against the page table. Checkpoints serialize the LOGICAL
form, which is what keeps them byte-identical across pool layouts.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from livekit_server_tpu.analysis.registry import device_entry
from livekit_server_tpu.models import plane
from livekit_server_tpu.models.plane import (
    MAX_LAYERS,
    SPEAKER_TOP_K,
    PlaneDims,
    PlaneState,
    TickInputs,
    TickOutputs,
)
from livekit_server_tpu.ops import allocation, audio, bwe, pacer, quality, selector
from livekit_server_tpu.ops.bits import mask_words


class PagedDims(NamedTuple):
    """Logical plane dims + the page geometry over them.

    `tpage`/`spage` must divide `tracks`/`subs` (pow2, spage | 32 so a
    sub page never straddles a bit-mask word boundary): the logical
    plane is exactly an MT × MS grid of page-shaped tiles, which keeps
    logical↔pooled translation pure index arithmetic.
    """

    rooms: int
    tracks: int
    pkts: int
    subs: int
    tpage: int
    spage: int
    pool_pages: int

    @property
    def max_tpages(self) -> int:
        return self.tracks // self.tpage

    @property
    def max_spages(self) -> int:
        return self.subs // self.spage

    @property
    def logical(self) -> PlaneDims:
        return PlaneDims(self.rooms, self.tracks, self.pkts, self.subs)

    def pooled(self) -> PlaneDims:
        """The pool as the PlaneDims the ops stack compiles against:
        pages are the batch axis, a page is a [tpage, K, spage] room."""
        return PlaneDims(self.pool_pages, self.tpage, self.pkts, self.spage)


class PageTable(NamedTuple):
    """Device-resident page table (host canonical copy lives in the
    pager; this is the delta-uploaded device mirror).

    `rooms_pages` is the ISSUE's `[R, max_pages]` room→pages view (host
    debug/audit walks); the tick itself indirects through the inverse
    maps, which is what a static-shape gather wants:
    """

    rooms_pages: jax.Array  # [R, MT*MS] int32 — room's grid, -1 empty
    tmembers: jax.Array     # [P, MT] int32 — same-(room, sp) pages by tp
    pg_room: jax.Array      # [P] int32 — owning room (-1 free)
    pg_tp: jax.Array        # [P] int32 — track-page index within room
    pg_sp: jax.Array        # [P] int32 — sub-page index within room


def init_table(dims: PagedDims) -> PageTable:
    P = dims.pool_pages
    return PageTable(
        rooms_pages=jnp.full(
            (dims.rooms, dims.max_tpages * dims.max_spages), -1, jnp.int32
        ),
        tmembers=jnp.full((P, dims.max_tpages), -1, jnp.int32),
        pg_room=jnp.full((P,), -1, jnp.int32),
        pg_tp=jnp.full((P,), -1, jnp.int32),
        pg_sp=jnp.full((P,), -1, jnp.int32),
    )


# ---------------------------------------------------------------------------
# The paged tick
# ---------------------------------------------------------------------------


@device_entry("paged.paged_plane_tick")
def paged_plane_tick(
    state: PlaneState,
    inp: TickInputs,
    table: PageTable,
    audio_params: audio.AudioLevelParams = audio.AudioLevelParams(),
    bwe_params: bwe.BWEParams = bwe.BWEParams(),
    red_enabled: bool = True,
):
    """One tick over the page pool; same three phases as
    `media_plane_tick` with pages as the batch axis and the two genuine
    cross-page couplings routed through `tmembers` gathers (module doc).
    State/inputs are at `dims.pooled()`; jit with `state` donated.
    """
    L = MAX_LAYERS
    P, MT = table.tmembers.shape
    TP = state.meta.is_video.shape[1]
    SP = state.ctrl.subscribed.shape[2]
    mem = jnp.clip(table.tmembers, 0, P - 1)      # [P, MT]
    mvalid = table.tmembers >= 0                  # [P, MT]

    # ---- phase 0: forward decision, pages batched ----------------------
    # Per-(track, pkt, sub)-element — page-local by construction. Free
    # pages have init ctrl (subscribed=False) → no sends.
    base = (
        state.ctrl.subscribed
        & ~state.ctrl.sub_muted
        & (state.meta.published & ~state.meta.pub_muted)[:, :, None]
    )
    (sel_state, send_bits, drop_bits, switch_bits, need_kf,
     pkts_sent, sent_bytes, fwd_packets, fwd_bytes) = selector.decide_rooms(
        state.sel, state.meta.is_svc, state.meta.is_video, base,
        inp.layer, inp.temporal, inp.keyframe, inp.layer_sync,
        inp.end_frame, inp.valid, inp.size,
        wire_overhead=pacer.WIRE_OVERHEAD_BYTES,
    )

    # Cross-page coupling #1: a subscriber's true send totals span every
    # track page of its room. Gather-sum over tmembers — the (track,
    # pkt) blocks are disjoint, so the int sums are exactly the dense
    # per-sub sums; every page of the same (room, sp) column computes
    # the same value, keeping the tp-duplicated BWE/pacer state in sync.
    def gsum(x):  # [P, SP] int32 → [P, SP]
        return jnp.sum(jnp.where(mvalid[:, :, None], x[mem], 0), axis=1)

    pkts_sent_g = gsum(pkts_sent)
    sent_bytes_g = gsum(sent_bytes)

    # ---- phase 1: per-page core (vmapped dense room tick) --------------
    def tick_one(st, i, sb, db, wb, nk, ps, sby, fp, fby):
        return plane._room_tick(st, i, sb, db, wb, nk, ps, sby, fp, fby,
                                audio_params, bwe_params, red_enabled)

    inp_axes = TickInputs(**{f: 0 for f in TickInputs._fields})._replace(
        tick_ms=None, roll_quality=None
    )
    new_state, outputs, bitrates = jax.vmap(
        tick_one, in_axes=(0, inp_axes, 0, 0, 0, 0, 0, 0, 0, 0)
    )(state, inp, send_bits, drop_bits, switch_bits, need_kf,
      pkts_sent_g, sent_bytes_g, fwd_packets, fwd_bytes)

    # ---- phase 2: allocation with the room's FULL track axis -----------
    # Cross-page coupling #2: the budget algebra ranks layers across all
    # of a room's tracks. Each page gathers its room's MT·TP (== logical
    # T) track entries through tmembers; rows the room never allocated
    # get the dense-init fill values (bitrates 0, unsubscribed, caps at
    # init), so the operand set is bit-identical to the dense plane's.
    def gtrack(x, fill):  # [P, TP, ...] per-track-page → [P, MT, TP, ...]
        g = x[mem]
        m = mvalid.reshape((P, MT) + (1,) * (g.ndim - 2))
        return jnp.where(m, g, fill)

    def to_st(x):  # [P, MT, TP, SP] → [P, SP, MT*TP]
        return x.transpose(0, 3, 1, 2).reshape(P, SP, MT * TP)

    bit_g = gtrack(bitrates, 0.0).reshape(P, MT * TP, 4, 4)
    sub_g = to_st(gtrack(state.ctrl.subscribed, False))
    mut_g = to_st(gtrack(state.ctrl.sub_muted, False))
    msp_g = to_st(gtrack(state.ctrl.max_spatial, L - 1))
    mtp_g = to_st(gtrack(state.ctrl.max_temporal, 3))
    video_active = (
        state.meta.is_video & state.meta.published & ~state.meta.pub_muted
    )
    va_g = gtrack(video_active, False).reshape(P, MT * TP)
    alloc_muted = ~(sub_g & va_g[:, None, :] & ~mut_g)        # [P, SP, MT*TP]
    target_full, _used, deficient = allocation.allocate_budget_rooms(
        bit_g, msp_g, mtp_g, alloc_muted, outputs.committed_bps,
        allow_pause=bwe_params.allow_pause,
    )                                                          # [P, SP, MT*TP]
    # Keep only this page's own tracks: every (tp, sp) block is computed
    # by exactly one page, so the logical [R, S, T] targets reassemble
    # from the pool without duplication.
    tgt4 = target_full.reshape(P, SP, MT, TP)
    own_tp = jnp.clip(table.pg_tp, 0, MT - 1)
    tgt_own = jnp.take_along_axis(
        tgt4, own_tp[:, None, None, None], axis=2
    )[:, :, 0, :]                                              # [P, SP, TP]
    tgt_ts = tgt_own.transpose(0, 2, 1)                        # [P, TP, SP]
    sel_state = selector.set_target(
        sel_state,
        jnp.clip(allocation.spatial_of(tgt_ts), -1, L - 1),
        allocation.temporal_of(tgt_ts),
    )
    any_deficient = jnp.any(deficient, axis=-1)                # [P, SP]
    sub_q = jnp.where(
        outputs.congested,
        quality.QUALITY_POOR,
        jnp.where(any_deficient, quality.QUALITY_GOOD,
                  quality.QUALITY_EXCELLENT),
    ).astype(jnp.int32)
    new_state = new_state._replace(sel=sel_state)
    outputs = outputs._replace(
        target_layers=tgt_own,
        deficient=any_deficient,
        sub_quality=sub_q,
    )
    # Freeze unmapped pages: zero inputs alone do NOT make a free page a
    # fixed point (pacer tokens, BWE sample age, and tracker windows all
    # advance per tick — unbounded counter drift), so pin dead rows to
    # their pre-tick values. This makes the module invariant — a free
    # page always holds pristine init state — a property of the tick
    # itself rather than of reinit-on-free alone, and it is the contract
    # the live-extent path relies on to skip dead pages entirely.
    live = table.pg_room >= 0                                      # [P]

    def _freeze(n, o):
        return jnp.where(live.reshape((P,) + (1,) * (n.ndim - 1)), n, o)

    new_state = jax.tree.map(_freeze, new_state, state)
    return new_state, outputs


# ---------------------------------------------------------------------------
# Live-extent fused tick: pay compute only for mapped pages.
#
# The stock pooled tick above computes every pool row and masks the dead
# ones. This variant takes the LIVE page extents as explicit operands —
# `live_rows [NL]` (pool ids of mapped pages, host-derived from the same
# device-table mirror the upload pinned, padded to a pow2 bucket by
# repeating a LIVE row) and `live_inv [P]` (pool id → compact index,
# 0 for dead rows, only ever read masked) — and runs every phase over
# the compact [NL] batch:
#
#   phase 0  ops/paged_kernel.decide_pages — one Pallas grid step per
#            live page (the page table is the scalar-prefetch operand;
#            dead pages are never *scheduled*, not merely masked), fusing
#            the selector algebra, egress bit packing, send sums, and the
#            [5,T,K,L] stats/tracker routing selects into one pass.
#   phase 1  the vmapped dense room core over [NL] rows, with the
#            kernel's routed stats passed through (`routed_stats`).
#   phase 2  the cross-track allocation over [NL] rows; a live page's
#            tmembers only ever reference live pages, so the gather
#            stays inside the compact batch via `live_inv`.
#
# Dead rows: state is untouched (the stock tick's freeze makes pristine
# init a fixed point) and outputs are one shared constant — a 1-page
# representative free page ticked in-trace from the init template, so
# traced scalars (tick_ms, roll_quality) flow into it and the result is
# bit-identical to what the stock tick computes for every dead row.
# ---------------------------------------------------------------------------


@device_entry("paged.dead_page_outputs")
def dead_page_outputs(
    MT: int, TP: int, K: int, SP: int,
    inp: TickInputs,
    audio_params: audio.AudioLevelParams = audio.AudioLevelParams(),
    bwe_params: bwe.BWEParams = bwe.BWEParams(),
    red_enabled: bool = True,
) -> TickOutputs:
    """TickOutputs of ONE free page under this tick's scalar inputs.

    Free pages hold pristine init state (enforced by the tick's freeze)
    and zero inputs, so every dead row's outputs equal this constant.
    Computed in-trace on a 1-page pool with the SAME MT (the phase-2
    gather width) so the operand set matches a dead row bit-for-bit.
    """
    rep_dims = PagedDims(
        rooms=1, tracks=MT * TP, pkts=K, subs=SP,
        tpage=TP, spage=SP, pool_pages=1,
    )
    rep_state = page_init_template(rep_dims)

    def z(a):
        return jnp.zeros((1,) + a.shape[1:], a.dtype)

    rep_inp = TickInputs(**{
        f: (getattr(inp, f) if f in ("tick_ms", "roll_quality")
            else z(getattr(inp, f)))
        for f in TickInputs._fields
    })
    _, rep_out = paged_plane_tick(
        rep_state, rep_inp, init_table(rep_dims),
        audio_params, bwe_params, red_enabled=red_enabled,
    )
    return rep_out


def broadcast_dead_outputs(rep_out: TickOutputs, P: int) -> TickOutputs:
    """Tile the representative free page's outputs to the full pool."""
    return jax.tree.map(
        lambda r: jnp.broadcast_to(r, (P,) + r.shape[1:]), rep_out
    )


@device_entry("paged.paged_plane_tick_live")
def paged_plane_tick_live(
    state: PlaneState,
    inp: TickInputs,
    table: PageTable,
    live_rows: jax.Array,   # [NL] int32 pool ids, pow2-padded with live dups
    live_inv: jax.Array,    # [P] int32 pool id → compact index (dead → 0)
    decide,                 # ops/paged_kernel.LiveDecide (compact phase 0)
    audio_params: audio.AudioLevelParams = audio.AudioLevelParams(),
    bwe_params: bwe.BWEParams = bwe.BWEParams(),
    red_enabled: bool = True,
):
    """Phases 1–2 of the live-extent tick over the compact [NL] batch,
    plus the scatter back to pool shape. `decide` is phase 0's output
    (ops/paged_kernel.decide_pages). Requires NL >= 1 — the all-dead
    pool is the caller's trivial case (state unchanged, dead fill).

    Bit-parity with `paged_plane_tick`: every op here is the stock op
    over a gathered row subset — int algebra is order-independent and
    the float chains are per-row identical across batch shapes — and
    padded duplicate rows scatter identical values.
    """
    L = MAX_LAYERS
    P, MT = table.tmembers.shape
    TP = state.meta.is_video.shape[1]
    SP = state.ctrl.subscribed.shape[2]
    NL = live_rows.shape[0]

    tm_c = table.tmembers[live_rows]                  # [NL, MT]
    mvalid = tm_c >= 0
    # A live page's valid tmembers always name live pages, so the
    # cross-page gathers stay inside the compact batch.
    mem = live_inv[jnp.clip(tm_c, 0, P - 1)]          # [NL, MT]

    # Cross-page coupling #1 (see paged_plane_tick): per-sub send totals
    # across the room's track pages, now over compact rows.
    def gsum(x):  # [NL, SP] int32 → [NL, SP]
        return jnp.sum(jnp.where(mvalid[:, :, None], x[mem], 0), axis=1)

    pkts_sent_g = gsum(decide.pkts_sent)
    sent_bytes_g = gsum(decide.sent_bytes)

    state_c = jax.tree.map(lambda a: a[live_rows], state)
    inp_c = inp._replace(**{
        f: getattr(inp, f)[live_rows]
        for f in TickInputs._fields if f not in ("tick_ms", "roll_quality")
    })

    # ---- phase 1: per-page core over live rows only --------------------
    inp_axes = TickInputs(**{f: 0 for f in TickInputs._fields})._replace(
        tick_ms=None, roll_quality=None
    )

    def tick_one(st, i, sb, db, wb, nk, ps, sby, fp, fby, rs):
        return plane._room_tick(st, i, sb, db, wb, nk, ps, sby, fp, fby,
                                audio_params, bwe_params, red_enabled,
                                routed_stats=rs)

    rs = (decide.st, decide.tr) if decide.st is not None else None
    rs_axes = (0, 0) if rs is not None else None
    new_c, outputs_c, bitrates = jax.vmap(
        tick_one, in_axes=(0, inp_axes, 0, 0, 0, 0, 0, 0, 0, 0, rs_axes)
    )(state_c, inp_c, decide.send_bits, decide.drop_bits,
      decide.switch_bits, decide.need_kf, pkts_sent_g, sent_bytes_g,
      decide.fwd_packets, decide.fwd_bytes, rs)

    # ---- phase 2: allocation with the room's FULL track axis -----------
    # The stock phase 2 verbatim, with the tmembers gather routed through
    # live_inv so it reads compact rows.
    def gtrack(x, fill):  # [NL, TP, ...] → [NL, MT, TP, ...]
        g = x[mem]
        m = mvalid.reshape((NL, MT) + (1,) * (g.ndim - 2))
        return jnp.where(m, g, fill)

    def to_st(x):  # [NL, MT, TP, SP] → [NL, SP, MT*TP]
        return x.transpose(0, 3, 1, 2).reshape(NL, SP, MT * TP)

    bit_g = gtrack(bitrates, 0.0).reshape(NL, MT * TP, 4, 4)
    sub_g = to_st(gtrack(state_c.ctrl.subscribed, False))
    mut_g = to_st(gtrack(state_c.ctrl.sub_muted, False))
    msp_g = to_st(gtrack(state_c.ctrl.max_spatial, L - 1))
    mtp_g = to_st(gtrack(state_c.ctrl.max_temporal, 3))
    video_active = (
        state_c.meta.is_video & state_c.meta.published
        & ~state_c.meta.pub_muted
    )
    va_g = gtrack(video_active, False).reshape(NL, MT * TP)
    alloc_muted = ~(sub_g & va_g[:, None, :] & ~mut_g)      # [NL, SP, MT*TP]
    target_full, _used, deficient = allocation.allocate_budget_rooms(
        bit_g, msp_g, mtp_g, alloc_muted, outputs_c.committed_bps,
        allow_pause=bwe_params.allow_pause,
    )
    tgt4 = target_full.reshape(NL, SP, MT, TP)
    own_tp = jnp.clip(table.pg_tp[live_rows], 0, MT - 1)
    tgt_own = jnp.take_along_axis(
        tgt4, own_tp[:, None, None, None], axis=2
    )[:, :, 0, :]                                           # [NL, SP, TP]
    tgt_ts = tgt_own.transpose(0, 2, 1)                     # [NL, TP, SP]
    sel_state = selector.set_target(
        decide.sel,
        jnp.clip(allocation.spatial_of(tgt_ts), -1, L - 1),
        allocation.temporal_of(tgt_ts),
    )
    any_deficient = jnp.any(deficient, axis=-1)             # [NL, SP]
    sub_q = jnp.where(
        outputs_c.congested,
        quality.QUALITY_POOR,
        jnp.where(any_deficient, quality.QUALITY_GOOD,
                  quality.QUALITY_EXCELLENT),
    ).astype(jnp.int32)
    new_c = new_c._replace(sel=sel_state)
    outputs_c = outputs_c._replace(
        target_layers=tgt_own,
        deficient=any_deficient,
        sub_quality=sub_q,
    )

    # ---- scatter back to pool shape ------------------------------------
    # Dead state rows are untouched (frozen at pristine init by
    # contract); dead output rows get the shared representative fill.
    # Padded duplicate live rows scatter identical values.
    new_state = jax.tree.map(
        lambda full, c: full.at[live_rows].set(c), state, new_c
    )
    rep_out = dead_page_outputs(
        MT, TP, inp.sn.shape[2], SP, inp,
        audio_params, bwe_params, red_enabled,
    )
    outputs = jax.tree.map(
        lambda r, c: jnp.broadcast_to(
            r, (P,) + r.shape[1:]
        ).at[live_rows].set(c),
        rep_out, outputs_c,
    )
    return new_state, outputs


@device_entry("paged.paged_plane_tick_fused")
def paged_plane_tick_fused(
    state: PlaneState,
    inp: TickInputs,
    table: PageTable,
    live_rows,
    live_inv,
    audio_params: audio.AudioLevelParams = audio.AudioLevelParams(),
    bwe_params: bwe.BWEParams = bwe.BWEParams(),
    red_enabled: bool = True,
    use_pallas: bool | None = None,
    interpret: bool = False,
):
    """The whole live-extent tick in one trace: phase-0 kernel + live
    phases 1–2 + scatter. The served step (`runtime/paged_runtime.py`
    `_build_live_step`) is this entry between the packed wire's unpack
    and pack, one program a tick; `live_pages` of the tick record is
    what its grid ran over."""
    from livekit_server_tpu.ops import paged_kernel

    live_rows = jnp.asarray(live_rows, jnp.int32)
    live_inv = jnp.asarray(live_inv, jnp.int32)
    if live_rows.shape[0] == 0:
        TP = state.meta.is_video.shape[1]
        SP = state.ctrl.subscribed.shape[2]
        P, MT = table.tmembers.shape
        rep = dead_page_outputs(
            MT, TP, inp.sn.shape[2], SP, inp,
            audio_params, bwe_params, red_enabled,
        )
        return state, broadcast_dead_outputs(rep, P)
    base = (
        state.ctrl.subscribed
        & ~state.ctrl.sub_muted
        & (state.meta.published & ~state.meta.pub_muted)[:, :, None]
    )
    dec = paged_kernel.decide_pages(
        state.sel, state.meta.is_svc, state.meta.is_video, base, inp,
        live_rows, wire_overhead=pacer.WIRE_OVERHEAD_BYTES,
        use_pallas=use_pallas, interpret=interpret,
    )
    return paged_plane_tick_live(
        state, inp, table, live_rows, live_inv, dec,
        audio_params, bwe_params, red_enabled,
    )


# ---------------------------------------------------------------------------
# Page-table delta lane (device side) — the page analog of
# pack_ctrl_rows/apply_ctrl_delta: alloc/free/grow/compact events upload
# O(dirty pages) table rows, never the whole table.
# ---------------------------------------------------------------------------


def pack_table_delta(pager, delta, pad_pages_to=None, pad_rooms_to=None):
    """Host half: gather the table rows dirtied by a drained PageDelta
    from the pager's canonical numpy mirrors. Dirty pages = fresh +
    freed + both ends of every move + every current page of a dirty
    room (tmembers of ALL of a room's pages change when its grid grows).
    Padding repeats row 0 (identical values → deterministic scatter) so
    the device applier compiles per pow2 bucket."""
    pages: set[int] = set(int(p) for p in delta.fresh_pages)
    pages.update(int(p) for p in delta.freed_pages)
    for src, dst in delta.moves:
        pages.add(int(src))
        pages.add(int(dst))
    for r in delta.rooms:
        pages.update(int(p) for p in pager.pages_of_room(int(r)))
    page_rows = np.asarray(sorted(pages), np.int32)
    room_rows = np.asarray(delta.rooms, np.int32)

    def pad(rows, to):
        if to is not None and 0 < len(rows) < to:
            rows = np.concatenate([rows, np.repeat(rows[:1], to - len(rows))])
        return rows

    page_rows = pad(page_rows, pad_pages_to)
    room_rows = pad(room_rows, pad_rooms_to)
    return (
        page_rows,
        pager.tmembers[page_rows],
        pager.pg_room[page_rows],
        pager.pg_tp[page_rows],
        pager.pg_sp[page_rows],
        room_rows,
        pager.rooms_pages[room_rows],
    )


@device_entry("paged.apply_table_delta")
def apply_table_delta(
    table: PageTable,
    page_rows, tmember_rows, pg_room_rows, pg_tp_rows, pg_sp_rows,
    room_rows, rooms_pages_rows,
) -> PageTable:
    """Device half (traced; jit with `table` donated): scatter the
    dirtied rows into the device table. A room row past the table is
    dropped: the caller's padding when no room row changed."""
    return PageTable(
        rooms_pages=table.rooms_pages.at[room_rows].set(
            rooms_pages_rows, mode="drop"),
        tmembers=table.tmembers.at[page_rows].set(tmember_rows),
        pg_room=table.pg_room.at[page_rows].set(pg_room_rows),
        pg_tp=table.pg_tp.at[page_rows].set(pg_tp_rows),
        pg_sp=table.pg_sp.at[page_rows].set(pg_sp_rows),
    )


@device_entry("paged.page_init_template")
def page_init_template(dims: PagedDims) -> PlaneState:
    """A single init page ([1, TP, K, SP] PlaneState) — the scatter
    source for fresh/freed page re-init and the fill for unmapped
    regions in pooled→logical translation."""
    return plane.init_state(PlaneDims(1, dims.tpage, dims.pkts, dims.spage))


@device_entry("paged.reinit_pages")
def reinit_pages(state: PlaneState, rows, template: PlaneState) -> PlaneState:
    """Device side (traced): reset `rows` to pristine init state — run
    for freshly allocated pages (a new room must not inherit the prior
    tenant's cursors) AND freed pages (stale state must stop computing).
    Duplicate rows are fine (identical values)."""
    n = rows.shape[0]

    def f(leaf, tleaf):
        return leaf.at[rows].set(
            jnp.broadcast_to(tleaf, (n,) + tleaf.shape[1:]).astype(leaf.dtype)
        )

    return jax.tree.map(f, state, template)


@device_entry("paged.move_state_rows")
def move_state_rows(state: PlaneState, src, dst) -> PlaneState:
    """Device side (traced): replay compaction relocations as page-row
    copies. Gather-then-scatter on the functional pre-move state, so
    overlapping src/dst sets are safe; dst rows are unique by
    construction (pad by repeating move 0)."""

    def f(leaf):
        return leaf.at[dst].set(leaf[src])

    return jax.tree.map(f, state)


# ---------------------------------------------------------------------------
# Host-side layout translation: pooled ↔ logical (numpy).
#
# Leaf-kind table — every PlaneState leaf is one of:
#   "track":     [R, T·m, *tail]  (stats/tracker rows are t-major, so a
#                track page's m rows are one contiguous block)
#   "sub":       [R, S, *tail]
#   "track_sub": [R, T, S, *tail]
# and the pooled counterpart replaces (R, T, S) with (P, TP, SP).
# ---------------------------------------------------------------------------

_K_TRACK, _K_SUB, _K_TS = "track", "sub", "track_sub"


def _kind_tree(template: PlaneState) -> PlaneState:
    def const(tree, kind):
        return jax.tree.map(lambda _: kind, tree)

    return PlaneState(
        meta=const(template.meta, _K_TRACK),
        ctrl=const(template.ctrl, _K_TS),
        stats=const(template.stats, _K_TRACK),
        audio_state=const(template.audio_state, _K_TRACK),
        sel=const(template.sel, _K_TS),
        bwe_state=const(template.bwe_state, _K_SUB),
        delay_bwe=const(template.delay_bwe, _K_SUB),
        tracker=const(template.tracker, _K_TRACK),
        pacer_state=const(template.pacer_state, _K_SUB),
        red_state=const(template.red_state, _K_TRACK),
        temporal_bytes=_K_TRACK,
    )


class LayoutXlate:
    """Pooled ↔ logical translation for one page-table snapshot.

    Built from the pager's numpy mirrors; cache per pager epoch (the
    index arrays are the only state). Reads follow duplicate-everywhere
    /read-from-one: track kinds from sp==0 pages, sub kinds from tp==0
    pages, track_sub kinds from every page (each block is unique).
    Writes go to ALL of a room's pages, re-establishing the duplication
    invariant — which is exactly what restore and row repair need.
    """

    def __init__(self, dims: PagedDims, pg_room, pg_tp, pg_sp):
        self.dims = dims
        self.pg_room = np.asarray(pg_room, np.int64)
        self.pg_tp = np.asarray(pg_tp, np.int64)
        self.pg_sp = np.asarray(pg_sp, np.int64)
        self.occ = self.pg_room >= 0
        self.sp0 = self.occ & (self.pg_sp == 0)
        self.tp0 = self.occ & (self.pg_tp == 0)

    # -- generic state trees ---------------------------------------------

    def state_to_logical(self, pooled_tree, fill_tree):
        """Pooled PlaneState (numpy-able) → logical PlaneState of numpy
        arrays; unmapped regions come from `fill_tree` (the logical init
        state), which is what makes checkpoints layout-independent."""
        kinds = _kind_tree(fill_tree)
        return jax.tree.map(self._leaf_to_logical, kinds, pooled_tree, fill_tree)

    def state_to_pooled(self, logical_tree, pooled_init_tree):
        """Logical PlaneState → pooled PlaneState of numpy arrays; free
        pages keep `pooled_init_tree` values. Writes every page of every
        room (the duplication invariant holds by construction)."""
        kinds = _kind_tree(logical_tree)
        return jax.tree.map(self._leaf_to_pooled, kinds, logical_tree,
                            pooled_init_tree)

    def _views(self, kind, logical, pooled):
        d = self.dims
        R, T, S, P = d.rooms, d.tracks, d.subs, d.pool_pages
        MT, TP, MS, SP = d.max_tpages, d.tpage, d.max_spages, d.spage
        if kind == _K_TRACK:
            w = logical.size // (R * T)
            return (logical.reshape(R, MT, TP, w), pooled.reshape(P, TP, w))
        if kind == _K_SUB:
            w = logical.size // (R * S)
            return (logical.reshape(R, MS, SP, w), pooled.reshape(P, SP, w))
        w = logical.size // (R * T * S)
        return (
            logical.reshape(R, MT, TP, MS, SP, w),
            pooled.reshape(P, TP, SP, w),
        )

    def _leaf_to_logical(self, kind, pl, fill):
        pl = np.ascontiguousarray(np.asarray(pl))
        # C order, said: a host copy of a TPU array can come back in the
        # device's own strides, a copy "as laid out" keeps them, and the
        # views below are views only of a C-contiguous array (of any
        # other, `reshape` copies and the writes are lost).
        out = np.array(np.asarray(fill), copy=True, order="C")
        lv, pv = self._views(kind, out, pl)
        if kind == _K_TRACK:
            sel = self.sp0
            lv[self.pg_room[sel], self.pg_tp[sel]] = pv[sel]
        elif kind == _K_SUB:
            sel = self.tp0
            lv[self.pg_room[sel], self.pg_sp[sel]] = pv[sel]
        else:
            sel = self.occ
            lv[self.pg_room[sel], self.pg_tp[sel], :, self.pg_sp[sel]] = pv[sel]
        return out

    def _leaf_to_pooled(self, kind, lg, pooled_init):
        lg = np.ascontiguousarray(np.asarray(lg))
        out = np.array(np.asarray(pooled_init), copy=True, order="C")
        lv, pv = self._views(kind, lg, out)
        sel = self.occ
        if kind == _K_TRACK:
            pv[sel] = lv[self.pg_room[sel], self.pg_tp[sel]]
        elif kind == _K_SUB:
            pv[sel] = lv[self.pg_room[sel], self.pg_sp[sel]]
        else:
            pv[sel] = lv[self.pg_room[sel], self.pg_tp[sel], :, self.pg_sp[sel]]
        return out

    # -- tick I/O --------------------------------------------------------

    def stage_inputs(self, pkt, fb, tf):
        """Packed LOGICAL tick inputs → packed POOLED inputs, duplicating
        per the module-doc staging rule: a track page's packets go to
        every sp-duplicate (the formula only reads pg_tp) and a sub
        page's feedback to every tp-duplicate. Free pages read zeros."""
        d = self.dims
        R, MT, TP = d.rooms, d.max_tpages, d.tpage
        MS, SP, K = d.max_spages, d.spage, d.pkts
        roomc = np.where(self.occ, self.pg_room, 0)
        tpc = np.where(self.occ, self.pg_tp, 0)
        spc = np.where(self.occ, self.pg_sp, 0)
        F = pkt.shape[0]
        pkt_p = pkt.reshape(F, R, MT, TP, K)[:, roomc, tpc]
        pkt_p = np.where(self.occ[None, :, None, None], pkt_p, 0)
        fb_p = fb.reshape(fb.shape[0], R, MS, SP)[:, roomc, spc]
        fb_p = np.where(self.occ[None, :, None], fb_p, 0.0)
        tf_p = tf.reshape(tf.shape[0], R, MT, TP)[:, roomc, tpc]
        tf_p = np.where(self.occ[None, :, None], tf_p, 0.0)
        return pkt_p, fb_p, tf_p

    def outputs_to_logical(self, out: TickOutputs) -> TickOutputs:
        """Pooled TickOutputs (numpy) → logical TickOutputs. Bit masks
        re-pack into the logical ⌈S/32⌉ words (a sub page never
        straddles a word: spage | 32); per-room counters sum over the
        room's pages; speakers merge per room (exact — see
        merge_speakers)."""
        d = self.dims
        R, T, K, S = d.logical
        TP, SP, MT = d.tpage, d.spage, d.max_tpages
        L = MAX_LAYERS
        W = mask_words(S)
        rooms = self.pg_room[self.occ]
        tps = self.pg_tp[self.occ]
        sps = self.pg_sp[self.occ]

        def bits(pb):  # [P, TP, K, 1] → [R, T, K, W]
            lw = np.zeros(R * T * K * W, np.uint32)
            vals = np.asarray(pb)[self.occ][:, :, :, 0].astype(np.uint32)
            shift = ((sps * SP) % 32).astype(np.uint32)
            words = (sps * SP) // 32
            shifted = vals << shift[:, None, None]
            t_glob = tps[:, None] * TP + np.arange(TP)[None, :]      # [N, TP]
            flat_idx = (
                (rooms[:, None, None] * T + t_glob[:, :, None]) * K
                + np.arange(K)[None, None, :]
            ) * W + words[:, None, None]
            np.bitwise_or.at(lw, flat_idx, shifted)
            return lw.view(np.int32).reshape(R, T, K, W)

        def ts(x, fill=0):  # [P, TP, SP, ...] → [R, T, S, ...]
            x = np.asarray(x)
            lg = np.full((R, MT, TP, d.max_spages, SP) + x.shape[3:],
                         fill, x.dtype)
            lg[rooms, tps, :, sps] = x[self.occ]
            return lg.reshape((R, T, S) + x.shape[3:])

        def sub(x, fill=0):  # [P, SP, ...] → [R, S, ...]
            x = np.asarray(x)
            lg = np.full((R, d.max_spages, SP) + x.shape[2:], fill, x.dtype)
            s = self.tp0
            lg[self.pg_room[s], self.pg_sp[s]] = x[s]
            return lg.reshape((R, S) + x.shape[2:])

        def track(x, fill=0):  # [P, TP, ...] → [R, T, ...]
            x = np.asarray(x)
            lg = np.full((R, MT, TP) + x.shape[2:], fill, x.dtype)
            s = self.sp0
            lg[self.pg_room[s], self.pg_tp[s]] = x[s]
            return lg.reshape((R, T) + x.shape[2:])

        def room_sum(x):  # [P] → [R]
            lg = np.zeros(R, np.asarray(x).dtype)
            np.add.at(lg, rooms, np.asarray(x)[self.occ])
            return lg

        # target_layers: [P, SP, TP] own-track slices → [R, S, T]
        tgt = np.asarray(out.target_layers)
        tgt_lg = np.full((R, d.max_spages, SP, MT, TP), -1,
                         tgt.dtype)
        tgt_lg[rooms, sps, :, tps] = tgt[self.occ]
        tgt_lg = tgt_lg.reshape(R, S, T)

        spk_lv, spk_tr = self.merge_speakers(
            out.speaker_levels, out.speaker_tracks
        )
        red_k = np.asarray(out.red_sn).shape[2]
        return TickOutputs(
            send_bits=bits(out.send_bits),
            drop_bits=bits(out.drop_bits),
            switch_bits=bits(out.switch_bits),
            need_keyframe=ts(out.need_keyframe, False),
            speaker_levels=spk_lv,
            speaker_tracks=spk_tr,
            congested=sub(out.congested, False),
            target_layers=tgt_lg,
            fwd_packets=room_sum(out.fwd_packets),
            fwd_bytes=room_sum(out.fwd_bytes),
            track_mos=track(out.track_mos, 0.0),
            track_quality=track(out.track_quality, quality.QUALITY_LOST),
            sub_quality=sub(out.sub_quality, quality.QUALITY_LOST),
            layer_live=track(out.layer_live),
            layer_fps=track(out.layer_fps, 0.0),
            track_loss_pct=track(out.track_loss_pct, 0.0),
            track_jitter_ms=track(out.track_jitter_ms, 0.0),
            track_bps=track(out.track_bps, 0.0),
            committed_bps=sub(out.committed_bps, 0.0),
            pacer_allowed=sub(out.pacer_allowed, 0.0),
            deficient=sub(out.deficient, False),
            red_sn=(track(out.red_sn) if red_k
                    else np.zeros((R, T, 0, np.asarray(out.red_sn).shape[3]),
                                  np.int32)),
            red_off=(track(out.red_off) if red_k
                     else np.zeros((R, T, 0, np.asarray(out.red_off).shape[3]),
                                   np.int32)),
            red_ok=(track(out.red_ok).astype(bool) if red_k
                    else np.zeros((R, T, 0, np.asarray(out.red_ok).shape[3]),
                                  bool)),
        )

    def merge_speakers(self, levels_p, tracks_p):
        """Per-room merge of per-page top-k speaker rankings, EXACT vs
        the dense top-k: a page's top-min(3, TP) dominates every track
        it omits, so the union of page rankings contains the global
        top-3; stable argsort on -level reproduces lax.top_k's
        lowest-index tie-break (including the dense all-zero case, which
        yields tracks 0, 1, 2 at level 0)."""
        d = self.dims
        R, T, TP = d.rooms, d.tracks, d.tpage
        levels_p = np.asarray(levels_p)
        tracks_p = np.asarray(tracks_p)
        lv = np.zeros((R, T), np.float32)
        for p in np.nonzero(self.sp0)[0]:
            r, tp = self.pg_room[p], self.pg_tp[p]
            for i in range(levels_p.shape[1]):
                tr = tracks_p[p, i]
                if tr >= 0:
                    lv[r, tp * TP + tr] = levels_p[p, i]
        k = min(SPEAKER_TOP_K, T)
        order = np.argsort(-lv, axis=1, kind="stable")[:, :k]
        out_lv = np.take_along_axis(lv, order, axis=1).astype(np.float32)
        out_tr = order.astype(np.int32)
        if k < SPEAKER_TOP_K:
            pad = SPEAKER_TOP_K - k
            out_lv = np.pad(out_lv, ((0, 0), (0, pad)))
            out_tr = np.pad(out_tr, ((0, 0), (0, pad)), constant_values=-1)
        return out_lv, out_tr

    def sel_to_logical(self, sel_pooled, sel_fill):
        """Pooled SelectorState → logical (express-lane mirror): each
        leaf is track_sub kind."""
        return jax.tree.map(
            lambda pl, fl: self._leaf_to_logical(_K_TS, pl, fl),
            sel_pooled, sel_fill,
        )

    def page_mask_to_rooms(self, mask):
        """[P] per-page audit/violation mask → [R] per-room mask (OR of
        the room's pages) — the integrity monitor's map_audit_mask."""
        room_mask = np.zeros(self.dims.rooms, np.asarray(mask).dtype)
        np.bitwise_or.at(
            room_mask, self.pg_room[self.occ], np.asarray(mask)[self.occ]
        )
        return room_mask
