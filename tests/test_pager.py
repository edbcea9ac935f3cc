"""Paged room-state plane: host buddy allocator (rounding, churn,
fragmentation, compaction, exhaustion), the free-page re-init
invariant, page-handle epoch discipline, `plane.pager_*` config
validation, page-backed admission headroom, and the runtime acceptance
criteria — dense↔paged bit-parity on a mixed-size room population,
layout-independent checkpoints, cross-layout room migration,
grow-on-join across a page boundary, and a seeded page-table SDC
drill (detect → table repair → room quarantine → row repair)."""

from __future__ import annotations

import numpy as np
import pytest

from livekit_server_tpu.config import ConfigError, load_config
from livekit_server_tpu.models import paged, plane
from livekit_server_tpu.runtime import PlaneRuntime
from livekit_server_tpu.runtime.governor import OverloadGovernor
from livekit_server_tpu.runtime.ingest import PacketIn
from livekit_server_tpu.runtime.integrity import BIT_TABLE, IntegrityMonitor
from livekit_server_tpu.runtime.paged_runtime import PagedPlaneRuntime
from livekit_server_tpu.runtime.pager import RoomPager, StalePageError
from livekit_server_tpu.runtime.slots import CapacityError, PagedSlotAllocator

DD = plane.PlaneDims(rooms=4, tracks=4, pkts=4, subs=8)
PD = paged.PagedDims(rooms=4, tracks=4, pkts=4, subs=8,
                     tpage=2, spage=4, pool_pages=16)
PD_WIDE = paged.PagedDims(rooms=4, tracks=4, pkts=4, subs=8,
                          tpage=2, spage=4, pool_pages=32)

# The mixed-size fixture: a 2-person room, the full-width room, and an
# odd-extent room whose sub count does not land on a page boundary.
ROOMS = [("a", 1, 2), ("b", 4, 8), ("c", 2, 5)]


def _pager(**kw) -> RoomPager:
    args = dict(rooms=4, tracks=4, subs=8, tpage=2, spage=4, pool_pages=16)
    args.update(kw)
    return RoomPager(**args)


# -- host allocator ----------------------------------------------------------

def test_alloc_page_rounding_and_slack():
    pg = _pager()
    assert pg.alloc_room(0, tracks=1, subs=2) == (2, 4)   # one page
    assert pg.alloc_room(1, tracks=4, subs=8) == (4, 8)   # full 2x2 grid
    assert pg.alloc_room(2, tracks=2, subs=5) == (2, 8)   # 1x2, subs round up
    st = pg.stats()
    assert st["pages_mapped"] == 1 + 4 + 2
    # room 2's 2-page grid reserved a pow2 run of 2 — no slack there; the
    # 1-page and 4-page rooms are exact too.
    assert st["internal_slack"] == st["pages_used"] - st["pages_mapped"]
    assert len(pg.pages_of_room(1)) == 4
    assert pg.extent(2) == (2, 8)
    # every mapped page's inverse maps agree with the room grids
    for row in (0, 1, 2):
        for p in pg.pages_of_room(row):
            assert pg.room_of_page(int(p)) == row


def test_buddy_coalesces_back_to_one_run_after_churn():
    pg = _pager()
    for round_ in range(3):
        for row, (_, tr, sb) in enumerate(ROOMS):
            pg.alloc_room(row, tracks=tr, subs=sb)
        for row in range(len(ROOMS)):
            pg.release_room(row)
    st = pg.stats()
    assert st["pages_used"] == 0
    assert st["pages_free"] == 16
    # full coalesce: one max-order free run, zero external fragmentation
    assert st["free_runs_by_order"] == {4: 1}
    assert st["fragmentation_ratio"] == 0.0
    assert st["allocs"] == 9 and st["frees"] == 9


def test_exhaustion_is_atomic_and_counted():
    # 1-page rooms over a 4-page pool: the 5th room must be refused
    # without disturbing the 4 resident ones.
    pg = RoomPager(rooms=8, tracks=2, subs=4, tpage=2, spage=4, pool_pages=4)
    for row in range(4):
        pg.alloc_room(row)
    before = pg.stats()
    with pytest.raises(CapacityError):
        pg.alloc_room(4)
    after = pg.stats()
    assert after["alloc_failures"] == 1
    assert after["pages_used"] == before["pages_used"] == 4
    assert len(pg.pages_of_room(4)) == 0
    # the failed alloc must leave no queued device events for room 4
    delta = pg.drain_delta()
    assert 4 not in delta.rooms.tolist()


def test_grow_keeps_existing_pages_and_fails_at_old_extent():
    pg = _pager()
    pg.alloc_room(0, tracks=1, subs=2)
    old_pages = set(pg.pages_of_room(0).tolist())
    ext = pg.grow_room(0, subs=8)
    assert ext == (2, 8)
    # grow never relocates: the original page survives in place
    assert old_pages <= set(pg.pages_of_room(0).tolist())
    assert pg.stats()["grows"] == 1

    # exhaustion mid-grow leaves the room at its old extent (tiny pool:
    # the 3 new grid cells need a 4-page run that does not exist)
    pg2 = RoomPager(rooms=2, tracks=4, subs=8, tpage=2, spage=4, pool_pages=4)
    pg2.alloc_room(0, tracks=1, subs=2)
    with pytest.raises(CapacityError):
        pg2.grow_room(0, tracks=4, subs=8)
    assert pg2.extent(0) == (2, 4)
    assert pg2.pages_reserved == 1


def test_compaction_packs_pool_and_reports_moves():
    pg = _pager()
    for row, (_, tr, sb) in enumerate(ROOMS):
        pg.alloc_room(row, tracks=tr, subs=sb)
    pg.drain_delta()
    # free the small rooms around the big one -> external fragmentation
    pg.release_room(0)
    pg.release_room(2)
    epoch_before = pg.epoch
    moves = pg.compact()
    assert pg.epoch > epoch_before
    assert len(moves) == 4                      # room 1's full grid moved
    dsts = sorted(d for _, d in moves)
    assert dsts == [0, 1, 2, 3]                 # packed to the pool bottom
    st = pg.stats()
    assert st["pages_used"] == 4
    # free space is fully buddy-coalesced above the live run: one run
    # per order, nothing stranded between rooms
    assert st["free_runs_by_order"] == {2: 1, 3: 1}
    assert st["compactions"] == 1
    # grids and inverse maps stayed consistent through the relocation
    for p in pg.pages_of_room(1):
        assert pg.room_of_page(int(p)) == 1


def test_freed_page_remapped_by_compaction_is_not_reinit():
    """Regression: a page released to the freed queue and then picked as
    a compaction move DESTINATION before the drain must not appear in
    freed_pages — the device re-init runs after the move replay and
    would wipe the relocated room state."""
    pg = _pager()
    for row, (_, tr, sb) in enumerate(ROOMS):
        pg.alloc_room(row, tracks=tr, subs=sb)
    pg.drain_delta()
    pg.release_room(0)
    pg.release_room(2)
    moves = pg.compact()
    dsts = {d for _, d in moves}
    # precondition: the hazard actually occurs in this scenario
    assert pg._freed & dsts, "scenario no longer exercises freed∩move-dst"
    delta = pg.drain_delta()
    freed = set(delta.freed_pages.tolist())
    assert not (freed & dsts)
    for p in freed:
        assert pg.pg_room[p] < 0                # only unmapped pages re-init
    # the vacated move sources do re-init (their stale state must not
    # forward if the pool hands them out again)
    assert freed == {s for s, _ in moves} - dsts


def test_page_handle_epoch_discipline():
    pg = _pager()
    pg.alloc_room(0)
    minted = pg.epoch
    pages = pg.pages_of_room(0)
    pg.check_epoch(minted)                      # no churn: still valid
    pg.alloc_room(1)                            # structural change
    with pytest.raises(StalePageError):
        pg.check_epoch(minted)
    # re-mint is the other sanctioned recovery
    assert np.array_equal(pg.pages_of_room(0), pages)
    pg.check_epoch(pg.epoch)


def test_pager_ctor_validation():
    with pytest.raises(ValueError):
        _pager(tpage=3)                         # not pow2
    with pytest.raises(ValueError):
        _pager(tpage=8)                         # does not divide tracks=4
    with pytest.raises(ValueError):
        _pager(spage=64, subs=64)               # sub page > mask word
    with pytest.raises(ValueError):
        _pager(pool_pages=12)                   # not pow2


# -- config knobs ------------------------------------------------------------

def test_pager_config_validation():
    cfg = load_config(yaml_text="""
development: true
plane:
  pager_enabled: true
  pager_tpage: 4
  pager_spage: 8
  pager_pool_pages: 256
""")
    assert cfg.plane.pager_enabled and cfg.plane.pager_pool_pages == 256

    with pytest.raises(ConfigError, match="pager_tpage must be a power"):
        load_config(yaml_text="development: true\nplane:\n"
                              "  pager_enabled: true\n  pager_tpage: 3")
    # pow2 and dividing the sub axis, but wider than the 32-bit mask word
    with pytest.raises(ConfigError, match="pager_spage must divide 32"):
        load_config(yaml_text="development: true\nplane:\n"
                              "  subs_per_room: 64\n"
                              "  pager_enabled: true\n  pager_spage: 64")
    with pytest.raises(ConfigError, match="pager_pool_pages"):
        load_config(yaml_text="development: true\nplane:\n"
                              "  pager_enabled: true\n  pager_pool_pages: 100")
    # divisor check against the actual plane axes
    with pytest.raises(ConfigError, match="must divide plane.subs_per_room"):
        load_config(yaml_text="development: true\nplane:\n"
                              "  subs_per_room: 20\n  pager_enabled: true")
    # knobs are inert while the pager is off
    cfg = load_config(yaml_text="development: true\nplane:\n  pager_tpage: 3")
    assert not cfg.plane.pager_enabled


# -- admission on real page headroom ----------------------------------------

def test_pool_exhaustion_denies_room_admission():
    # Every room is exactly one page; a 2-page pool admits two rooms.
    dims = paged.PagedDims(rooms=8, tracks=2, pkts=4, subs=4,
                           tpage=2, spage=4, pool_pages=2)
    rt = PagedPlaneRuntime(dims, tick_ms=10)
    gov = OverloadGovernor(rt)
    assert gov.should_admit("room")
    rt.slots.alloc_room("a")
    rt.slots.alloc_room("b")
    occ = rt.occupancy()
    # rows remain, but the page pool is the binding constraint
    assert occ["rooms_used"] == 2 < occ["rooms_capacity"]
    assert occ["pages_free"] == 0 and occ["admittable_rooms"] == 0
    assert not gov.should_admit("room")
    assert gov.should_admit("join")             # only NEW rooms are refused
    with pytest.raises(CapacityError):
        rt.slots.alloc_room("c")
    # the failed alloc must not leak the room row
    assert rt.occupancy()["rooms_used"] == 2
    rt.slots.release_room("a")
    assert rt.occupancy()["admittable_rooms"] == 1
    assert gov.should_admit("room")


def test_paged_allocator_grows_columns_through_pager():
    pg = _pager()
    slots = PagedSlotAllocator(pg)
    s = slots.alloc_room("r")
    assert (s.tracks.capacity, s.subs.capacity) == (2, 4)  # one-page extent
    for i in range(5):
        s.alloc_sub(f"p{i}")                    # 5th sub crosses spage=4
    assert s.subs.capacity == 8
    assert pg.extent(s.row).subs == 8
    occ = slots.occupancy()
    assert occ["subs_used"] == 5 and occ["subs_capacity"] == 8


# -- runtime: parity / checkpoints / migration / chaos -----------------------

def _setup_rooms(rt) -> None:
    for name, tr, sb in ROOMS:
        s = rt.slots.alloc_room(name)
        for i in range(tr):
            s.alloc_track(f"t{i}")
        for i in range(sb):
            s.alloc_sub(f"p{i}")
    rt.set_track(0, 0, published=True, is_video=True)
    rt.set_subscription(0, 0, 1, subscribed=True)
    rt.set_track(1, 0, published=True, is_video=True)
    rt.set_track(1, 3, published=True, is_video=False)
    for sub in range(8):
        rt.set_subscription(1, 0, sub, subscribed=True)
    rt.set_subscription(1, 3, 2, subscribed=True)
    rt.set_track(2, 1, published=True, is_video=False)
    rt.set_subscription(2, 1, 4, subscribed=True)


def _push(rt, tick: int) -> None:
    for room, track, base in [(0, 0, 100), (1, 0, 500), (1, 3, 900),
                              (2, 1, 1300)]:
        for j in range(2):
            sn = base + tick * 2 + j
            rt.ingest.push(PacketIn(
                room=room, track=track, sn=sn & 0xFFFF,
                ts=(960 * (tick * 2 + j)) & 0xFFFFFFFF,
                size=120, payload=b"x" * 120,
                keyframe=(tick == 0 and j == 0),
                audio_level=-(30 + (sn % 20)),
            ))


async def _run_ticks(rt, n: int, start: int = 0) -> None:
    for t in range(start, start + n):
        _push(rt, t)
        await rt.step_once()


def _capture(rt, log: list):
    orig = rt._unpack_outputs

    def wrapped(buf):
        out = orig(buf)
        log.append(out)
        return out

    rt._unpack_outputs = wrapped


def _round_up(n: int, p: int) -> int:
    return -(-n // p) * p


def _assert_outputs_match(tick: int, a, b) -> None:
    """a: dense logical outputs, b: paged logical outputs. Globally
    computed fields must match exactly; per-room fields must match
    within each room's PAGE-ROUNDED extent (outside it the paged layout
    has no backing state and reports the init fill)."""
    for f in ("send_bits", "drop_bits", "switch_bits", "need_keyframe",
              "speaker_levels", "speaker_tracks", "fwd_packets", "fwd_bytes"):
        va, vb = np.asarray(getattr(a, f)), np.asarray(getattr(b, f))
        assert np.array_equal(va, vb), (tick, f)
    exts = {row: (tr, sb) for row, (_, tr, sb) in enumerate(ROOMS)}
    for f in ("congested", "committed_bps", "pacer_allowed", "deficient",
              "sub_quality"):
        va, vb = np.asarray(getattr(a, f)), np.asarray(getattr(b, f))
        for r, (_, sb) in exts.items():
            sb_p = _round_up(sb, PD.spage)
            assert np.array_equal(va[r, :sb_p], vb[r, :sb_p]), (tick, f, r)
    for f in ("track_mos", "track_quality", "layer_live", "layer_fps",
              "track_loss_pct", "track_jitter_ms", "track_bps",
              "red_sn", "red_off", "red_ok"):
        va, vb = np.asarray(getattr(a, f)), np.asarray(getattr(b, f))
        for r, (tr, _) in exts.items():
            tr_p = _round_up(tr, PD.tpage)
            if f == "track_bps":
                # The one float field with a tolerance: an EMA whose mul+add
                # XLA:CPU contracts into an FMA in one graph and not the other.
                np.testing.assert_array_max_ulp(
                    va[r, :tr_p], vb[r, :tr_p], maxulp=2)
                continue
            assert np.array_equal(va[r, :tr_p], vb[r, :tr_p]), (tick, f, r)
    va, vb = np.asarray(a.target_layers), np.asarray(b.target_layers)
    for r, (tr, sb) in exts.items():
        tr_p, sb_p = _round_up(tr, PD.tpage), _round_up(sb, PD.spage)
        assert np.array_equal(va[r, :sb_p, :tr_p], vb[r, :sb_p, :tr_p]), \
            (tick, "target_layers", r)


async def test_dense_vs_paged_bit_parity_mixed_sizes():
    """The acceptance gate: the pooled layout is a pure re-arrangement —
    every tick decision on the mixed-size fixture is bit-identical to
    the dense plane, including egress sequence numbers."""
    dense = PlaneRuntime(DD, tick_ms=10)
    prt = PagedPlaneRuntime(PD, tick_ms=10)
    dlog, plog = [], []
    _capture(dense, dlog)
    _capture(prt, plog)
    _setup_rooms(dense)
    _setup_rooms(prt)
    for tick in range(12):
        _push(dense, tick)
        _push(prt, tick)
        rd = await dense.step_once()
        rp = await prt.step_once()
        _assert_outputs_match(tick, dlog[-1], plog[-1])
        assert rd.fwd_packets == rp.fwd_packets
        assert np.array_equal(np.asarray(rd.egress_batch.sn),
                              np.asarray(rp.egress_batch.sn)), tick
    assert dense.stats["fwd_packets"] == prt.stats["fwd_packets"] > 0


async def test_checkpoint_byte_parity_across_pool_layouts():
    """Checkpoints serialize LOGICAL rows, so the blob is independent of
    the pool geometry — and restoring into a different layout then
    ticking stays bit-identical to the source runtime."""
    p1 = PagedPlaneRuntime(PD, tick_ms=10)
    _setup_rooms(p1)
    await _run_ticks(p1, 8)
    blob1 = p1.encode_snapshot(p1.snapshot())

    p2 = PagedPlaneRuntime(PD_WIDE, tick_ms=10)
    _setup_rooms(p2)
    await _run_ticks(p2, 8)
    assert p2.encode_snapshot(p2.snapshot()) == blob1

    # restore the 16-page blob into a fresh 32-page runtime and diverge-check
    p3 = PagedPlaneRuntime(PD_WIDE, tick_ms=10)
    _setup_rooms(p3)
    p3.restore(p3.decode_snapshot(blob1))
    await _run_ticks(p1, 4, start=8)
    await _run_ticks(p3, 4, start=8)
    assert p1.encode_snapshot(p1.snapshot()) == p3.encode_snapshot(p3.snapshot())


def _alloc_full_room(rt, name: str):
    s = rt.slots.alloc_room(name)
    for i in range(4):
        s.alloc_track(f"t{i}")
    for i in range(8):
        s.alloc_sub(f"p{i}")
    return s


async def test_room_migration_across_layouts():
    """snapshot_room/restore_room move a room dense→paged and back with
    no bit drift (reference: a dense→dense restore of the same snapshot,
    since restore_room clears subscription masks on every layout)."""
    dense = PlaneRuntime(DD, tick_ms=10)
    _setup_rooms(dense)
    await _run_ticks(dense, 8)
    room_snap = dense.snapshot_room(1)

    prt = PagedPlaneRuntime(PD, tick_ms=10)
    s = _alloc_full_room(prt, "b")
    prt.restore_room(s.row, room_snap)
    paged_back = prt.snapshot_room(s.row)

    dref = PlaneRuntime(DD, tick_ms=10)
    sr = _alloc_full_room(dref, "b")
    dref.restore_room(sr.row, room_snap)
    ref = dref.snapshot_room(sr.row)
    for i, (x, y) in enumerate(zip(ref["arrays"], paged_back["arrays"])):
        assert np.array_equal(np.asarray(x), np.asarray(y)), i

    # paged -> dense direction round-trips too
    d2 = PlaneRuntime(DD, tick_ms=10)
    s2 = _alloc_full_room(d2, "b")
    d2.restore_room(s2.row, paged_back)
    for i, (x, y) in enumerate(zip(paged_back["arrays"],
                                   d2.snapshot_room(s2.row)["arrays"])):
        assert np.array_equal(np.asarray(x), np.asarray(y)), i


async def test_compaction_preserves_live_room_state():
    """Release the rooms around a live one, compact (its pages relocate
    into the freed bottom of the pool), and the room's logical state is
    bit-identical — the device-move + no-reinit-of-mapped-pages path."""
    prt = PagedPlaneRuntime(PD, tick_ms=10)
    _setup_rooms(prt)
    await _run_ticks(prt, 5)
    before = prt.snapshot_room(1)
    prt.slots.release_room("a")
    prt.slots.release_room("c")
    moves = prt.compact()                       # returns queued move count
    assert moves > 0
    after = prt.snapshot_room(1)
    for i, (x, y) in enumerate(zip(before["arrays"], after["arrays"])):
        assert np.array_equal(np.asarray(x), np.asarray(y)), i
    # and the plane still ticks cleanly on the compacted layout
    _push(prt, 5)
    res = await prt.step_once()
    assert res.fwd_packets > 0
    # Recompile watchdog: the first post-compaction tick above paid any
    # new pow2-bucket compiles; steady state on the compacted layout
    # must then hold the cache (zero XLA compiles per tick).
    prt.mark_warm()
    await _run_ticks(prt, 3, start=6)
    assert prt.compile_ledger.post_warmup == 0


async def test_grow_on_join_across_page_boundary():
    """A join past the room's current sub extent grows the page grid
    mid-stream; forwarding to the new subscriber works on the next tick."""
    prt = PagedPlaneRuntime(PD, tick_ms=10)
    s = prt.slots.alloc_room("g")
    s.alloc_track("t0")
    for i in range(3):
        s.alloc_sub(f"p{i}")
    prt.set_track(0, 0, published=True, is_video=False)
    prt.set_subscription(0, 0, 0, subscribed=True)

    async def tick(t):
        for j in range(2):
            prt.ingest.push(PacketIn(
                room=0, track=0, sn=100 + t * 2 + j, ts=960 * (t * 2 + j),
                size=90, payload=b"y" * 90, audio_level=-25))
        return await prt.step_once()

    for t in range(4):
        await tick(t)
    assert prt.pager.extent(0) == (2, 4)        # one page so far
    for i in range(3, 7):
        s.alloc_sub(f"p{i}")                    # crosses spage=4
    assert prt.pager.extent(0) == (2, 8)
    prt.set_subscription(0, 0, 6, subscribed=True)
    fwd = 0
    # First tick on the grown extent pays the new pow2 bucket's compile;
    # after that the watchdog must see a held cache (GC11 runtime half).
    res = await tick(4)
    fwd += res.fwd_packets
    prt.mark_warm()
    for t in range(5, 8):
        res = await tick(t)
        fwd += res.fwd_packets
    assert prt.compile_ledger.post_warmup == 0
    assert fwd > 0
    assert prt.pager.stats()["grows"] == 1


async def test_warm_compile_leaves_nothing_to_compile():
    """After `warm_compile` (and one tick of each kind, which the server's
    warm-up also runs) the churn a paged node sees compiles nothing: rooms
    of every size joining, a grid growing on a join, rooms restored into
    grids of different page counts, a release with compaction, and the
    table audit's repair with no room row to write."""
    prt = PagedPlaneRuntime(PD, tick_ms=10, paged_kernel="on")
    await prt.step_once()                       # the empty-pool tick
    async with prt.state_lock:
        prt.warm_compile()
    prt.mark_warm()

    _setup_rooms(prt)
    await _run_ticks(prt, 3)
    s = prt.slots.alloc_room("d")               # a fourth room, one page
    s.alloc_track("t0")
    s.alloc_sub("p0")
    prt.set_track(3, 0, published=True, is_video=False)
    prt.set_subscription(1, 0, 7, subscribed=False)
    await _run_ticks(prt, 2, start=3)
    snaps = {row: prt.snapshot_room(row) for row in (0, 1, 2)}
    assert len({len(prt.pager.pages_of_room(r)) for r in snaps}) == 3
    for row, snap in snaps.items():             # 1, 4 and 2 pages
        prt.restore_room(row, snap)
    prt.repair_room_row(1, snaps[1])
    await _run_ticks(prt, 2, start=5)
    prt.slots.release_room("a")
    prt.compact()
    await _run_ticks(prt, 2, start=7)
    victim = int(prt.pager.pages_of_room(1)[0])
    prt.table = prt.table._replace(             # a flipped table entry
        pg_room=np.asarray(prt.table.pg_room).copy())
    prt.table.pg_room[victim] = -1
    assert prt._audit_page_table() is not None and prt.table_repairs == 1
    assert prt._audit_page_table() is None
    await _run_ticks(prt, 2, start=9)
    assert prt.compile_ledger.post_warmup == 0, prt.compile_ledger.snapshot()


async def test_page_table_bitflip_detected_and_repaired():
    """SDC drill on the indirection layer itself: corrupt one mapped
    page's device pg_room entry. The next audit must spot the divergence
    from the last-sync mirrors, repair the table row from host canonical,
    flag the owning room with BIT_TABLE, quarantine it, and row-repair it
    from the checksummed checkpoint — then audit clean."""
    prt = PagedPlaneRuntime(PD, tick_ms=10)
    for room in range(3):
        s = prt.slots.alloc_room(f"r{room}")
        s.alloc_track("t0")
        s.alloc_sub("p0")
        s.alloc_sub("p1")
        prt.set_track(room, 0, published=True, is_video=False)
        prt.set_subscription(room, 0, 1, subscribed=True)

    def push_audio(i):
        for room in range(3):
            prt.ingest.push(PacketIn(room=room, track=0,
                                     sn=(1000 + i) & 0xFFFF, ts=960 * i,
                                     size=50, payload=b"a"))

    for i in range(2):
        push_audio(i)
        await prt.step_once()
    async with prt.state_lock:
        blob = prt.encode_snapshot(prt.snapshot())
    mon = IntegrityMonitor(prt, audit_every_ticks=4, max_row_repairs=3,
                           storm_threshold=4)
    mon.snapshot_provider = lambda: prt.decode_snapshot(blob)
    escalations: list[str] = []
    mon.escalate_cb = escalations.append
    prt.integrity = mon

    # flip a mapped page of room 1 to "free" on the DEVICE table only
    victim = int(prt.pager.pages_of_room(1)[0])
    prt.table = prt.table._replace(
        pg_room=prt.table.pg_room.at[victim].set(-1))
    assert prt.table_repairs == 0

    table_hit = False
    for i in range(2, 14):
        push_audio(i)
        await prt.step_once()
        if mon.last_mask and mon.last_mask[1] & BIT_TABLE:
            table_hit = True
    assert table_hit, "audit never flagged the table-corrupted room"
    assert prt.table_repairs >= 1
    assert mon.rows_quarantined >= 1 and mon.rows_repaired >= 1
    assert escalations == []                    # row repair, no restart
    assert sorted(mon.quarantined) == []        # released after repair
    # device table re-converged to the host canonical mirrors
    assert np.array_equal(np.asarray(prt.table.pg_room), prt.pager.pg_room)
    # and the plane keeps forwarding on the repaired layout
    push_audio(14)
    res = await prt.step_once()
    assert res.fwd_packets > 0
