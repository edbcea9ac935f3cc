"""The served live-extent step of `PagedPlaneRuntime` (one program a tick,
`paged.paged_plane_tick_fused` between the packed wire's unpack and pack)
against its plain reference, the dense `PlaneRuntime` over
`models/plane.media_plane_tick`: the same seeded joins and packets through
`_device_step`, every output and every leaf of the logical state compared.
Then what the step leaves for its readers: one device program a live tick,
named `tick`; `live_pages` in the tick record; and every span of the
serving loop taken on the paged path as on the dense one."""

from __future__ import annotations

import asyncio

import jax
import numpy as np
import pytest

from livekit_server_tpu.models import paged, plane
from livekit_server_tpu.runtime import PlaneRuntime, trace
from livekit_server_tpu.runtime.ingest import PacketIn
from livekit_server_tpu.runtime.paged_runtime import PagedPlaneRuntime
from livekit_server_tpu.runtime.supervisor import PlaneSupervisor

DD = plane.PlaneDims(rooms=8, tracks=4, pkts=4, subs=8)
PD = paged.PagedDims(rooms=8, tracks=4, pkts=4, subs=8,
                     tpage=2, spage=4, pool_pages=32)
LIVE_MODES = ["interpret", "on"]    # the Pallas kernel interpreted; the gathered fallback

# The float leaves that may differ at all, and by how much: both are sums
# of products carried from tick to tick (the temporal layers' byte EMA of
# the state, the track bitrate it yields), and XLA:CPU contracts such a
# mul+add into an FMA in one program and not in the other (the live step
# gathers its rows first), so the last bit moves. Every other float leaf,
# like every integer and bool, is held to exact equality.
EMA_LEAVES = {"temporal_bytes", "track_bps"}
EMA_TOL = 1e-5


def _joins(rng) -> list[tuple]:
    """Rooms of seeded sizes: (name, tracks, subs, [(track, is_video)],
    [(track, sub) subscribed])."""
    rooms = []
    for r in range(5):
        nt, ns = int(rng.integers(1, 5)), int(rng.integers(1, 9))
        tracks = [(t, bool(rng.random() < 0.5)) for t in range(nt)]
        subs = [(t, s) for t in range(nt) for s in range(ns) if rng.random() < 0.7]
        rooms.append((f"r{r}", nt, ns, tracks, subs))
    return rooms


def _join(rt, row: int, room: tuple) -> None:
    name, nt, ns, tracks, subs = room
    handle = rt.slots.alloc_room(name)
    assert handle.row == row
    for t in range(nt):
        handle.alloc_track(f"t{t}")
    for s in range(ns):
        handle.alloc_sub(f"p{s}")
    for t, is_video in tracks:
        rt.set_track(row, t, published=True, is_video=is_video)
    for t, s in subs:
        rt.set_subscription(row, t, s, subscribed=True)


def _packets(rng, rooms, tick: int) -> list[PacketIn]:
    out = []
    for row, (_, _, _, tracks, _) in enumerate(rooms):
        for t, is_video in tracks:
            for j in range(int(rng.integers(0, 4))):
                k = tick * 4 + j
                out.append(PacketIn(
                    room=row, track=t, sn=(1000 * (row + 1) + 50 * t + k) & 0xFFFF,
                    ts=(960 * k) & 0xFFFFFFFF, size=int(rng.integers(40, 900)),
                    payload=b"x" * 40,
                    keyframe=(is_video and tick % 4 == 0 and j == 0),
                    audio_level=-int(rng.integers(20, 60))))
    return out


def _capture(rt, log: list) -> None:
    orig = rt._unpack_outputs

    def wrapped(buf):
        out = orig(buf)
        log.append(out)
        return out

    rt._unpack_outputs = wrapped


def _extent_masks(prt) -> tuple[np.ndarray, np.ndarray]:
    """[R, T] and [R, S]: the tracks and subscribers the paged layout
    backs with pages. Outside them it holds no state and reports the init
    fill, where the dense plane rolls its idle windows on."""
    d = prt.pdims
    tmask = np.zeros((d.rooms, d.max_tpages, d.tpage), bool)
    smask = np.zeros((d.rooms, d.max_spages, d.spage), bool)
    pg = prt.pager
    for p in np.nonzero(pg.pg_room >= 0)[0]:
        tmask[pg.pg_room[p], pg.pg_tp[p]] = True
        smask[pg.pg_room[p], pg.pg_sp[p]] = True
    return tmask.reshape(d.rooms, d.tracks), smask.reshape(d.rooms, d.subs)


OUT_KINDS = {
    "track": ("track_mos", "track_quality", "layer_live", "layer_fps",
              "track_loss_pct", "track_jitter_ms", "track_bps",
              "red_sn", "red_off", "red_ok"),
    "sub": ("congested", "sub_quality", "committed_bps", "pacer_allowed",
            "deficient"),
    "whole": ("send_bits", "drop_bits", "switch_bits", "need_keyframe",
              "speaker_levels", "speaker_tracks", "fwd_packets", "fwd_bytes"),
}


def _same(name: str, a, b, where) -> None:
    a, b = np.asarray(a), np.asarray(b)
    assert a.shape == b.shape and a.dtype == b.dtype, (where, name)
    if a.dtype.kind == "f" and name in EMA_LEAVES:
        np.testing.assert_allclose(a, b, rtol=EMA_TOL, atol=EMA_TOL,
                                   err_msg=f"{where} {name}")
    else:
        assert np.array_equal(a, b), (where, name, a.dtype)


def _assert_outputs_equal(tick: int, dense_out, paged_out, tmask, smask) -> None:
    assert set(sum(OUT_KINDS.values(), ())) | {"target_layers"} == set(dense_out._fields)
    for f in OUT_KINDS["whole"]:
        _same(f, getattr(dense_out, f), getattr(paged_out, f), tick)
    for kind, mask in (("track", tmask), ("sub", smask)):
        for f in OUT_KINDS[kind]:
            a, b = np.asarray(getattr(dense_out, f)), np.asarray(getattr(paged_out, f))
            _same(f, a[mask], b[mask], tick)
    both = smask[:, :, None] & tmask[:, None, :]                 # [R, S, T]
    _same("target_layers", np.asarray(dense_out.target_layers)[both],
          np.asarray(paged_out.target_layers)[both], tick)


async def _assert_state_equal(dense, prt, where) -> None:
    """Every leaf of the logical state. The dense state is put through the
    paged layout's own translation (to pages and back) first, which keeps
    what the pages back and gives the init fill elsewhere."""
    async with prt.state_lock:
        logical = prt._to_logical_state()
        xlate = prt._xlate_cached()
        dense_np = jax.tree.map(np.asarray, dense.state)
        backed = xlate.state_to_logical(
            xlate.state_to_pooled(dense_np, prt._pooled_fill()), prt._logical_fill())
    leaves = jax.tree_util.tree_leaves_with_path(backed)
    others = jax.tree.leaves(logical)
    assert len(leaves) == len(others) > 50
    for (path, a), b in zip(leaves, others):
        _same(jax.tree_util.keystr(path).rsplit(".", 1)[-1], a, b, where)


@pytest.mark.parametrize("mode", LIVE_MODES)
async def test_served_live_step_equals_the_dense_reference(mode):
    rng = np.random.default_rng(20260930)
    rooms = _joins(rng)
    dense = PlaneRuntime(DD, tick_ms=10)
    prt = PagedPlaneRuntime(PD, tick_ms=10, paged_kernel=mode)
    dense_log, paged_log = [], []
    for rt, log in ((dense, dense_log), (prt, paged_log)):
        _capture(rt, log)
        for row, room in enumerate(rooms):
            _join(rt, row, room)
    forwarded = 0
    for tick in range(12):
        if tick == 5:
            # Control changes under way, inside the pages the rooms hold. (A
            # room that joins under way differs for its first tick in either
            # paged tick, stock or live: its fresh pages start from the init
            # state, the dense row it takes has idled since the start.)
            for rt in (dense, prt):
                rt.set_subscription(0, 0, 1, subscribed=False)
                rt.set_subscription(2, 1, 0, subscribed=True, sub_muted=True)
                rt.set_track(4, 0, published=True, is_video=True)
        for pkt in _packets(rng, rooms, tick):
            dense.ingest.push(pkt)
            prt.ingest.push(pkt)
        rd = await dense.step_once()
        rp = await prt.step_once()
        _assert_outputs_equal(tick, dense_log[-1], paged_log[-1], *_extent_masks(prt))
        assert rd.fwd_packets == rp.fwd_packets
        assert np.array_equal(np.asarray(rd.egress_batch.sn), np.asarray(rp.egress_batch.sn))
        forwarded += rp.fwd_packets
        if tick in (4, 11):
            await _assert_state_equal(dense, prt, tick)
    assert forwarded > 300
    assert prt.stats["paged_kernel_ticks"] == 12
    assert prt.recent_ticks[-1]["live_pages"] == prt.pager.pages_mapped > 8


@pytest.mark.parametrize("mode", LIVE_MODES)
async def test_a_live_tick_is_one_device_program_named_tick(mode):
    """The compile ledger counts what the backend compiles: the first live
    tick of a runtime whose parameters no other test shares compiles one
    program inside `_step`, and its XLA module is `jit_tick`."""
    from livekit_server_tpu.ops import bwe

    unshared = bwe.BWEParams(nack_ratio_threshold=0.0817 + 0.001 * LIVE_MODES.index(mode))
    prt = PagedPlaneRuntime(PD, tick_ms=10, paged_kernel=mode, bwe_params=unshared)
    assert not hasattr(prt, "_live_decide") and not hasattr(prt, "_live_rest")
    _join(prt, 0, ("one", 2, 3, [(0, True), (1, False)], [(0, 1), (1, 2)]))
    st = prt._stage_host()
    async with prt.state_lock:
        prt._upload_ctrl()
        assert prt._live_rows.shape[0] >= prt._live_n == 1
        before = prt.compile_ledger.total
        state, buf = prt._step(prt.state, *st.packed)
        jax.block_until_ready(buf)
        assert prt.compile_ledger.total - before == 1
        prt.state = state
        args = (prt.state, prt.table, prt._live_rows, prt._live_inv, *st.packed)
        assert prt._live_tick.lower(*args).as_text().lstrip().startswith("module @jit_tick")
        # and a tick later the same program serves: nothing compiles
        before = prt.compile_ledger.total
        prt.state, buf = prt._step(prt.state, *st.packed)
        jax.block_until_ready(buf)
        assert prt.compile_ledger.total == before


async def test_tick_record_counts_the_live_pages_its_grid_ran_over():
    prt = PagedPlaneRuntime(PD, tick_ms=10, paged_kernel="on")
    await prt.step_once()                                  # nothing mapped: the dead tick
    rec = prt.recent_ticks[-1]
    assert rec["live_pages"] == 0 and rec["page_live_fraction"] == 0.0
    assert "paged_kernel_ms" not in rec
    _join(prt, 0, ("a", 1, 2, [(0, False)], [(0, 1)]))             # 1 x 1 pages
    await prt.step_once()
    assert prt.recent_ticks[-1]["live_pages"] == 1
    _join(prt, 1, ("b", 4, 8, [(0, True), (3, False)], [(0, 7)]))  # 2 x 2 pages
    await prt.step_once()
    rec = prt.recent_ticks[-1]
    assert rec["live_pages"] == 5 and rec["page_live_fraction"] == round(5 / 32, 4)
    # the grid is padded to its bucket (2, 4, 8 ... of this pool), the count is not
    assert prt.stats["paged_kernel_ticks"] == 3
    assert prt.stats["paged_kernel_steps"] == 0 + 2 + 8
    # the layout, for a reader that has the logical dims only
    assert (prt.stats["pager_tpage"], prt.stats["pager_spage"],
            prt.stats["pager_pool_pages"]) == (2, 4, 32)
    stock = PagedPlaneRuntime(PD, tick_ms=10, paged_kernel="off")
    await stock.step_once()
    assert "live_pages" not in stock.recent_ticks[-1]


# -- the serving loop's spans on the paged path -------------------------------

@pytest.fixture(scope="module")
def driven():
    """A paged runtime through three sequential ticks, a moment of the
    serving loop, pinned at depth 1 as `tests/test_trace.py` pins the dense,
    and one checkpoint of its supervisor."""
    prt = PagedPlaneRuntime(PD, tick_ms=5, paged_kernel="on", trace_ring_ticks=64)
    prt.choose_depth = lambda *a: (1, 0)
    sup = PlaneSupervisor(prt)

    async def room_checkpoints():
        await asyncio.sleep(0)

    sup.room_checkpoint_cb = room_checkpoints

    async def drive():
        _join(prt, 0, ("a", 2, 3, [(0, False), (1, True)], [(0, 1), (1, 2)]))
        prt.on_tick(lambda result: None)
        for k in range(3):
            prt.ingest.push(PacketIn(room=0, track=0, sn=100 + k, ts=960 * k,
                                     size=8, payload=b"p" * 8))
            await prt.step_once()
        prt.start()
        await asyncio.sleep(0.1)
        await sup.checkpoint_now()      # under the running loop, as the supervisor's own
        await asyncio.sleep(0.05)
        await prt.stop()

    asyncio.run(drive())
    return prt


# what this plane does not run: no express lane (retier, mirror), no
# integrity monitor (audit), no UDP transport (rx)
NOT_RUN = ("stage/retier", "device/mirror", "device/audit", "rx")


@pytest.mark.parametrize("name", [s for s in trace.SPANS if s not in NOT_RUN])
def test_each_span_is_taken_on_the_paged_path(driven, name):
    totals = driven.spans.snapshot()
    assert set(totals) == set(trace.SPANS)
    assert totals[name]["n"] > 0, name
    if name != "egress/send":       # a callback that does nothing: 0 s is a reading
        assert totals[name]["busy_s"] > 0.0, name


def test_the_device_calls_parts_mean_on_the_paged_path_what_they_mean_on_the_dense(driven):
    looped = [r for r in driven.recent_ticks if r["depth"] == 1]
    assert looped, "the serving loop completed no tick"
    for r in driven.recent_ticks:
        assert r["device_dispatch_ms"] > 0.0 and r["device_fetch_ms"] > 0.0
        assert r["device_dispatch_ms"] + r["device_fetch_ms"] <= r["device_ms"] + 0.002
        assert r["live_pages"] == 1 and "paged_kernel_ms" not in r
    for r in driven.trace.snapshot():
        assert "kernel_s" not in r
        parts = r["dispatch_s"] + r["fetch_s"] + r["mirror_s"] + r["audit_s"]
        assert 0.9 * r["device_s"] <= parts <= r["device_s"]


# -- the layout's translation on host arrays that are not C-contiguous --------

def _device_strided(tree):
    """The same values in the strides a host copy of a TPU array can have:
    each leaf's two innermost axes laid out the other way round, under the
    stride-0 leading axis `_logical_fill` / `_pooled_fill` broadcast."""
    def f(a):
        a = np.asarray(a)
        if a.ndim < 3:
            return a
        one = np.ascontiguousarray(a[:1].swapaxes(-1, -2)).swapaxes(-1, -2)
        assert not one.flags["C_CONTIGUOUS"] or 1 in one.shape[-2:]
        return np.broadcast_to(one, a.shape)
    return jax.tree.map(f, tree)


@pytest.mark.parametrize("direction", ["to_logical", "to_pooled"])
async def test_translation_keeps_every_leaf_under_device_strides(direction):
    """On the chip a checkpoint (`state_to_logical`) and a restore
    (`state_to_pooled`) start from a copy of the fill, and the fill's leaves
    came from the device: a copy that keeps their strides cannot be viewed
    as pages, and what was written through the view was lost (PR 30, first
    chip run of dense against paged: `temporal_bytes` read all zero)."""
    prt = PagedPlaneRuntime(PD, tick_ms=10, paged_kernel="on")
    rng = np.random.default_rng(5)
    rooms = _joins(rng)
    for row, room in enumerate(rooms):
        _join(prt, row, room)
    for tick in range(3):
        for pkt in _packets(rng, rooms, tick):
            prt.ingest.push(pkt)
        await prt.step_once()
    async with prt.state_lock:
        logical = prt._to_logical_state()
        xlate = prt._xlate_cached()
        pooled = jax.tree.map(np.asarray, prt.state)
        lfill, pfill = prt._logical_fill(), prt._pooled_fill()
    assert np.count_nonzero(logical.temporal_bytes) > 0
    if direction == "to_logical":
        ours = xlate.state_to_logical(pooled, _device_strided(lfill))
        theirs = logical
    else:
        ours = xlate.state_to_pooled(logical, _device_strided(pfill))
        theirs = xlate.state_to_pooled(logical, pfill)
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(ours), jax.tree.leaves(theirs)):
        assert np.array_equal(a, b), jax.tree_util.keystr(path)
