"""The serving loop and its pipeline (runtime/plane_runtime.py _run).

Covers the pipeline's invariants end to end: the step_once/serving-loop
mutual exclusion guard, cross-tick egress ordering under overlap, the
depth the loop chooses a tick at a time (`choose_depth`) and the wire
order across its changes, bounded pipeline depth when the device stalls
(faultinject), dirty-row delta
control uploads vs the full `_replace` path, and the double-buffered
ingest staging sets that let stage N+1 overlap device N.
"""

import asyncio

import numpy as np
import pytest

from livekit_server_tpu.models import plane
from livekit_server_tpu.runtime import PlaneRuntime
from livekit_server_tpu.runtime.faultinject import FaultInjector, FaultSpec
from livekit_server_tpu.runtime.ingest import IngestBuffer, PacketIn

DIMS = plane.PlaneDims(rooms=2, tracks=2, pkts=4, subs=4)


async def _first_tick(rt, timeout=60.0):
    """Wait out the first tick (it pays the jit compile)."""
    deadline = asyncio.get_event_loop().time() + timeout
    while rt.stats["ticks"] < 1:
        if asyncio.get_event_loop().time() > deadline:
            raise TimeoutError("first tick never completed")
        await asyncio.sleep(0.02)


async def _serve(rt, n_pkts, gap_s, first_sn):
    """Start the loop, push `n_pkts` one at a time, wait for them all."""
    rt.set_track(0, 0, published=True, is_video=False)
    rt.set_subscription(0, 0, 1, subscribed=True)
    ticks, batches = [], []
    rt.on_tick(lambda res: (ticks.append(res.tick_index),
                            batches.append(res.egress_batch)))
    rt.start()
    try:
        await _first_tick(rt)
        for i in range(n_pkts):
            rt.ingest.push(PacketIn(room=0, track=0, sn=first_sn + i,
                                    ts=960 * i, size=40, payload=b"d" * 40))
            await asyncio.sleep(gap_s)
        deadline = asyncio.get_event_loop().time() + 5.0
        while sum(len(b) for b in batches) < n_pkts:
            if asyncio.get_event_loop().time() > deadline:
                raise TimeoutError(f"only {sum(len(b) for b in batches)} sends")
            await asyncio.sleep(0.01)
    finally:
        await rt.stop()
    return ticks, batches


# -- step_once vs the serving loop ------------------------------------------

async def test_step_once_raises_while_loop_running():
    """step_once interleaved with the pipelined loop would fan out ahead
    of the loop's deferred fan-out of an earlier tick (munger lanes
    rewritten backwards) — it must refuse, hard, while the loop runs,
    and work again once the loop has stopped."""
    rt = PlaneRuntime(DIMS, tick_ms=10)
    rt.start()
    try:
        await _first_tick(rt)
        with pytest.raises(RuntimeError, match="serving loop"):
            await rt.step_once()
    finally:
        await rt.stop()
    res = await rt.step_once()  # sequential stepping is fine again
    assert res.tick_index >= 1


# -- ordering under overlap --------------------------------------------------

async def test_pipelined_egress_stays_in_tick_order():
    """With fan-out N-1 overlapping device N, completions must still be
    delivered strictly in tick order and every SN exactly once: the
    pipeline reorders WORK, never egress."""
    rt = PlaneRuntime(DIMS, tick_ms=10)
    rt.choose_depth = lambda *a: (1, 0)  # pinned: pipelined, a tick deep
    ticks, batches = await _serve(rt, 8, 0.015, 700)
    assert ticks == sorted(ticks) and len(set(ticks)) == len(ticks)
    sns = [int(sn) & 0xFFFF for b in batches for sn in np.asarray(b.sn)]
    # In arrival order across callbacks: monotonic, no dupes, no holes.
    assert sns == [700 + i for i in range(len(sns))]
    assert len(sns) >= 8
    # Munger lane advanced once per delivered packet (a double fan-out
    # would overshoot).
    assert int(rt.munger.last_sn[0, 0, 1]) == sns[-1]


async def test_device_stall_degrades_to_sequential_bounded_depth():
    """A stalling device (faultinject stall_every) must hold the pipeline
    at depth ≤ 1 — the loop degrades to sequential (pipeline_stalls
    counts the backpressure) rather than queueing stale sends. Every
    delivered SN still appears exactly once, in order."""
    rt = PlaneRuntime(DIMS, tick_ms=10)
    rt.choose_depth = lambda *a: (1, 0)  # pinned: the depth under test
    rt.fault = FaultInjector(FaultSpec(stall_every=2, stall_s=0.05))
    rt.set_track(0, 0, published=True, is_video=False)
    rt.set_subscription(0, 0, 1, subscribed=True)
    batches = []
    rt.on_tick(lambda res: batches.append(res.egress_batch))
    rt.start()
    try:
        await _first_tick(rt)
        for i in range(6):
            rt.ingest.push(PacketIn(room=0, track=0, sn=900 + i, ts=960 * i,
                                    size=40, payload=b"q" * 40))
            await asyncio.sleep(0.03)
            # Staged-but-not-dispatched never runs ahead: at most one tick
            # is in flight on the device plus one staged behind it.
            assert rt.tick_index - rt.stats["ticks"] <= 2
        deadline = asyncio.get_event_loop().time() + 5.0
        while sum(len(b) for b in batches) < 6:
            if asyncio.get_event_loop().time() > deadline:
                raise TimeoutError(
                    f"only {sum(len(b) for b in batches)} sends arrived"
                )
            await asyncio.sleep(0.01)
    finally:
        await rt.stop()
    assert rt.fault.stats.stalls >= 2
    sns = [int(sn) & 0xFFFF for b in batches for sn in np.asarray(b.sn)]
    assert sns == [900 + i for i in range(len(sns))]
    assert all(rec["depth"] <= 1 for rec in rt.recent_ticks)


# -- the depth the loop chooses ----------------------------------------------

P = 0.020   # a 20 ms period, the cells' own

# (chain_s, lag_s, slack_s, depth now, stay, retry) -> (depth, retry)
CHOICES = [
    # depth 0 holds while chain + lag fits the period, to a twentieth
    ((0.012, 0.000, 0.007, 0, 5, 0), (0, 0)),
    ((0.012, 0.0065, 0.0, 0, 5, 0), (0, 0)),
    ((0.012, -0.003, 0.007, 0, 5, 0), (0, 0)),   # early at the edge: no credit, no lag
    ((0.0185, 0.000, 0.001, 0, 5, 64), (0, 64)),  # a try that holds keeps its wait
    ((0.000, 0.000, 0.0, 0, 0, 0), (0, 0)),       # cold start: nothing measured yet
    # behind after a hold (the chain fits, the lag does not): pipeline, and
    # come back as soon as the lag is gone, slept or not
    ((0.012, 0.009, 0.0, 0, 90, 0), (1, 0)),
    ((0.012, 0.050, 0.057, 0, 90, 0), (1, 0)),
    ((0.012, 0.050, 0.0, 0, 3, 256), (1, 0)),     # a hold forgives earlier failures
    ((0.012, 0.030, 0.0, 1, 1, 0), (1, 0)),
    ((0.012, 0.0031, 0.0, 1, 3, 0), (1, 0)),
    ((0.012, 0.0029, 0.0, 1, 4, 0), (0, 0)),
    ((0.019, 0.001, 0.0, 1, 0, 0), (0, 0)),       # pipelined, the chain is not read
    # behind because the chain itself did not fit: a try that failed, each
    # one waits twice as long as the last, up to a limit
    ((0.0195, 0.000, 0.0, 0, 3, 0), (1, 32)),
    ((0.018, 0.003, 0.0, 0, 3, 32), (1, 64)),
    ((0.018, 0.003, 0.0, 0, 3, 512), (1, 1024)),
    ((0.018, 0.003, 0.0, 0, 3, 1024), (1, 1024)),
    ((0.018, 0.003, 0.0, 0, 40, 1024), (1, 0)),   # it held 40 ticks: not a failed try
    # the wait is served pipelined; a try starts only caught up, and from a
    # tick that slept a quarter of the period
    ((0.018, 0.000, 0.008, 1, 10, 32), (1, 32)),
    ((0.018, 0.000, 0.008, 1, 31, 32), (1, 32)),
    ((0.018, 0.000, 0.008, 1, 32, 32), (0, 32)),
    ((0.018, 0.004, 0.008, 1, 500, 32), (1, 32)),
    ((0.018, 0.000, 0.0049, 1, 500, 32), (1, 32)),
    ((0.018, 0.000, 0.0051, 1, 500, 32), (0, 32)),
]


@pytest.mark.parametrize("given, want", CHOICES)
def test_choose_depth_table(given, want):
    chain_s, lag_s, slack_s, depth, stay, retry = given
    assert PlaneRuntime.choose_depth(
        chain_s, lag_s, slack_s, P, depth, stay, retry) == want


@pytest.mark.parametrize("period", [0.005, 0.010, 0.080])
def test_choose_depth_thresholds_scale_with_the_period(period):
    """The thresholds are shares of the period, not milliseconds: the same
    shares of another period give the same walk 0 -> 1 -> 0 after a hold,
    and the same doubling wait for a chain that does not fit."""
    choose = PlaneRuntime.choose_depth
    depth = stay = retry = 0
    walk = []
    for chain, lag in [(0.6, 0.0), (0.6, 0.3), (0.6, 2.5), (0.6, 1.5), (0.6, 0.2),
                       (0.6, 0.1), (0.9, 0.0), (0.9, 0.1), (0.9, 0.0), (0.9, 0.1)]:
        want, retry = choose(chain * period, lag * period, 0.3 * period, period,
                             depth, stay, retry)
        if want != depth:
            depth, stay = want, 0
        stay += 1
        walk.append((depth, retry))
    assert walk == [(0, 0), (0, 0), (1, 0), (1, 0), (1, 0), (0, 0),
                    (0, 0), (1, 32), (1, 32), (1, 32)]


def _flipping_chooser(every: int):
    """1 -> 0 -> 1 -> 0 ...: `every` ticks at a depth, whatever is measured."""
    calls = [0]

    def choose(*measured):
        calls[0] += 1
        return (calls[0] // every + 1) % 2, 0
    return choose


WIDE = plane.PlaneDims(rooms=2, tracks=2, pkts=8, subs=4)


@pytest.mark.parametrize("every", [1, 2, 3])
async def test_depth_changes_keep_the_wire_order(every):
    """The chooser forced 1 -> 0 -> 1 -> 0 mid-stream with packets in
    flight: completions strictly in tick order, every SN exactly once and
    in order, the TS steps kept, and `depth0_ticks` counts the records
    that say depth 0."""
    rt = PlaneRuntime(WIDE, tick_ms=10)
    rt.choose_depth = _flipping_chooser(every)
    ticks, batches = await _serve(rt, 40, 0.008, 3000)
    assert ticks == sorted(ticks) and len(set(ticks)) == len(ticks)
    sns = [int(sn) & 0xFFFF for b in batches for sn in np.asarray(b.sn)]
    assert sns == [3000 + i for i in range(40)]
    ts = [int(t) for b in batches for t in np.asarray(b.ts)]
    assert {(b - a) & 0xFFFFFFFF for a, b in zip(ts, ts[1:])} == {960}
    assert int(rt.munger.last_sn[0, 0, 1]) == sns[-1]
    recs = list(rt.recent_ticks)
    assert len(recs) == rt.stats["ticks"] < rt.recent_ticks.maxlen
    depths = [r["depth"] for r in recs]
    assert rt.stats["depth0_ticks"] == depths.count(0) > 0
    assert depths.count(1) > 0
    assert sum(a != b for a, b in zip(depths, depths[1:])) >= 4


async def test_measured_depth_is_recorded_and_counted():
    """Nothing pinned: whichever depth the measurement gives on this host,
    the counter and the records agree, tick for tick."""
    rt = PlaneRuntime(WIDE, tick_ms=10)
    ticks, batches = await _serve(rt, 12, 0.01, 5000)
    sns = [int(sn) & 0xFFFF for b in batches for sn in np.asarray(b.sn)]
    assert sns == [5000 + i for i in range(12)]
    recs = list(rt.recent_ticks)
    assert len(recs) == rt.stats["ticks"]
    assert rt.stats["depth0_ticks"] == sum(r["depth"] == 0 for r in recs)
    assert all(r["depth"] in (0, 1) for r in recs)


@pytest.mark.parametrize("chooser", ["depth0", "depth1", "flipping"])
async def test_cancel_inside_complete_never_completes_a_tick_twice(chooser):
    """A stop() that lands while a delivery callback is awaiting, so inside
    `_complete`: the drain must not run that tick's fan-out again, at either
    depth or across a change of depth (double egress would repeat an SN and
    advance the munger lane twice)."""
    rt = PlaneRuntime(WIDE, tick_ms=10)
    rt.choose_depth = {"depth0": lambda *a: (0, 0), "depth1": lambda *a: (1, 0),
                       "flipping": _flipping_chooser(2)}[chooser]
    rt.set_track(0, 0, published=True, is_video=False)
    rt.set_subscription(0, 0, 1, subscribed=True)
    ticks, batches = [], []
    inside = asyncio.Event()

    async def deliver(res):
        ticks.append(res.tick_index)
        batches.append(res.egress_batch)
        if sum(len(b) for b in batches) >= 6:
            inside.set()
        await asyncio.sleep(0.004)      # the cancel lands here

    rt.on_tick(deliver)
    rt.start()
    try:
        await _first_tick(rt)
        for i in range(12):
            rt.ingest.push(PacketIn(room=0, track=0, sn=7000 + i, ts=960 * i,
                                    size=40, payload=b"c" * 40))
            await asyncio.sleep(0.008)
            if inside.is_set():
                break
        await asyncio.wait_for(inside.wait(), 5.0)
    finally:
        await rt.stop()
    assert ticks == sorted(ticks) and len(set(ticks)) == len(ticks)
    sns = [int(sn) & 0xFFFF for b in batches for sn in np.asarray(b.sn)]
    assert len(sns) >= 6 and sns == [7000 + i for i in range(len(sns))]
    assert int(rt.munger.last_sn[0, 0, 1]) == sns[-1]


# -- dirty-row delta control uploads ----------------------------------------

def _churn(rt, rng):
    """One round of subscription/meta churn across a few rooms."""
    for _ in range(4):
        r = int(rng.integers(rt.dims.rooms))
        t = int(rng.integers(rt.dims.tracks))
        s = int(rng.integers(rt.dims.subs))
        rt.set_track(r, t, published=True, is_video=bool(rng.integers(2)))
        rt.set_subscription(r, t, s, subscribed=bool(rng.integers(2)))
        rt.set_layer_caps(r, t, s, max_spatial=int(rng.integers(3)),
                          max_temporal=int(rng.integers(4)))


async def test_ctrl_delta_upload_matches_full_upload():
    """Device meta/ctrl state after churn must be identical whether it
    went up as dirty-row deltas or full `_replace` uploads."""
    dims = plane.PlaneDims(rooms=8, tracks=2, pkts=4, subs=4)
    rt_delta = PlaneRuntime(dims, tick_ms=20)
    rt_full = PlaneRuntime(dims, tick_ms=20)
    rt_delta.ctrl_delta_max_rows = dims.rooms     # always delta
    rt_full.ctrl_delta_max_rows = 0               # any dirty row → full
    await rt_delta.step_once()                    # clear the init full flag
    await rt_full.step_once()
    for round_ in range(5):
        rng_a, rng_b = (np.random.default_rng(round_) for _ in range(2))
        _churn(rt_delta, rng_a)
        _churn(rt_full, rng_b)
        await rt_delta.step_once()
        await rt_full.step_once()
        for a, b in zip(rt_delta.state.meta, rt_full.state.meta):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        for a, b in zip(rt_delta.state.ctrl, rt_full.state.ctrl):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert rt_delta.stats["ctrl_delta_uploads"] >= 5
    assert rt_delta.stats["ctrl_full_uploads"] == 1   # only the init upload
    assert rt_full.stats["ctrl_full_uploads"] >= 6
    assert rt_full.stats["ctrl_delta_uploads"] == 0
    await rt_delta.stop()
    await rt_full.stop()


def test_delta_upload_is_o_dirty_rows_at_northstar_dims():
    """A subscription flip in ONE room ships O(dirty rows) bytes, not the
    O(R·T·S) full mirror. Pure numpy — pack_ctrl_rows at north-star dims
    without compiling (or allocating) anything on the device."""
    R, T, S = 10240, 8, 50
    meta = plane.TrackMeta(
        is_video=np.zeros((R, T), bool),
        published=np.zeros((R, T), bool),
        pub_muted=np.zeros((R, T), bool),
        is_svc=np.zeros((R, T), bool),
    )
    ctrl = plane.SubControl(
        subscribed=np.zeros((R, T, S), bool),
        sub_muted=np.zeros((R, T, S), bool),
        max_spatial=np.full((R, T, S), plane.MAX_LAYERS - 1, np.int32),
        max_temporal=np.full((R, T, S), 3, np.int32),
    )
    ctrl.subscribed[3, 1, 7] = True  # the flip
    rows, meta_rows, ctrl_rows = plane.pack_ctrl_rows(meta, ctrl, {3})
    assert list(rows) == [3]
    assert meta_rows.shape[1:] == (1, T) and ctrl_rows.shape[1:] == (1, T, S)
    full_bytes = sum(a.nbytes for a in meta) + sum(a.nbytes for a in ctrl)
    delta_bytes = meta_rows.nbytes + ctrl_rows.nbytes
    assert delta_bytes * 1000 < full_bytes  # 1 of 10240 rows, not all
    # Row payloads round-trip exactly.
    assert bool(ctrl_rows[0, 0, 1, 7])
    np.testing.assert_array_equal(ctrl_rows[0], ctrl.subscribed[[3]])


async def test_ctrl_upload_bytes_counter_tracks_delta():
    """The stats counter bills delta bytes, and a clean tick uploads
    nothing at all."""
    rt = PlaneRuntime(DIMS, tick_ms=20)
    await rt.step_once()                         # init full upload
    assert rt.stats["ctrl_full_uploads"] == 1
    base = rt.stats["ctrl_upload_bytes"]
    await rt.step_once()                         # clean: no upload
    assert rt.stats["ctrl_upload_bytes"] == base
    assert rt.stats["ctrl_delta_uploads"] == 0
    rt.set_subscription(0, 0, 1, subscribed=True)
    await rt.step_once()
    assert rt.stats["ctrl_delta_uploads"] == 1
    assert rt.stats["ctrl_delta_rows"] == 1
    assert rt.stats["ctrl_upload_bytes"] > base
    await rt.stop()


# -- double-buffered ingest staging sets ------------------------------------

def test_ingest_drain_flips_staging_sets():
    """drain() hands out one staging set and flips pushes to the other:
    consecutive drains alternate between exactly two array sets, and
    zero-copy (reuse_fields) header views stay intact while the next
    tick's pushes land in the sibling set."""
    buf = IngestBuffer(plane.PlaneDims(1, 1, 8, 1), tick_ms=10)
    buf.push(PacketIn(room=0, track=0, sn=100, ts=0, size=10, layer=1))
    set_a = buf.sn
    inp1, _ = buf.drain(reuse_fields=True)
    set_b = buf.sn
    assert set_b is not set_a                    # flipped to the sibling
    buf.push(PacketIn(room=0, track=0, sn=200, ts=0, size=10, layer=2))
    # Tick 1's zero-copy pack-only view is untouched by tick 2's push...
    assert int(inp1.layer[0, 0, 0]) == 1
    # ...and the munge-lifetime headers were copied outright.
    assert inp1.sn is not set_a
    assert int(inp1.sn[0, 0, 0]) == 100
    inp2, _ = buf.drain(reuse_fields=True)
    assert buf.sn is set_a                       # ping-pong: back to A
    assert int(inp2.sn[0, 0, 0]) == 200 and int(inp2.layer[0, 0, 0]) == 2


def test_ingest_retired_set_scrub_is_deferred():
    """The drained set is scrubbed lazily: scrub_retired() (called once
    the pipeline no longer needs the views) or the next flip onto it —
    never while tick N's pre-pack might still be reading it."""
    buf = IngestBuffer(plane.PlaneDims(1, 1, 8, 1), tick_ms=10)
    buf.push(PacketIn(room=0, track=0, sn=100, ts=0, size=10))
    inp1, _ = buf.drain(reuse_fields=True)
    retired = buf._sets[1 - buf._active]
    assert retired.needs_scrub and bool(retired.valid.any())
    buf.scrub_retired()
    assert not retired.needs_scrub
    assert not bool(retired.valid.any())         # masks cleared for reuse
    # Without an explicit scrub, the flip scrubs before rebinding: a
    # drain-drain sequence never resurrects tick N's packets as tick N+2's.
    buf.push(PacketIn(room=0, track=0, sn=101, ts=0, size=10))
    buf.drain(reuse_fields=True)
    inp3, _ = buf.drain(reuse_fields=True)       # no pushes: must be empty
    assert int(np.asarray(inp3.valid).sum()) == 0


def test_ingest_default_drain_copies_pack_fields():
    """reuse_fields=False (mesh path / direct callers): pack-only fields
    are real copies, safe to read after the set recycles."""
    buf = IngestBuffer(plane.PlaneDims(1, 1, 8, 1), tick_ms=10)
    buf.push(PacketIn(room=0, track=0, sn=100, ts=0, size=10, layer=1))
    set_a_layer = buf.layer
    inp, _ = buf.drain()
    assert inp.layer is not set_a_layer
    set_a_layer[:] = 9                            # scribble over the set
    assert int(inp.layer[0, 0, 0]) == 1


def test_payload_slab_survives_set_recycling():
    """PayloadSlab copies payload bytes out of the staging set: RTX
    replays reference slabs up to SLAB_WINDOW ticks old, far past the
    2-set ping-pong."""
    buf = IngestBuffer(plane.PlaneDims(1, 1, 8, 1), tick_ms=10)
    buf.push(PacketIn(room=0, track=0, sn=100, ts=0, size=3, payload=b"abc"))
    _, slab1 = buf.drain(reuse_fields=True)
    for i in range(4):  # recycle both sets twice over
        buf.push(PacketIn(room=0, track=0, sn=101 + i, ts=0, size=3,
                          payload=b"xyz"))
        buf.drain(reuse_fields=True)
        buf.scrub_retired()
    assert slab1.get(0, 0, 0)[0] == b"abc"
