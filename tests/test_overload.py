"""Overload governor (runtime/governor.py) + the shedding actuators.

The acceptance scenario this file pins: under a seeded 4x ingest flood
the governor climbs the ladder one rung per sustained-pressure streak,
audio rides through with 100% continuity while video sheds in ladder
order, the supervisor does NOT restart a governed-but-progressing plane
(the restart-storm regression), admission refusals arrive as explicit
signal responses over the wire, and once the flood clears the governor
walks back to L0 — one dwell per step, no flapping.
"""

import asyncio

import aiohttp
import numpy as np
import pytest

from livekit_server_tpu.config.config import Config, LimitsConfig
from livekit_server_tpu.models import plane
from livekit_server_tpu.runtime import (
    FaultInjector,
    OverloadGovernor,
    PlaneRuntime,
    PlaneSupervisor,
)
from livekit_server_tpu.runtime import governor as gov_mod
from livekit_server_tpu.runtime.faultinject import FaultSpec
from livekit_server_tpu.runtime.ingest import PacketIn
from livekit_server_tpu.utils.backoff import BackoffPolicy

from test_service import SignalClient, running_server, token

DIMS = plane.PlaneDims(rooms=2, tracks=4, pkts=4, subs=4)

# Synthetic tick verdicts for the pure ladder tests (tick_ms=10):
HOT = {"total_ms": 20.0, "late": True}    # work 2.0, deadline missed
CALM = {"total_ms": 1.0, "late": False}   # work 0.1, under exit threshold
MID = {"total_ms": 7.0, "late": False}    # work 0.7: inside the hysteresis band


def make_rt() -> PlaneRuntime:
    return PlaneRuntime(DIMS, tick_ms=10)


# -- ladder state machine ---------------------------------------------------

def test_ladder_escalates_and_recovers_one_step_per_streak():
    rt = make_rt()
    gov = OverloadGovernor(rt, escalate_ticks=3, dwell_ticks=5)
    rt.governor = gov

    # Each rung needs its own full hot streak: 4 rungs x 3 ticks.
    for i in range(12):
        gov.on_tick(HOT)
    assert gov.level == gov_mod.L_REJECT
    ups = [(t["from"], t["to"]) for t in gov.transitions]
    assert ups == [(0, 1), (1, 2), (2, 3), (3, 4)]

    # Capped at L_MAX no matter how long the pressure lasts.
    for _ in range(30):
        gov.on_tick(HOT)
    assert gov.level == gov_mod.L_MAX
    assert gov.escalations == 4

    # Recovery: one dwell per downward step, single-step transitions.
    for _ in range(20):
        gov.on_tick(CALM)
    assert gov.level == gov_mod.L_HEALTHY
    seq = [(t["from"], t["to"]) for t in gov.transitions]
    assert seq[4:] == [(4, 3), (3, 2), (2, 1), (1, 0)]
    assert gov.transition_count == 8


def test_overlapped_stages_are_not_summed_into_pressure():
    """The work sensor reads what a tick asks of its window. Pipelined,
    the device step runs beside staging and fan-out (4.4 ms beside 4.2 ms
    in a 10 ms window: the `serve` defaults idle on the chip's host), so
    the window is half used although the stages sum to 0.86 of it; a
    device step of 9 ms does fill it."""
    rt = make_rt()
    gov = OverloadGovernor(rt, escalate_ticks=3, dwell_ticks=5)
    rt.governor = gov
    half = {"total_ms": 8.6, "work_ms": 4.4, "late": False}
    for _ in range(30):
        gov.on_tick(half)
    assert gov.level == 0 and gov.transition_count == 0
    full = {"total_ms": 13.2, "work_ms": 9.0, "late": False}
    for _ in range(3):
        gov.on_tick(full)
    assert gov.level == 1


async def test_tick_record_work_is_the_longer_of_the_overlapped_halves():
    """`work_ms` in a served tick's record: max(device, stage + fan-out)
    when the loop pipelines (depth 1), the plain sum when it does not
    (depth 0). The loop chooses its depth from what it measures, so the
    chooser is pinned on the instance for each reading."""
    for depth in (1, 0):
        rt = PlaneRuntime(DIMS, tick_ms=10)
        rt.choose_depth = lambda *a, _d=depth: (_d, 0)
        rt.start()
        try:
            deadline = asyncio.get_event_loop().time() + 20.0
            while (len(rt.recent_ticks) < 4
                   and asyncio.get_event_loop().time() < deadline):
                await asyncio.sleep(0.01)
        finally:
            await rt.stop()
        rec = list(rt.recent_ticks)[-1]
        parts = (rec["device_ms"], rec["stage_ms"] + rec["fanout_ms"])
        want = max(parts) if depth else sum(parts)
        assert rec["depth"] == depth
        assert abs(rec["work_ms"] - want) < 0.01, rec


def test_oscillating_load_does_not_flap():
    rt = make_rt()
    gov = OverloadGovernor(rt, escalate_ticks=5, dwell_ticks=5)
    rt.governor = gov

    # 2 hot / 2 calm forever: neither streak ever reaches its threshold.
    for _ in range(20):
        for rec in (HOT, HOT, CALM, CALM):
            gov.on_tick(rec)
    assert gov.level == 0 and gov.transition_count == 0

    # The middle band resets BOTH streaks: 4 hot ticks then one
    # neither-hot-nor-calm tick, repeated — never escalates.
    for _ in range(10):
        for rec in (HOT, HOT, HOT, HOT, MID):
            gov.on_tick(rec)
    assert gov.level == 0 and gov.transition_count == 0

    # From an elevated level the same oscillation HOLDS the level
    # (monotonic under churn) instead of bouncing around it.
    gov._set_level(2, "test setup")
    for _ in range(20):
        for rec in (HOT, HOT, CALM, CALM):
            gov.on_tick(rec)
    assert gov.level == 2 and gov.transition_count == 1


def test_from_config_maps_limit_keys():
    rt = make_rt()
    lim = LimitsConfig(
        governor_enter_pressure=0.9, governor_exit_pressure=0.4,
        governor_escalate_ticks=7, governor_dwell_ticks=9,
        governor_ingress_pps=123.0, governor_ingress_burst=45.0,
    )
    gov = OverloadGovernor.from_config(rt, lim)
    assert gov.enter_pressure == 0.9 and gov.exit_pressure == 0.4
    assert gov.escalate_ticks == 7 and gov.dwell_ticks == 9
    assert gov.ingress_pps == 123.0 and gov.ingress_burst == 45.0


# -- actuators follow the ladder --------------------------------------------

def test_actuators_follow_ladder_levels():
    rt = make_rt()
    gov = OverloadGovernor(rt, ingress_pps=50.0, ingress_burst=10.0)
    rt.governor = gov
    rt.set_track(0, 0, published=True, is_video=True)
    rt.set_track(0, 1, published=True, is_video=False)
    rt.set_subscription(0, 0, 1, subscribed=True)
    rt.set_subscription(0, 1, 1, subscribed=True)
    rt.set_layer_caps(0, 0, 1, max_spatial=2)

    # L1: top layer shed, desired caps untouched.
    gov._set_level(1, "test")
    assert rt.shed_spatial_cap == plane.MAX_LAYERS - 2
    eff = rt._effective_ctrl()
    assert int(eff.max_spatial[0, 0, 1]) == plane.MAX_LAYERS - 2
    assert int(rt.ctrl.max_spatial[0, 0, 1]) == 2  # authoritative mirror intact
    assert rt.ingest._police_rate == 0.0

    # L2: base layer only + token-bucket policer armed on video.
    gov._set_level(2, "test")
    assert rt.shed_spatial_cap == 0
    assert rt.ingest._police_rate == 50.0
    assert rt.ingest._police_video is rt.meta.is_video

    # L3: non-pinned video subs muted; audio and pinned video stay live.
    gov._set_level(3, "test")
    eff = rt._effective_ctrl()
    assert bool(eff.sub_muted[0, 0, 1])          # video: paused
    assert not bool(eff.sub_muted[0, 1, 1])      # audio: untouched
    assert not bool(rt.ctrl.sub_muted[0, 0, 1])  # desired state intact
    rt.set_pinned(0, 0, 1, True)
    assert not bool(rt._effective_ctrl().sub_muted[0, 0, 1])  # pin exempts

    # L4: admission closes; existing sessions keep their gate open below.
    gov._set_level(4, "test")
    assert not gov.should_admit("room")
    assert not gov.should_admit("join")
    assert not gov.should_admit("publish")
    gov.note_rejection("join")
    assert gov.rejected == {"join": 1}

    # Full recovery restores every actuator.
    for lvl in (3, 2, 1, 0):
        gov._set_level(lvl, "test")
    assert rt.shed_spatial_cap == plane.MAX_LAYERS - 1
    assert not rt.shed_pause_video
    assert rt.ingest._police_rate == 0.0
    assert rt._effective_ctrl() is rt.ctrl  # overlay fully out of the way
    assert gov.should_admit("join")


# -- the acceptance scenario: 4x seeded flood -------------------------------

async def test_flood_sheds_video_keeps_audio_and_recovers():
    """Seeded 4x flood on one room: capacity drops drive the governor up
    the ladder in order; video sheds (pause at L3) while audio continuity
    stays 100%; p99 tick time stays bounded; after the flood clears the
    governor dwells back down to L0 and every actuator resets."""
    rt = make_rt()
    inj = FaultInjector(FaultSpec(seed=7, flood_mult=4.0))
    rt.fault = inj
    rt.ingest.fault = inj
    # Pressure thresholds pushed out of reach so only the deterministic
    # sensors (capacity-drop deltas) classify ticks — CPU speed of the
    # test host cannot flake the ladder. Policer rates set transparent so
    # the climb is driven end-to-end to L4.
    gov = OverloadGovernor(
        rt, enter_pressure=1e9, exit_pressure=1e8,
        escalate_ticks=3, dwell_ticks=10,
        ingress_pps=1e6, ingress_burst=1e6,
    )
    rt.governor = gov
    rt.set_track(0, 0, published=True, is_video=False)   # audio
    rt.set_track(0, 1, published=True, is_video=True)    # video
    rt.set_subscription(0, 0, 1, subscribed=True)
    rt.set_subscription(0, 1, 1, subscribed=True)

    audio_sns: list[int] = []
    video_per_tick: list[int] = []
    level_per_tick: list[int] = []
    sn_v = 5000

    async def one_tick(tick: int, video_pkts: int):
        nonlocal sn_v
        # One audio packet per tick: flood copies are same-SN duplicates,
        # so audio fills its K=4 slab exactly — zero audio capacity drops.
        rt.ingest.push(PacketIn(room=0, track=0, sn=100 + tick, ts=tick * 90,
                                size=20, payload=b"a"))
        # Offered video at 4x capacity: flood turns each push into 4.
        for _ in range(video_pkts):
            rt.ingest.push(PacketIn(
                room=0, track=1, sn=sn_v, ts=tick * 90, size=120,
                payload=b"v", keyframe=True, layer_sync=True,
                begin_pic=True, marker=True,
            ))
            sn_v += 1
        res = await rt.step_once()
        audio_sns.extend(p.sn for p in res.egress if p.track == 0)
        video_per_tick.append(sum(1 for p in res.egress if p.track == 1))
        level_per_tick.append(gov.level)

    flood_ticks = 40
    for tick in range(flood_ticks):
        await one_tick(tick, video_pkts=4)
        if tick == 19:
            # The ladder is at L4 by ~tick 12: every actuator (policer,
            # shed caps, pause) has fired and compiled its paths. The
            # rest of the flood and the whole recovery must then hold
            # the jit cache — shedding is a data change, not a shape
            # change (recompile watchdog, GC11 runtime half).
            rt.mark_warm()

    # Ladder climbed in order, one rung per 3-tick streak, to L4.
    ups = [(t["from"], t["to"]) for t in gov.transitions]
    assert ups == [(0, 1), (1, 2), (2, 3), (3, 4)]
    assert gov.level == gov_mod.L_REJECT
    assert not gov.should_admit("join")
    assert rt.ingest.dropped_capacity > 0
    # Drop split: this is genuine overflow, not policing or chaos faults.
    assert rt.ingest.dropped_fault == 0

    # Video flowed before the pause rung, then shed to zero.
    pause_at = level_per_tick.index(gov_mod.L_PAUSE)
    assert sum(video_per_tick[:pause_at]) > 0
    assert sum(video_per_tick[pause_at + 2:]) == 0

    # p99 tick time bounded (loose wall-clock bound: the plane kept
    # ticking, it did not degrade into multi-second stalls).
    totals = sorted(t["total_ms"] for t in rt.recent_ticks)
    p99 = totals[int(0.99 * (len(totals) - 1))]
    assert p99 < 20 * rt.tick_ms, f"p99 tick {p99}ms"

    # Flood clears; audio-only load from here.
    inj.spec.flood_mult = 1.0
    recovery_ticks = 55
    for tick in range(flood_ticks, flood_ticks + recovery_ticks):
        await one_tick(tick, video_pkts=0)

    # One dwell (10 calm ticks) per downward rung: L0 within 4 dwells.
    assert gov.level == gov_mod.L_HEALTHY
    downs = [(t["from"], t["to"]) for t in gov.transitions][4:]
    assert downs == [(4, 3), (3, 2), (2, 1), (1, 0)]
    assert rt.shed_spatial_cap == plane.MAX_LAYERS - 1
    assert not rt.shed_pause_video
    assert rt.ingest._police_rate == 0.0

    # Audio continuity 100%: every offered audio packet egressed exactly
    # once (flood duplicates deduped), munged SNs contiguous.
    uniq = sorted(set(audio_sns))
    assert len(uniq) == flood_ticks + recovery_ticks
    assert len(audio_sns) == len(uniq)
    assert all(b - a == 1 for a, b in zip(uniq, uniq[1:]))

    # Governor actuation up AND down the ladder never retraced the tick.
    assert rt.compile_ledger.post_warmup == 0


# -- supervisor interaction: governed lateness is not a stall ---------------

async def test_supervisor_spares_governed_plane_restarts_wedged_one():
    """Restart-storm regression: a governed plane ticking 2x over its
    stall deadline must NOT be restarted (the governor owns slowness);
    a genuinely wedged plane still is, through the widened deadline."""
    rt = make_rt()
    rt.set_track(0, 0, published=True, is_video=False)
    rt.set_subscription(0, 0, 1, subscribed=True)
    # Streak thresholds out of reach: the level stays where the test
    # puts it regardless of what the slow ticks look like.
    gov = OverloadGovernor(rt, escalate_ticks=10**6, dwell_ticks=10**6)
    rt.governor = gov
    gov._set_level(1, "governed for test")

    inj = FaultInjector(FaultSpec(stall_every=1, stall_s=0.12))
    rt.fault = inj
    sup = PlaneSupervisor(
        rt, tick_deadline_s=0.05, warmup_deadline_s=10.0,
        check_interval_s=0.02, checkpoint_interval_s=60.0,
        max_restarts=5, overload_grace=10.0,
        backoff=BackoffPolicy(base=0.02, max_delay=0.1),
    )
    await sup.checkpoint_now()
    rt.start()
    sup.start()
    try:
        async def until(cond, timeout=30.0):
            deadline = asyncio.get_running_loop().time() + timeout
            while not cond():
                assert asyncio.get_running_loop().time() < deadline, \
                    "timed out waiting for supervisor"
                await asyncio.sleep(0.01)

        # Every tick takes ~0.12s against a 0.05s deadline: ungoverned,
        # the watchdog would restart; governed, the widened deadline
        # (0.5s) reads it as slow-but-progressing.
        base = rt.stats["ticks"]
        await until(lambda: rt.stats["ticks"] >= base + 6)
        assert sup.restarts == 0
        assert not sup.gave_up

        # Genuine wedge: stalls longer than even the widened deadline.
        inj.spec.stall_s = 1.5
        await until(lambda: sup.restarts >= 1)
        rt.fault = None  # the hang clears; restarted plane runs clean
        base = rt.stats["ticks"]
        await until(lambda: rt.stats["ticks"] >= base + 5)
        assert not sup.gave_up
    finally:
        await sup.stop()
        await rt.stop()


# -- admission control over the wire ----------------------------------------

async def test_max_rooms_rejection_and_debug_endpoint():
    async with running_server(
        configure=lambda cfg: setattr(cfg.limits, "max_rooms", 1)
    ) as server:
        async with aiohttp.ClientSession() as s:
            alice = SignalClient(s, server.port)
            await alice.connect("one", "alice")

            # Second room trips max_rooms: explicit leave, not a hang.
            bob = SignalClient(s, server.port)
            bob.ws = await s.ws_connect(
                f"ws://127.0.0.1:{server.port}/rtc"
                f"?access_token={token('bob', 'two')}"
            )
            bob._reader = asyncio.ensure_future(bob._read())
            leave = await bob.wait_for("leave")
            assert leave["reason"] == 7  # JOIN_FAILURE

            async with s.get(
                f"http://127.0.0.1:{server.port}/debug/overload"
            ) as r:
                assert r.status == 200
                j = await r.json()
            assert j["governor"]["level"] == 0
            assert j["admission_rejected"].get("room") == 1
            assert j["limits"]["max_rooms"] == 1
            assert "dropped_capacity" in j["ingest"]
            # The same refusal, attributed to its canonical cause
            # ("max rooms on node" → no_capacity).
            assert j["admission_denied_reasons"].get("no_capacity") == 1

            # The reason-labelled counter reaches the scrape endpoint
            # once a tick's observe_overload has run.
            deadline = asyncio.get_running_loop().time() + 5.0
            while True:
                async with s.get(
                    f"http://127.0.0.1:{server.port}/metrics"
                ) as r:
                    text = await r.text()
                if 'livekit_admission_denied_total{reason="no_capacity"} 1' \
                        in text:
                    break
                assert asyncio.get_running_loop().time() < deadline, \
                    "denied_total{reason} never reached /metrics"
                await asyncio.sleep(0.02)

            await alice.close()
            await bob.close()


async def test_governor_l4_rejects_joins_and_publishes_over_wire():
    async with running_server() as server:
        async with aiohttp.ClientSession() as s:
            alice = SignalClient(s, server.port)
            await alice.connect("lobby", "alice")

            gov = server.room_manager.governor
            assert gov is not None  # enabled by default
            gov._set_level(4, "test overload")

            # New join: explicit JOIN_FAILURE leave.
            bob = SignalClient(s, server.port)
            bob.ws = await s.ws_connect(
                f"ws://127.0.0.1:{server.port}/rtc"
                f"?access_token={token('bob', 'lobby')}"
            )
            bob._reader = asyncio.ensure_future(bob._read())
            leave = await bob.wait_for("leave")
            assert leave["reason"] == 7

            # Existing participant stays connected but new publishes are
            # refused with an explicit request_response error.
            await alice.send_signal(
                "add_track", {"cid": "mic", "type": 0, "name": "mic"}
            )
            rr = await alice.wait_for("request_response")
            assert rr["error"]["reason"] == "node_overloaded"
            assert rr["error"]["cid"] == "mic"
            assert gov.rejected.get("join", 0) >= 1
            assert gov.rejected.get("publish", 0) >= 1

            # Recovery reopens admission.
            gov._set_level(0, "test recovered")
            carol = SignalClient(s, server.port)
            join = await carol.connect("lobby", "carol")
            assert join["participant"]["identity"] == "carol"

            await alice.close()
            await bob.close()
            await carol.close()


def test_failover_restore_bypasses_transient_overload_ladder():
    """A 'restore' (failover adoption of a dead node's room) is existing
    load the fleet already admitted — the transient L4 ladder must never
    refuse it, or a busy fleet orphans rooms permanently exactly when a
    flash crowd makes every survivor late. Hard gates still apply:
    drain_hold stops restores (this node is leaving)."""
    rt = make_rt()
    gov = OverloadGovernor(rt, escalate_ticks=3, dwell_ticks=5)
    rt.governor = gov
    for _ in range(12):
        gov.on_tick(HOT)
    assert gov.level == gov_mod.L_REJECT
    assert not gov.should_admit("room")
    assert not gov.should_admit("join")
    assert gov.should_admit("restore")
    gov.hold_max()
    assert not gov.should_admit("restore")
    gov.release_hold()
    assert gov.should_admit("restore")


async def test_room_manager_restores_room_at_l4():
    """End-to-end through get_or_create_room: at L4 a client-driven
    create is refused with an explicit reason, while the failover
    orchestrator's admission_kind='restore' create proceeds."""
    from livekit_server_tpu.runtime import CapacityError

    async with running_server() as server:
        rm = server.room_manager
        gov = rm.governor
        assert gov is not None
        gov._set_level(4, "test overload")
        with pytest.raises(CapacityError, match="node overloaded"):
            await rm.get_or_create_room("orphan")
        room = await rm.get_or_create_room("orphan", admission_kind="restore")
        assert room is rm.rooms["orphan"]
        assert rm.admission_denied_reasons.get("overload", 0) == 1


# -- ingest drop split + policer --------------------------------------------

def test_ingest_drop_split_and_rx_symmetry():
    rt = make_rt()
    buf = rt.ingest
    rt.set_track(0, 0, published=True, is_video=False)

    # Capacity overflow: K=4 slots, 6 arrivals.
    for i in range(6):
        buf.push(PacketIn(room=0, track=0, sn=i, ts=0, size=10, payload=b"x"))
    assert buf.dropped_capacity == 2
    assert buf.dropped_fault == 0 and buf.dropped_policed == 0
    assert buf.dropped == 2  # aggregate property sums the split
    assert int(buf.rx_pkts[0, 0]) == 6  # drops still arrived on the wire

    # Fault drops count rx too (the old asymmetry: fault path returned
    # before accounting, skewing rx rates against capacity drops).
    buf.fault = FaultInjector(FaultSpec(seed=0, drop_pct=1.0))
    assert buf.push(
        PacketIn(room=0, track=0, sn=50, ts=0, size=10, payload=b"x")
    ) is False
    assert buf.dropped_fault == 1
    assert int(buf.rx_pkts[0, 0]) == 7
    assert buf.dropped == 3


def test_policer_scalar_video_only_with_refill():
    rt = make_rt()
    buf = rt.ingest
    rt.set_track(0, 0, published=True, is_video=False)
    rt.set_track(0, 1, published=True, is_video=True)
    # 200 pps at tick_ms=10 → 2 tokens refilled per drain; burst 2.
    buf.set_policer(200.0, 2.0, is_video=rt.meta.is_video)

    got = [
        buf.push(PacketIn(room=0, track=1, sn=i, ts=0, size=10, payload=b"v"))
        for i in range(4)
    ]
    assert got == [True, True, False, False]
    assert buf.dropped_policed == 2

    # Audio bypasses the bucket entirely.
    for i in range(4):
        assert buf.push(
            PacketIn(room=0, track=0, sn=10 + i, ts=0, size=10, payload=b"a")
        )
    assert buf.dropped_policed == 2 and buf.dropped_capacity == 0

    # drain() refills: 2 fresh tokens admit 2 more video packets.
    buf.drain()
    assert buf.push(PacketIn(room=0, track=1, sn=20, ts=0, size=10, payload=b"v"))
    assert buf.push(PacketIn(room=0, track=1, sn=21, ts=0, size=10, payload=b"v"))
    assert not buf.push(
        PacketIn(room=0, track=1, sn=22, ts=0, size=10, payload=b"v")
    )
    assert buf.dropped_policed == 3

    # Disarm: everything admitted again (up to slab capacity).
    buf.clear_policer()
    buf.drain()
    for i in range(4):
        assert buf.push(
            PacketIn(room=0, track=1, sn=30 + i, ts=0, size=10, payload=b"v")
        )
    assert buf.dropped_policed == 3


def test_policer_batch_matches_scalar_semantics():
    rt = make_rt()
    buf = rt.ingest
    rt.set_track(0, 0, published=True, is_video=False)
    rt.set_track(0, 1, published=True, is_video=True)
    buf.set_policer(100.0, 3.0, is_video=rt.meta.is_video)

    # 6 video + 2 audio interleaved: quota floor(3.0)=3 admits the first
    # three video arrivals, polices the rest; audio is exempt.
    track = np.array([1, 1, 1, 0, 1, 1, 0, 1], np.int64)
    n = len(track)
    zeros = np.zeros(n, np.int64)
    fal = np.zeros(n, bool)
    staged = buf.push_batch(
        np.zeros(n, np.int64),            # room
        track,
        zeros,                            # layer
        np.arange(n, dtype=np.int64),     # sn
        zeros,                            # ts
        fal,                              # ts_aligned
        zeros,                            # temporal
        fal,                              # keyframe
        fal,                              # layer_sync
        fal,                              # begin_pic
        fal,                              # marker
        zeros,                            # pid
        zeros,                            # tl0
        zeros,                            # keyidx
        np.full(n, 10, np.int64),         # size
        np.full(n, 20, np.int64),         # frame_ms
        np.full(n, 127, np.int64),        # audio_level
        zeros,                            # arrival_rtp
        np.arange(n, dtype=np.int64),     # pay_start
        np.ones(n, np.int64),             # pay_length
        b"x" * n,                         # blob
    )
    assert staged == 5  # 3 video within quota + 2 exempt audio
    assert buf.dropped_policed == 3
    assert buf.dropped_capacity == 0
    assert int(buf.rx_pkts[0, 1]) == 6  # policed arrivals still counted rx


# -- flood fault mode --------------------------------------------------------

def test_flood_copies_seeded_and_room_filtered():
    # Fractional multiplier: the extra-copy draw is seeded.
    a = FaultInjector(FaultSpec(seed=3, flood_mult=2.5))
    b = FaultInjector(FaultSpec(seed=3, flood_mult=2.5))
    sa = [a.flood_copies(0) for _ in range(40)]
    assert sa == [b.flood_copies(0) for _ in range(40)]
    assert set(sa) == {1, 2}  # 2.5x → 1 or 2 extra copies
    assert a.stats.flooded == sum(sa)
    c = FaultInjector(FaultSpec(seed=4, flood_mult=2.5))
    assert [c.flood_copies(0) for _ in range(40)] != sa

    # Integer multiplier draws nothing: the drop/dup/delay verdict
    # sequence is alignment-identical to a non-flood run, same seed.
    plain = FaultInjector(FaultSpec(seed=9, drop_pct=0.2))
    ref = [plain.on_packet(None, i) for i in range(100)]
    flooded = FaultInjector(FaultSpec(seed=9, drop_pct=0.2, flood_mult=4.0))
    got = []
    for i in range(100):
        got.append(flooded.on_packet(None, i))
        flooded.flood_copies(0)
    assert got == ref

    # Room filter: only listed rooms flood.
    f = FaultInjector(FaultSpec(seed=0, flood_mult=4.0, flood_rooms=(1,)))
    assert f.flood_copies(0) == 0
    assert f.flood_copies(1) == 3


def test_flood_copies_staged_and_rx_counted():
    rt = make_rt()
    buf = rt.ingest
    buf.fault = FaultInjector(FaultSpec(seed=0, flood_mult=4.0))
    rt.set_track(0, 0, published=True, is_video=False)
    assert buf.push(PacketIn(room=0, track=0, sn=1, ts=0, size=10, payload=b"x"))
    # Original + 3 copies staged, all counted as wire arrivals.
    assert int(buf._count[0, 0]) == 4
    assert int(buf.rx_pkts[0, 0]) == 4
    assert buf.fault.stats.flooded == 3


# -- queue-overflow visibility ----------------------------------------------

async def test_queue_overflow_counters_and_gauges():
    from livekit_server_tpu.routing.kv import MemoryBus, Subscription
    from livekit_server_tpu.routing.messagechannel import (
        ChannelFull,
        MessageChannel,
    )
    from livekit_server_tpu.telemetry.service import TelemetryService

    # Class counters accumulate process-wide: assert deltas.
    mc_base = MessageChannel.total_dropped
    sub_base = Subscription.total_dropped

    ch = MessageChannel(size=1)
    ch.write_message({"n": 1})
    with pytest.raises(ChannelFull):
        ch.write_message({"n": 2})
    assert ch.dropped == 1
    assert MessageChannel.total_dropped == mc_base + 1

    bus = MemoryBus()
    sub = bus.subscribe("chan", size=1)
    await bus.publish("chan", "m1")
    await bus.publish("chan", "m2")  # overflow: silently counted, not lost-silently
    assert sub.dropped == 1
    assert Subscription.total_dropped == sub_base + 1

    telem = TelemetryService(Config())
    telem.observe_queue_drops()
    assert (
        telem.gauges["livekit_signal_channel_dropped_total"]
        == MessageChannel.total_dropped
    )
    assert (
        telem.gauges["livekit_bus_sub_dropped_total"]
        == Subscription.total_dropped
    )


def test_governor_telemetry_gauges():
    from livekit_server_tpu.telemetry.service import TelemetryService

    rt = make_rt()
    gov = OverloadGovernor(rt)
    rt.governor = gov
    gov._set_level(1, "test")
    gov.note_rejection("join")

    telem = TelemetryService(Config())
    telem.observe_overload(gov.stats_dict())
    assert telem.gauges["livekit_governor_level"] == 1
    assert telem.gauges["livekit_governor_escalations_total"] == 1
    assert telem.gauges['livekit_admission_rejected_total{kind="join"}'] == 1
    assert telem.gauges["livekit_ingest_dropped_capacity_total"] == 0

    # Reason-labelled denial breakdown (roommanager feeds this from
    # admission_denied_reasons via _dispatch_tick).
    telem.observe_overload({**gov.stats_dict(),
                            "denied_reasons": {"overload": 3, "draining": 1}})
    assert telem.gauges[
        'livekit_admission_denied_total{reason="overload"}'] == 3
    assert telem.gauges[
        'livekit_admission_denied_total{reason="draining"}'] == 1

    snap = gov.snapshot()
    assert snap["level"] == 1
    assert snap["transitions"][0]["to"] == 1
    assert snap["thresholds"]["dwell_ticks"] == gov.dwell_ticks


def test_denial_reason_labels_cover_every_refusal_string():
    """Every human-readable refusal `_admission_denied` can produce maps
    to one of the four canonical causes — an unmapped string would fall
    back to `overload` and silently misattribute the denial."""
    import inspect
    import re

    from livekit_server_tpu.service import roommanager
    from livekit_server_tpu.service.roommanager import DENIAL_REASON_LABELS

    assert set(DENIAL_REASON_LABELS.values()) <= {
        "overload", "draining", "no_capacity", "fenced"
    }
    src = inspect.getsource(roommanager.RoomManager._admission_denied)
    produced = set(re.findall(r'reason = "([^"]+)"', src))
    assert produced, "refusal strings moved; update this scrape"
    unmapped = produced - set(DENIAL_REASON_LABELS)
    assert not unmapped, f"refusal strings without a canonical label: {unmapped}"
    stale = set(DENIAL_REASON_LABELS) - produced
    assert not stale, f"labels for refusals that no longer exist: {stale}"
