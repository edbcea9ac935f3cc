"""End-to-end media-plane tick tests.

Behavioral spec: BASELINE.md config 1 (single room, 2 participants, 1 Opus
audio track each — the reference's TestSinglePublisher scenario,
test/singlenode_test.go:140) plus a VP8 simulcast room.
"""

import asyncio

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from livekit_server_tpu.models import plane
from livekit_server_tpu.ops import audio
from livekit_server_tpu.runtime.munge import HostMunger


class DenseOut:
    """Adapter: device decision masks + host munger → the dense grids the
    assertions use (the production split: decide on device, rewrite on
    host — runtime/munge.py)."""

    def __init__(self, out, dims, munger, inp):
        self.raw = out
        self.send, drop, switch = plane.masks_to_dense(
            jax.tree.map(np.asarray, out), dims
        )
        self.out_sn, self.out_ts, self.out_pid, self.out_tl0, self.out_keyidx = (
            munger.apply_dense(
                np.asarray(inp.sn), np.asarray(inp.ts), np.asarray(inp.ts_jump),
                np.asarray(inp.pid), np.asarray(inp.tl0), np.asarray(inp.keyidx),
                np.asarray(inp.begin_pic), np.asarray(inp.valid),
                self.send, drop, switch,
            )
        )
        for f in ("need_keyframe", "speaker_levels", "speaker_tracks",
                  "congested", "target_layers", "fwd_packets", "fwd_bytes"):
            setattr(self, f, getattr(out, f))


def dense_step(step, dims):
    """Stateful step wrapper: carries the host munger across ticks exactly
    like PlaneRuntime does."""
    munger = HostMunger(dims)

    def run(st, inp):
        st, out = step(st, inp)
        return st, DenseOut(out, dims, munger, inp)
    return run


def make_inputs(dims: plane.PlaneDims, **over):
    R, T, K, S = dims
    z = lambda dt: jnp.zeros((R, T, K), dt)
    inp = plane.TickInputs(
        sn=z(jnp.int32), ts=z(jnp.int32), layer=z(jnp.int32), temporal=z(jnp.int32),
        keyframe=z(jnp.bool_), layer_sync=jnp.ones((R, T, K), jnp.bool_),
        begin_pic=jnp.ones((R, T, K), jnp.bool_),
        end_frame=jnp.ones((R, T, K), jnp.bool_),
        pid=z(jnp.int32), tl0=z(jnp.int32), keyidx=z(jnp.int32),
        size=z(jnp.int32), frame_ms=jnp.full((R, T, K), 20, jnp.int32),
        audio_level=jnp.full((R, T, K), 127, jnp.int32),
        arrival_rtp=z(jnp.int32),
        ts_jump=jnp.full((R, T, K), 3000, jnp.int32),
        valid=jnp.zeros((R, T, K), jnp.bool_),
        estimate=jnp.zeros((R, S), jnp.float32),
        estimate_valid=jnp.zeros((R, S), jnp.bool_),
        nacks=jnp.zeros((R, S), jnp.float32),
        pub_rtt_ms=jnp.zeros((R, T), jnp.float32),
        fb_delay_ms=jnp.zeros((R, S), jnp.float32),
        fb_recv_bps=jnp.zeros((R, S), jnp.float32),
        fb_valid=jnp.zeros((R, S), jnp.bool_),
        fb_enabled=jnp.zeros((R, S), jnp.bool_),
        sub_reset=jnp.zeros((R, S), jnp.bool_),
        pad_num=jnp.zeros((R, S), jnp.int32),
        pad_track=jnp.full((R, S), -1, jnp.int32),
        tick_ms=jnp.int32(20),
        roll_quality=jnp.int32(0),
    )
    return inp._replace(**over)


def two_party_audio_state():
    """Room with participants A, B; track 0 published by A (sub: B=slot1),
    track 1 published by B (sub: A=slot0)."""
    dims = plane.PlaneDims(rooms=1, tracks=2, pkts=1, subs=2)
    st = plane.init_state(dims)
    pub = np.zeros((1, 2), bool); pub[0, :] = True
    subd = np.zeros((1, 2, 2), bool)
    subd[0, 0, 1] = True  # track0 → sub B
    subd[0, 1, 0] = True  # track1 → sub A
    st = st._replace(
        meta=st.meta._replace(published=jnp.asarray(pub)),
        ctrl=st.ctrl._replace(subscribed=jnp.asarray(subd)),
    )
    return dims, st


def test_two_party_audio_forwarding():
    dims, st = two_party_audio_state()
    step = dense_step(jax.jit(plane.media_plane_tick), dims)
    sn = 1000
    for i in range(5):
        inp = make_inputs(
            dims,
            sn=jnp.asarray([[[sn + i], [sn + i]]], jnp.int32),
            ts=jnp.asarray([[[960 * i], [960 * i]]], jnp.int32),
            size=jnp.full((1, 2, 1), 120, jnp.int32),
            audio_level=jnp.asarray([[[20], [90]]], jnp.int32),  # A loud, B quiet
            valid=jnp.ones((1, 2, 1), jnp.bool_),
        )
        st, out = step(st, inp)
        send = np.asarray(out.send)[0]  # [T, K, S]
        # Track 0 goes only to sub 1; track 1 only to sub 0.
        assert send[0, 0, 1] and not send[0, 0, 0]
        assert send[1, 0, 0] and not send[1, 0, 1]
        # Audio munging is identity for a continuous stream.
        assert int(out.out_sn[0, 0, 0, 1]) == sn + i
        assert int(out.out_ts[0, 0, 0, 1]) == 960 * i
    assert int(out.fwd_packets[0]) == 2


def test_two_party_active_speaker():
    dims, st = two_party_audio_state()
    step = dense_step(jax.jit(plane.media_plane_tick), dims)
    # 30 ticks × 20 ms = 600 ms > 500 ms window ⇒ speaker ranking updates.
    for i in range(30):
        inp = make_inputs(
            dims,
            sn=jnp.asarray([[[i], [i]]], jnp.int32),
            size=jnp.full((1, 2, 1), 120, jnp.int32),
            audio_level=jnp.asarray([[[20], [90]]], jnp.int32),
            valid=jnp.ones((1, 2, 1), jnp.bool_),
        )
        st, out = step(st, inp)
    levels = np.asarray(out.speaker_levels)[0]
    tracks = np.asarray(out.speaker_tracks)[0]
    assert tracks[0] == 0          # track 0 (loud) is top speaker
    assert levels[0] > 0.05
    assert levels[1] == 0.0        # quiet track below active threshold


def test_unsubscribed_not_forwarded():
    dims, st = two_party_audio_state()
    st = st._replace(ctrl=st.ctrl._replace(subscribed=jnp.zeros((1, 2, 2), jnp.bool_)))
    step = dense_step(jax.jit(plane.media_plane_tick), dims)
    inp = make_inputs(
        dims,
        valid=jnp.ones((1, 2, 1), jnp.bool_),
        size=jnp.full((1, 2, 1), 120, jnp.int32),
    )
    st, out = step(st, inp)
    assert not np.asarray(out.send).any()
    assert int(out.fwd_packets[0]) == 0


def test_pub_mute_stops_forwarding():
    dims, st = two_party_audio_state()
    st = st._replace(meta=st.meta._replace(pub_muted=jnp.asarray([[True, False]])))
    step = dense_step(jax.jit(plane.media_plane_tick), dims)
    inp = make_inputs(
        dims, valid=jnp.ones((1, 2, 1), jnp.bool_), size=jnp.full((1, 2, 1), 100, jnp.int32)
    )
    st, out = step(st, inp)
    send = np.asarray(out.send)[0]
    assert not send[0].any()       # muted track 0
    assert send[1, 0, 0]           # track 1 still flows


def video_room_state():
    """1 video track (simulcast 3-layer), 3 subscribers."""
    dims = plane.PlaneDims(rooms=1, tracks=1, pkts=3, subs=3)
    st = plane.init_state(dims)
    st = st._replace(
        meta=plane.TrackMeta(
            is_video=jnp.ones((1, 1), jnp.bool_),
            published=jnp.ones((1, 1), jnp.bool_),
            pub_muted=jnp.zeros((1, 1), jnp.bool_),
            is_svc=jnp.zeros((1, 1), jnp.bool_),
        ),
        ctrl=st.ctrl._replace(subscribed=jnp.ones((1, 1, 3), jnp.bool_)),
    )
    return dims, st


def test_simulcast_keyframe_lockon_and_munge():
    dims, st = video_room_state()
    # Targets: selector init targets spatial 2; sub caps limit sub0 to layer 0.
    sel = st.sel._replace(
        target_spatial=jnp.asarray([[[0, 2, 2]]], jnp.int32),
        target_temporal=jnp.full((1, 1, 3), 3, jnp.int32),
    )
    # Pin allocator caps so per-tick allocation preserves the intent.
    ctrl = st.ctrl._replace(max_spatial=jnp.asarray([[[0, 2, 2]]], jnp.int32))
    st = st._replace(sel=sel, ctrl=ctrl)
    step = dense_step(jax.jit(plane.media_plane_tick), dims)

    # Tick 1: keyframes on all three layers (one packet per layer).
    inp = make_inputs(
        dims,
        sn=jnp.asarray([[[100, 5000, 9000]]], jnp.int32),
        ts=jnp.asarray([[[10, 20, 30]]], jnp.int32),
        layer=jnp.asarray([[[0, 1, 2]]], jnp.int32),
        keyframe=jnp.ones((1, 1, 3), jnp.bool_),
        pid=jnp.asarray([[[7, 300, 900]]], jnp.int32),
        size=jnp.full((1, 1, 3), 500, jnp.int32),
        valid=jnp.ones((1, 1, 3), jnp.bool_),
    )
    st, out = step(st, inp)
    send = np.asarray(out.send)[0, 0]  # [K, S]
    assert send[0, 0] and not send[1, 0] and not send[2, 0]  # sub0 ← layer0
    assert send[2, 1] and send[2, 2]                          # subs 1,2 ← layer2
    assert not send[0, 1]
    # Identity munge on first packet.
    assert int(out.out_sn[0, 0, 0, 0]) == 100
    assert int(out.out_sn[0, 0, 2, 1]) == 9000

    # Tick 2: delta frames keep flowing on locked layers.
    inp2 = make_inputs(
        dims,
        sn=jnp.asarray([[[101, 5001, 9001]]], jnp.int32),
        ts=jnp.asarray([[[3010, 3020, 3030]]], jnp.int32),
        layer=jnp.asarray([[[0, 1, 2]]], jnp.int32),
        pid=jnp.asarray([[[8, 301, 901]]], jnp.int32),
        size=jnp.full((1, 1, 3), 500, jnp.int32),
        valid=jnp.ones((1, 1, 3), jnp.bool_),
    )
    st, out = step(st, inp2)
    send = np.asarray(out.send)[0, 0]
    assert send[0, 0] and send[2, 1] and send[2, 2]
    assert int(out.out_sn[0, 0, 0, 0]) == 101
    assert not np.asarray(out.need_keyframe).any()


def svc_room_state():
    """1 SVC (VP9-style) video track, 2 subscribers."""
    dims = plane.PlaneDims(rooms=1, tracks=1, pkts=3, subs=2)
    st = plane.init_state(dims)
    st = st._replace(
        meta=plane.TrackMeta(
            is_video=jnp.ones((1, 1), jnp.bool_),
            published=jnp.ones((1, 1), jnp.bool_),
            pub_muted=jnp.zeros((1, 1), jnp.bool_),
            is_svc=jnp.ones((1, 1), jnp.bool_),
        ),
        ctrl=st.ctrl._replace(subscribed=jnp.ones((1, 1, 2), jnp.bool_)),
    )
    return dims, st


def test_svc_onion_forwarding():
    """SVC tracks forward ALL spatial layers <= current (onion), unlike
    simulcast which forwards exactly one (videolayerselector/vp9.go:43)."""
    dims, st = svc_room_state()
    # sub0 capped at spatial 0, sub1 wants the full onion.
    st = st._replace(
        sel=st.sel._replace(target_spatial=jnp.asarray([[[0, 2]]], jnp.int32)),
        ctrl=st.ctrl._replace(max_spatial=jnp.asarray([[[0, 2]]], jnp.int32)),
    )
    step = dense_step(jax.jit(plane.media_plane_tick), dims)

    # Keyframe picture carrying spatial layers 0..2 in one stream.
    inp = make_inputs(
        dims,
        sn=jnp.asarray([[[100, 101, 102]]], jnp.int32),
        ts=jnp.full((1, 1, 3), 90, jnp.int32),
        layer=jnp.asarray([[[0, 1, 2]]], jnp.int32),
        keyframe=jnp.ones((1, 1, 3), jnp.bool_),
        size=jnp.full((1, 1, 3), 500, jnp.int32),
        valid=jnp.ones((1, 1, 3), jnp.bool_),
    )
    st, out = step(st, inp)
    send = np.asarray(out.send)[0, 0]  # [K, S]
    # sub0: only spatial 0; sub1: all three layers of the onion.
    assert send[0, 0] and not send[1, 0] and not send[2, 0]
    assert send[0, 1] and send[1, 1] and send[2, 1]
    # Single SN space: munged SNs stay contiguous for the full-onion sub.
    assert [int(out.out_sn[0, 0, k, 1]) for k in range(3)] == [100, 101, 102]

    # Delta picture: same onion behavior without keyframes.
    inp2 = make_inputs(
        dims,
        sn=jnp.asarray([[[103, 104, 105]]], jnp.int32),
        ts=jnp.full((1, 1, 3), 3090, jnp.int32),
        layer=jnp.asarray([[[0, 1, 2]]], jnp.int32),
        size=jnp.full((1, 1, 3), 500, jnp.int32),
        valid=jnp.ones((1, 1, 3), jnp.bool_),
    )
    st, out = step(st, inp2)
    send = np.asarray(out.send)[0, 0]
    assert send[0, 0] and not send[2, 0]
    assert send[0, 1] and send[1, 1] and send[2, 1]
    # sub0 dropped layers 1-2 compact its SN space: next SN follows 100.
    assert int(out.out_sn[0, 0, 0, 0]) == 101


def test_quality_outputs_and_window_roll():
    """Clean stream scores EXCELLENT; heavy loss scores worse; rolling the
    window resets the accumulators (scorer.go E-model + windows)."""
    dims, st = two_party_audio_state()
    step = dense_step(jax.jit(plane.media_plane_tick), dims)
    # 10 clean ticks.
    for i in range(10):
        inp = make_inputs(
            dims,
            sn=jnp.asarray([[[i], [i]]], jnp.int32),
            size=jnp.full((1, 2, 1), 120, jnp.int32),
            valid=jnp.ones((1, 2, 1), jnp.bool_),
        )
        st, out = step(st, inp)
    assert int(out.raw.track_quality[0, 0]) == 2  # EXCELLENT
    assert float(out.raw.track_mos[0, 0]) > 4.1
    assert float(out.raw.track_loss_pct[0, 0]) == 0.0

    # Roll the window, then deliver 1-in-5 packets (80% loss).
    inp = make_inputs(dims, roll_quality=jnp.int32(1))
    st, out = step(st, inp)
    for i in range(10):
        inp = make_inputs(
            dims,
            sn=jnp.asarray([[[10 + 5 * i], [10 + i]]], jnp.int32),
            size=jnp.full((1, 2, 1), 120, jnp.int32),
            valid=jnp.ones((1, 2, 1), jnp.bool_),
        )
        st, out = step(st, inp)
    assert float(out.raw.track_loss_pct[0, 0]) > 50.0
    assert int(out.raw.track_quality[0, 0]) == 0  # POOR
    assert int(out.raw.track_quality[0, 1]) == 2  # clean track unaffected


def test_rtt_lowers_mos():
    """Measured publisher-path RTT feeds the E-model delay term
    (scorer.go:45-120): the same clean stream scores a lower MOS on a
    high-RTT path than on a low-RTT one."""
    dims, st = two_party_audio_state()
    step = dense_step(jax.jit(plane.media_plane_tick), dims)
    st_hi = st
    for i in range(10):
        base = dict(
            sn=jnp.asarray([[[i], [i]]], jnp.int32),
            size=jnp.full((1, 2, 1), 120, jnp.int32),
            valid=jnp.ones((1, 2, 1), jnp.bool_),
        )
        st, out_lo = step(st, make_inputs(dims, **base))
        st_hi, out_hi = step(
            st_hi,
            make_inputs(
                dims, pub_rtt_ms=jnp.full((1, 2), 400.0, jnp.float32), **base
            ),
        )
    mos_lo = float(out_lo.raw.track_mos[0, 0])
    mos_hi = float(out_hi.raw.track_mos[0, 0])
    assert mos_hi < mos_lo - 0.2, (mos_lo, mos_hi)
    assert mos_lo > 4.1  # clean + zero RTT stays excellent


def test_svc_single_stream_stats_no_false_loss():
    """An SVC track interleaves spatial layers in ONE SN space; stats must
    fold into one stream row, or healthy traffic reads as ~66% loss."""
    dims, st = svc_room_state()
    step = dense_step(jax.jit(plane.media_plane_tick), dims)
    for i in range(10):
        inp = make_inputs(
            dims,
            sn=jnp.asarray([[[100 + 3 * i, 101 + 3 * i, 102 + 3 * i]]], jnp.int32),
            layer=jnp.asarray([[[0, 1, 2]]], jnp.int32),
            keyframe=jnp.full((1, 1, 3), i == 0, jnp.bool_),
            size=jnp.asarray([[[300, 600, 900]]], jnp.int32),
            valid=jnp.ones((1, 1, 3), jnp.bool_),
        )
        st, out = step(st, inp)
    assert float(out.raw.track_loss_pct[0, 0]) == 0.0
    assert int(out.raw.track_quality[0, 0]) == 2  # EXCELLENT
    # Onion cost: the allocator's layer-2 entry covers layers 0+1+2, so the
    # per-subscriber target cost is the full track bitrate, not layer 2's.
    bps = float(out.raw.track_bps[0, 0])
    assert bps > 0


def test_pub_muted_track_not_lost():
    """A muted publisher sends nothing by design — quality must not read
    LOST (connectionstats.go excludes muted tracks)."""
    dims, st = two_party_audio_state()
    st = st._replace(meta=st.meta._replace(pub_muted=jnp.asarray([[True, False]])))
    step = dense_step(jax.jit(plane.media_plane_tick), dims)
    for i in range(5):
        inp = make_inputs(
            dims,
            sn=jnp.asarray([[[0], [i]]], jnp.int32),
            size=jnp.full((1, 2, 1), 120, jnp.int32),
            valid=jnp.asarray([[[False], [True]]], jnp.bool_),
        )
        st, out = step(st, inp)
    assert int(out.raw.track_quality[0, 0]) == 2  # muted ⇒ EXCELLENT, not LOST
    assert int(out.raw.track_quality[0, 1]) == 2


def test_measured_bitrate_matrix():
    """The allocator's bitrate matrix comes from measured per-layer bytes
    (streamtracker), not hardcoded fractions."""
    dims, st = video_room_state()
    step = dense_step(jax.jit(plane.media_plane_tick), dims)
    # ~600ms of traffic at 20ms ticks: layer sizes 300/600/900 bytes.
    for i in range(30):
        inp = make_inputs(
            dims,
            sn=jnp.asarray([[[100 + 3 * i, 5000 + 3 * i, 9000 + 3 * i]]], jnp.int32),
            layer=jnp.asarray([[[0, 1, 2]]], jnp.int32),
            keyframe=jnp.full((1, 1, 3), i == 0, jnp.bool_),
            size=jnp.asarray([[[300, 600, 900]]], jnp.int32),
            valid=jnp.ones((1, 1, 3), jnp.bool_),
        )
        st, out = step(st, inp)
    # All three layers live after the tracker cycles.
    assert np.asarray(out.raw.layer_live)[0, 0].tolist() == [1, 1, 1]
    # Track bitrate reflects the 1800 B/tick → ~720 kbps load.
    bps = float(out.raw.track_bps[0, 0])
    assert 4e5 < bps < 1.1e6, bps


def test_multi_room_vmap_isolation():
    dims = plane.PlaneDims(rooms=2, tracks=1, pkts=1, subs=2)
    st = plane.init_state(dims)
    pub = jnp.asarray([[True], [True]])
    subd = np.zeros((2, 1, 2), bool)
    subd[0, 0, 1] = True   # room0: sub1 subscribed
    # room1: nobody subscribed
    st = st._replace(
        meta=st.meta._replace(published=pub),
        ctrl=st.ctrl._replace(subscribed=jnp.asarray(subd)),
    )
    step = dense_step(jax.jit(plane.media_plane_tick), dims)
    inp = make_inputs(
        dims, valid=jnp.ones((2, 1, 1), jnp.bool_), size=jnp.full((2, 1, 1), 99, jnp.int32)
    )
    st, out = step(st, inp)
    assert int(out.fwd_packets[0]) == 1
    assert int(out.fwd_packets[1]) == 0


def test_sub_reset_clears_per_sub_bwe_state():
    """A released subscriber slot must hand its successor FRESH per-sub
    state: a decayed delay-BWE floor rate (silent previous occupant) would
    otherwise cap the new subscriber's budget for up to a minute."""
    dims, st = two_party_audio_state()
    step = jax.jit(plane.media_plane_tick)
    # Starve sub 0: sealed path enabled, sends outstanding, never acks.
    inp = make_inputs(
        dims,
        valid=jnp.ones((1, 2, 1), jnp.bool_),
        size=jnp.full((1, 2, 1), 120, jnp.int32),
        fb_enabled=jnp.asarray([[True, False]]),
    )
    for _ in range(120):
        st, out = step(st, inp)
    decayed = float(st.delay_bwe.rate_bps[0, 0])
    assert decayed < 2_000_000.0  # well below the 7 Mbps initial
    # Slot released & reused: one tick with sub_reset set.
    st, out = step(st, inp._replace(sub_reset=jnp.asarray([[True, False]])))
    assert float(st.delay_bwe.rate_bps[0, 0]) > 6_000_000.0
    assert not bool(st.delay_bwe.ever_fb[0, 0])


async def test_watchdog_restarts_stalled_plane_from_snapshot():
    """Supervision: a wedged device step (injected stall) trips the tick
    watchdog; the supervisor abandons the stuck worker thread, restores
    the last checkpoint, and the plane resumes ticking within the restart
    budget — with munger state REWOUND to the snapshot (post-checkpoint
    packets would be re-issued as duplicates, never skipped)."""
    import asyncio

    from livekit_server_tpu.runtime import (
        FaultInjector,
        PlaneRuntime,
        PlaneSupervisor,
    )
    from livekit_server_tpu.runtime.faultinject import FaultSpec
    from livekit_server_tpu.runtime.ingest import PacketIn
    from livekit_server_tpu.utils.backoff import BackoffPolicy

    dims = plane.PlaneDims(rooms=2, tracks=4, pkts=4, subs=4)
    rt = PlaneRuntime(dims, tick_ms=10)
    rt.set_track(0, 0, published=True, is_video=False)
    rt.set_subscription(0, 0, 1, subscribed=True)
    for i in range(3):
        rt.ingest.push(PacketIn(room=0, track=0, sn=100 + i, ts=0,
                                size=20, payload=b"x"))
        await rt.step_once()

    sup = PlaneSupervisor(
        rt, tick_deadline_s=0.25, check_interval_s=0.02,
        checkpoint_interval_s=60.0, max_restarts=5,
        backoff=BackoffPolicy(base=0.02, max_delay=0.1),
    )
    await sup.checkpoint_now()
    at_checkpoint = int(rt.munger.last_sn[0, 0, 1])
    assert at_checkpoint == 102

    # Advance PAST the checkpoint so the restore is observable as a
    # rewind, not just "state unchanged".
    for i in range(2):
        rt.ingest.push(PacketIn(room=0, track=0, sn=103 + i, ts=0,
                                size=20, payload=b"x"))
        await rt.step_once()
    assert int(rt.munger.last_sn[0, 0, 1]) > at_checkpoint

    rt.fault = FaultInjector(FaultSpec(stall_every=1, stall_s=0.8))
    rt.start()
    sup.start()
    try:
        async def until(cond, timeout=30.0):
            deadline = asyncio.get_running_loop().time() + timeout
            while not cond():
                assert asyncio.get_running_loop().time() < deadline, \
                    "timed out waiting for supervisor"
                await asyncio.sleep(0.01)

        await until(lambda: sup.restarts >= 1)
        stalls = rt.fault.stats.stalls
        assert stalls >= 1
        rt.fault = None  # the hang "clears"; the restarted plane runs clean
        base = rt.stats["ticks"]
        await until(lambda: rt.stats["ticks"] >= base + 5)
        assert sup.restarts >= 1
        assert not sup.gave_up
        assert int(rt.munger.last_sn[0, 0, 1]) == at_checkpoint
    finally:
        await sup.stop()
        await rt.stop()


@pytest.mark.parametrize("with_callback", [False, True])
async def test_checkpoint_now_is_one_span_with_its_children(with_callback):
    """One call adds one `supervisor/checkpoint` to the span totals, with
    its parts inside it: the snapshot under state_lock, the encode (+
    checksum) and, where there is one, the per-room callback."""
    from livekit_server_tpu.runtime import PlaneRuntime, PlaneSupervisor

    dims = plane.PlaneDims(rooms=2, tracks=4, pkts=4, subs=4)
    rt = PlaneRuntime(dims, tick_ms=10)
    await rt.step_once()
    called = []

    async def callback():
        called.append(1)
        await asyncio.sleep(0.002)

    sup = PlaneSupervisor(rt, checkpoint_interval_s=60.0)
    if with_callback:
        sup.room_checkpoint_cb = callback
    await sup.checkpoint_now()
    spans = rt.spans.snapshot()
    whole = spans["supervisor/checkpoint"]
    parts = [spans[f"supervisor/checkpoint/{part}"]
             for part in ("snapshot", "encode", "callback")]
    assert whole["n"] == 1 and [p["n"] for p in parts] == [1, 1, int(with_callback)]
    assert all(p["busy_s"] > 0.0 for p in parts[:2])
    assert sum(p["busy_s"] for p in parts) <= whole["busy_s"] + 3e-6
    if with_callback:
        assert called == [1] and parts[2]["busy_s"] >= 0.002
    assert sup.last_good_snapshot() is not None
    await sup.checkpoint_now()
    assert rt.spans.snapshot()["supervisor/checkpoint"]["n"] == 2
    await rt.stop()
