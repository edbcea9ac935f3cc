"""The batched SFU media plane — flagship model.

One `media_plane_tick` call advances the entire media plane of a node by one
tick (~5-20 ms): for every room, every published track, every subscriber, it

  1. folds received packets into per-stream RTP stats
     (reference: buffer.Buffer.calc — pkg/sfu/buffer/buffer.go:417)
  2. updates per-layer bitrate estimates
     (reference: StreamTrackerManager Bitrates — streamtrackermanager.go)
  3. runs BWE trend detection + congestion per subscriber
     (reference: StreamAllocator event loop — streamallocator.go:563)
  4. allocates layers across tracks under the committed channel budget
     (reference: allocateAllTracks + Forwarder provisional algebra)
  5. selects simulcast/temporal layers per packet per subscriber
     (reference: videolayerselector — the Select half of WriteRTP)
  6. mixes audio levels into active-speaker rankings per room
     (reference: audio.AudioLevel + Room.audioUpdateWorker)

The whole thing is jit-compiled once; the room axis is vmapped and shards
over the device mesh (livekit_server_tpu.parallel). The host control plane
mutates subscription/mute masks and reads egress outputs between ticks.

Decide on device, rewrite on host (round-5 split)
-------------------------------------------------
The tick's egress product is three BIT-PACKED mask tensors — send / drop /
switch per (track, packet, subscriber), ⌈S/32⌉ words each — NOT per-send
SN/TS values. The SN/TS/VP8 offset rewriting (rtpmunger.go +
codecmunger/vp8.go semantics) runs on the HOST (runtime/munge.py + the
native walker), in the egress path that already touches every outgoing
packet's bytes — exactly where the reference runs it. Device tracing
showed the former device-side compaction (`jnp.nonzero` + six value
gathers) WAS the tick at scale: TPUs have no vector gather, so the
gathers cost ~29 ms of a 38 ms cfg4 tick, and at the 10k-room north-star
shape any multi-pass op over the dense [R,T,K,S] value tensors is
unaffordable. Masks are one elementwise pass and 32× smaller on the wire.

Shape glossary (static per compiled program):
  R rooms · T tracks/room · K packets/track/tick · S subscribers/room
  streams N = T (one SN space per simulcast layer is carried in the packet
  `layer` field; per-layer stats use T*L rows with L = MAX_LAYERS).
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from livekit_server_tpu.analysis.registry import device_entry
from livekit_server_tpu.ops import (
    allocation,
    audio,
    bwe,
    pacer,
    quality,
    red,
    rtpstats,
    scanops,
    selector,
    streamtracker,
)

MAX_LAYERS = 3          # simulcast spatial layers (reference: 3 — receiver.go)
MAX_TEMPORAL = 4        # temporal sublayers tracked per spatial layer
SPEAKER_TOP_K = 3
SLAB_WINDOW = 64        # ticks of payload history the host retains for RTX
                        # (sequencer.go rtt-bounded ring; 64×10 ms = 640 ms —
                        # NACK resolution itself is host-side: see
                        # runtime/plane_runtime.py HostSequencer)
PAD_MAX = 8             # max probe-padding packets per subscriber per tick
                        # (8 × 255 B / 10 ms ≈ 1.6 Mbps of probe headroom)
# Cold-start per-temporal-sublayer bitrate shares, used only until measured
# per-temporal byte attribution (state.temporal_bytes) accumulates — the
# live path derives the [4][4] Bitrates matrix from observed traffic like
# the reference's StreamTrackerManager (streamtrackermanager.go:60-732).
TEMPORAL_FRACTIONS = (0.45, 0.65, 0.85, 1.0)


class PlaneDims(NamedTuple):
    rooms: int = 1
    tracks: int = 4        # per room
    pkts: int = 4          # per track per tick
    subs: int = 4          # per room


class TrackMeta(NamedTuple):
    """Host-written per-track control tensors, [R, T]."""

    is_video: jax.Array     # bool
    published: jax.Array    # bool — track exists and is live
    pub_muted: jax.Array    # bool — publisher muted
    is_svc: jax.Array       # bool — single-stream SVC (VP9/AV1) vs simulcast
                            # (receiver.go IsSvcCodec :142-150)


class SubControl(NamedTuple):
    """Host-written per-(track, subscriber) control tensors, [R, T, S]."""

    subscribed: jax.Array    # bool — SubscriptionManager desired state
    sub_muted: jax.Array     # bool — subscriber-requested mute
    max_spatial: jax.Array   # int32 — adaptive-stream cap
    max_temporal: jax.Array  # int32


class PlaneState(NamedTuple):
    """Full media-plane state, all leading axis [R] (sharded over mesh).

    SN/TS/VP8 munger state lives on the HOST (runtime/munge.py HostMunger)
    since the round-5 decide-on-device/rewrite-on-host split; the device
    carries only decision state.
    """

    meta: TrackMeta
    ctrl: SubControl
    stats: rtpstats.StreamStats          # [R, T*L] per (track, layer) stream
    audio_state: audio.AudioLevelState   # [R, T]
    sel: selector.SelectorState          # [R, T, S]
    bwe_state: bwe.BWEState              # [R, S]
    delay_bwe: bwe.DelayBWEState         # [R, S] — TWCC send-side estimator
    tracker: streamtracker.TrackerState  # [R, T*L] per (track, layer) stream
    pacer_state: pacer.PacerState        # [R, S] — leaky-bucket egress pacing
    red_state: red.REDState              # [R, T, D] — RED history rings
    temporal_bytes: jax.Array            # [R, T, L, MAX_TEMPORAL] float32 —
                                         # per-temporal byte/tick EMA (the
                                         # measured Bitrates attribution)


class TickInputs(NamedTuple):
    """Per-tick ingest tensors (host-packed; static shapes)."""

    # Packet fields, [R, T, K]:
    sn: jax.Array          # int32, 16-bit
    ts: jax.Array          # int32, 32-bit
    layer: jax.Array       # int32 — spatial/simulcast layer (0 for audio)
    temporal: jax.Array    # int32 — temporal id (0 if none)
    keyframe: jax.Array    # bool
    layer_sync: jax.Array  # bool — temporal upswitch point (VP8 Y bit)
    begin_pic: jax.Array   # bool — first packet of a picture / frame
    end_frame: jax.Array   # bool — last packet of the frame (RTP marker;
                           # SVC downswitch boundary — vp9.go)
    pid: jax.Array         # int32 — VP8 picture id (0 for audio)
    tl0: jax.Array         # int32 — VP8 TL0PICIDX
    keyidx: jax.Array      # int32 — VP8 KEYIDX
    size: jax.Array        # int32 — payload bytes
    frame_ms: jax.Array    # int32 — media duration carried by the packet
                           # (Opus ptime; 0 for video — levels are audio-only)
    audio_level: jax.Array # int32 — RFC6464 dBov (127 if none)
    arrival_rtp: jax.Array # int32 — arrival time in RTP units
    ts_jump: jax.Array     # int32 — TS advance at a source switch landing on
                           # this packet; -1 = host SR-normalized the TS onto
                           # the track's common timeline (no re-anchor)
    valid: jax.Array       # bool
    # Per-subscriber feedback, [R, S]:
    estimate: jax.Array        # float32 — TWCC/REMB estimate sample
    estimate_valid: jax.Array  # bool
    nacks: jax.Array           # float32 — NACK count this tick (BWE loss
                               # channel; resolution is host-side — see
                               # runtime HostSequencer)
    # Publisher-path RTT per track, [R, T] float32: measured host-side from
    # RTCP SR/RR (ingest.rtt_ms, RFC 3550 A.8) and gathered through the
    # track→publisher-slot mapping. Feeds the E-model delay term
    # (scorer.go:45-120 includes RTT); 0 where unknown.
    pub_rtt_ms: jax.Array
    # TWCC feedback reductions, [R, S] (see ops/bwe delay estimator):
    fb_delay_ms: jax.Array    # float32 — mean delay-variation this tick
    fb_recv_bps: jax.Array    # float32 — acked receive rate sample
    fb_valid: jax.Array       # bool — feedback arrived this tick
    fb_enabled: jax.Array     # bool — sub is on the sealed UDP path
    sub_reset: jax.Array      # [R, S] bool — slot released this tick:
                              # reset its per-sub device state (BWE/
                              # delay/pacer) before this tick's update
    # BWE probe padding (probe_controller → WritePaddingRTP), [R, S]:
    pad_num: jax.Array         # int32 — padding packets to synthesize (≤ PAD_MAX)
    pad_track: jax.Array       # int32 — track whose downtrack carries them (-1 none)
    # Scalars:
    tick_ms: jax.Array     # int32
    roll_quality: jax.Array  # int32 bool-ish — close the stats window this
                             # tick (host sets it ~1/s; the quality outputs
                             # always score the accumulating window)


class TickOutputs(NamedTuple):
    """Egress + signal tensors pulled by the host after each tick.

    Egress is three BIT-PACKED mask tensors (send / drop / switch), one bit
    per (track, packet, subscriber), W = ⌈S/32⌉ words on the minor axis.
    One elementwise pass to produce, ~32× smaller than dense bools on the
    device→host wire, and no gathers anywhere (see module docstring). The
    host (runtime/munge.py + native walker) expands the bits it forwards
    and applies the SN/TS/VP8 rewrites with host-owned state.
    """

    send_bits: jax.Array      # [R, T, K, W] int32 — forward pkt k to sub s
    drop_bits: jax.Array      # [R, T, K, W] int32 — current-stream drop
                              #   (SN-gap compaction event, rtpmunger.go
                              #   PacketDropped)
    switch_bits: jax.Array    # [R, T, K, W] int32 — source-switch re-anchor
                              #   (forwarder.go processSourceSwitch)
    need_keyframe: jax.Array   # [R, T, S] bool — host sends PLI upstream
    speaker_levels: jax.Array  # [R, SPEAKER_TOP_K] float32
    speaker_tracks: jax.Array  # [R, SPEAKER_TOP_K] int32 — room-local track idx
    congested: jax.Array       # [R, S] bool
    target_layers: jax.Array   # [R, S, T] int32 — flat layer targets
    fwd_packets: jax.Array     # [R] int32 — packets forwarded (telemetry)
    fwd_bytes: jax.Array       # [R] int32
    # Connection quality (ops/quality E-model; room.go:1318 worker feed):
    track_mos: jax.Array       # [R, T] float32 — publisher-side MOS
    track_quality: jax.Array   # [R, T] int32 — ConnectionQuality enum
    sub_quality: jax.Array     # [R, S] int32 — subscriber-side enum
    # Per-(track, layer) stream liveness (streamtracker; dynacast feed):
    layer_live: jax.Array      # [R, T, L] int32 — STOPPED/LIVE
    layer_fps: jax.Array       # [R, T, L] float32 — measured frame rate
                               # (fps.go; frame-tracker variant output)
    # Windowed per-track receive stats (telemetry; rolled by roll_quality):
    track_loss_pct: jax.Array  # [R, T] float32
    track_jitter_ms: jax.Array # [R, T] float32
    track_bps: jax.Array       # [R, T] float32 — summed live-layer bitrate
    # (Probe padding synthesis moved host-side with the munger state —
    # runtime/munge.py HostMunger.padding.)
    # Allocator budget per subscriber (probe goal baseline + telemetry):
    committed_bps: jax.Array   # [R, S] float32
    pacer_allowed: jax.Array   # [R, S] float32 — leaky-bucket byte budget
                               # the host egress may write this tick
    deficient: jax.Array       # [R, S] bool — allocation under-served this
                               # sub (probe trigger; streamallocator
                               # "deficient" state)
    # RED encapsulation plan for audio packets (redreceiver.go): per
    # packet, the D candidate redundancy blocks by source SN, their 14-bit
    # TS offsets, and RFC 2198 fit. Host egress assembles bytes for
    # RED-negotiated subscribers from its payload ring.
    red_sn: jax.Array          # [R, T, K, D] int32
    red_off: jax.Array         # [R, T, K, D] int32
    red_ok: jax.Array          # [R, T, K, D] bool


@device_entry("plane.init_state")
def init_state(dims: PlaneDims) -> PlaneState:
    R, T, K, S = dims
    L = MAX_LAYERS

    def tile(x, *lead):
        return jnp.broadcast_to(x, lead + x.shape).copy()

    meta = TrackMeta(
        is_video=jnp.zeros((R, T), jnp.bool_),
        published=jnp.zeros((R, T), jnp.bool_),
        pub_muted=jnp.zeros((R, T), jnp.bool_),
        is_svc=jnp.zeros((R, T), jnp.bool_),
    )
    ctrl = SubControl(
        subscribed=jnp.zeros((R, T, S), jnp.bool_),
        sub_muted=jnp.zeros((R, T, S), jnp.bool_),
        max_spatial=jnp.full((R, T, S), MAX_LAYERS - 1, jnp.int32),
        max_temporal=jnp.full((R, T, S), 3, jnp.int32),
    )
    return PlaneState(
        meta=meta,
        ctrl=ctrl,
        stats=jax.tree.map(lambda x: tile(x, R), rtpstats.init_state(T * L)),
        audio_state=jax.tree.map(lambda x: tile(x, R), audio.init_state(T)),
        sel=jax.tree.map(lambda x: tile(x, R, T), selector.init_state(S)),
        bwe_state=jax.tree.map(lambda x: tile(x, R), bwe.init_state(S)),
        delay_bwe=jax.tree.map(lambda x: tile(x, R), bwe.delay_init_state(S)),
        tracker=jax.tree.map(lambda x: tile(x, R), streamtracker.init_state(T * L)),
        pacer_state=jax.tree.map(lambda x: tile(x, R), pacer.init_state(S)),
        red_state=jax.tree.map(lambda x: tile(x, R), red.init_state(T)),
        temporal_bytes=jnp.zeros((R, T, L, MAX_TEMPORAL), jnp.float32),
    )


# Bit-mask helpers live in ops/bits.py (shared with the decision kernel's
# CPU fallback); re-exported here for the runtime and tests.
from livekit_server_tpu.ops.bits import (  # noqa: E402
    mask_words,
    pack_bits as _pack_bits,
    unpack_bits,
)


def _room_tick(
    state: PlaneState,
    inp: TickInputs,
    send_bits: jax.Array,    # [T, K, W] — phase-0 decision kernel outputs
    drop_bits: jax.Array,
    switch_bits: jax.Array,
    need_kf: jax.Array,      # [T, S] bool, base-merged
    pkts_sent_i: jax.Array,  # [S] int32
    sent_bytes_i: jax.Array, # [S] int32 (wire overhead included)
    fwd_packets_i: jax.Array,  # [] int32
    fwd_bytes_i: jax.Array,    # [] int32
    audio_params: audio.AudioLevelParams,
    bwe_params: bwe.BWEParams,
    red_enabled: bool = True,
    *,
    routed_stats=None,
):
    """Phase-1 core tick for ONE room; every field has its leading R axis
    stripped. The forward decision (phase 0) and allocation (phase 2) run
    room-batched in `media_plane_tick`; this returns `bitrates` for phase
    2 and placeholder zeros for the allocation-derived output fields.

    `routed_stats`, when given, is `(st [5, T*L, K], tr_sums [3, T*L])` —
    the stats/tracker routing selects precomputed by the live-page fused
    kernel (ops/paged_kernel.py) with the identical int algebra; the
    in-place computation below is then skipped bit-for-bit."""
    T, K = inp.sn.shape
    S = state.ctrl.subscribed.shape[-1]
    L = MAX_LAYERS

    # ---- 1. RTP stats per (track, layer) stream -------------------------
    # Simulcast layers are independent RTP streams (own SN spaces) and get
    # one stats row each; an SVC track carries every spatial layer in ONE
    # stream/SN space, so all its packets fold into row 0 — per-layer rows
    # would misread the interleaved SNs as massive loss.
    lanes = jnp.arange(L, dtype=jnp.int32)[None, None, :]            # [1,1,L]
    if routed_stats is None:
        eff_layer = jnp.where(
            state.meta.is_svc[:, None], 0, jnp.clip(inp.layer, 0, L - 1)
        )
        # Route packets into [T*L, K] rows by (track, layer) — as an
        # elementwise one-hot select, NOT a scatter: k is preserved, so
        # (t, k) → (t, eff_layer, k) can never collide, and
        # data-dependent scatters serialize per element on TPU while
        # this select/transpose fuses (the cfg4-scale tick was dominated
        # by exactly this scatter).
        # One stacked routed select for all five stats fields (sn/ts/
        # size/arrival/valid) — five separate [T,K,L] selects each
        # materialize their own routing compare + transpose; stacked
        # they share it and fuse into one pass (same discipline as the
        # tracker's tr_vals stack below). Every field's "not this lane"
        # fill is 0 (valid rides as int32 0/1), so a single zero fill
        # serves the stack.
        st_vals = jnp.stack(
            [inp.sn, inp.ts, inp.size, inp.arrival_rtp,
             inp.valid.astype(jnp.int32)]
        )                                                            # [5,T,K]
        st_routed = jnp.where(
            (eff_layer[:, :, None] == lanes)[None], st_vals[:, :, :, None], 0
        )                                                            # [5,T,K,L]
        st = st_routed.transpose(0, 1, 3, 2).reshape(5, T * L, K)
        # Tracker rows route by each packet's TRUE spatial layer (see
        # the section-2 comment below); computed here so the fused
        # kernel can hand BOTH routings in via `routed_stats`.
        true_layer = jnp.clip(inp.layer, 0, L - 1)
        t_lane = true_layer[:, :, None] == lanes                    # [T,K,L]
        # One stacked routed-sum for (pkts, bytes, frames) — three
        # separate reduces cost ~0.9 ms/tick at cfg4; stacked they share
        # the routing select and fuse into one pass.
        ones_k = jnp.ones((T, K), jnp.int32)
        tr_vals = jnp.stack([ones_k, inp.size, ones_k])             # [3,T,K]
        tr_pred = jnp.stack(
            [inp.valid, inp.valid, inp.valid & inp.begin_pic]
        )                                                           # [3,T,K]
        routed = jnp.where(
            t_lane[None] & tr_pred[:, :, :, None], tr_vals[:, :, :, None], 0
        )                                                           # [3,T,K,L]
        tr_sums = jnp.sum(routed, axis=2).reshape(3, T * L)
    else:
        st, tr_sums = routed_stats
    stats = rtpstats.update_tick(
        state.stats, st[0], st[1], st[2], st[3], st[4].astype(jnp.bool_)
    )

    # ---- 2. per-layer liveness + measured [4][4] bitrate matrix ---------
    # StreamTracker rows per (track, layer). Unlike the stats rows above,
    # tracker rows route by each packet's TRUE spatial layer — for SVC
    # tracks that's the DD/VP9-refined layer, which IS the reference's
    # DD-driven tracker variant (streamtracker_dd.go): an SVC layer's row
    # goes LIVE/STOPPED as decode targets appear/vanish. Frame starts
    # feed the frame-rate rule + fps estimation (streamtracker_frame.go,
    # fps.go). (The routed sums themselves are computed above, next to
    # the stats routing, so `routed_stats` can replace both at once.)
    st_pkts, st_bytes, st_frames = tr_sums[0], tr_sums[1], tr_sums[2]
    tracker, layer_status, _status_changed, tracker_bps, layer_fps = (
        streamtracker.update_tick(
            state.tracker, streamtracker.TrackerParams(), st_pkts, st_bytes,
            inp.tick_ms, frames=st_frames,
        )
    )
    # Per-(layer, temporal) byte attribution EMA — the measured version of
    # the reference's Bitrates matrix (streamtrackermanager.go:60).
    layer_oh = jax.nn.one_hot(jnp.clip(inp.layer, 0, L - 1), L, dtype=jnp.float32)
    tm_oh = jax.nn.one_hot(
        jnp.clip(inp.temporal, 0, MAX_TEMPORAL - 1), MAX_TEMPORAL, dtype=jnp.float32
    )
    vbytes = jnp.where(inp.valid, inp.size, 0).astype(jnp.float32)
    tick_bytes_lt = jnp.einsum("tk,tkl,tkm->tlm", vbytes, layer_oh, tm_oh)  # [T,L,4]
    temporal_bytes = state.temporal_bytes * 0.9 + tick_bytes_lt * 0.1
    tick_s = jnp.maximum(inp.tick_ms.astype(jnp.float32), 1.0) / 1000.0
    # Layer bitrate: tracker cycles once committed; per-tick EMA bootstraps
    # the first cycle so allocation starts on the first packets. SVC tracks
    # keep the EMA attribution even though tracker rows are now per true
    # spatial layer (the DD-variant liveness feed): their temporal splits
    # come from temporal_bytes either way, and the faster EMA avoids a
    # 500 ms tracker-cycle lag on the onion's cumulative costs.
    boot_bps = jnp.sum(temporal_bytes, axis=-1) * 8.0 / tick_s        # [T, L]
    layer_bps = jnp.where(
        ~state.meta.is_svc[:, None] & (tracker_bps.reshape(T, L) > 0),
        tracker_bps.reshape(T, L),
        boot_bps,
    )
    # Cumulative temporal shares from measured bytes; cold-start fractions
    # until any bytes attribute. (scanops: jnp.cumsum lowers to a
    # reduce-window that measured ~2.7 ms/tick at cfg4 on these tiny axes.)
    tot = jnp.sum(temporal_bytes, axis=-1, keepdims=True)             # [T, L, 1]
    cum = scanops.cumsum_small(temporal_bytes, axis=-1)               # [T, L, 4]
    frac0 = jnp.asarray(TEMPORAL_FRACTIONS, jnp.float32)
    frac = jnp.where(tot > 0, cum / jnp.maximum(tot, 1e-6), frac0[None, None, :])
    bitrates = jnp.zeros((T, 4, 4), jnp.float32)
    bitrates = bitrates.at[:, :L, :].set(layer_bps[:, :, None] * frac)
    # SVC onion: forwarding spatial s sends every layer <= s, so the cost
    # of an SVC entry is the cumulative sum over spatial layers (the
    # reference reports cumulative SVC bitrates) — without this the
    # allocator over-commits the channel by the lower layers' bps.
    bitrates = jnp.where(
        state.meta.is_svc[:, None, None],
        scanops.cumsum_small(bitrates, axis=1),
        bitrates,
    )
    # Audio has a single "layer": zero the matrix so allocation skips it.
    bitrates = jnp.where(state.meta.is_video[:, None, None], bitrates, 0.0)

    # ---- 3+6. forward decision: computed in media_plane_tick's phase 0
    # as ONE room-batched Pallas kernel (selection + subscription/mute
    # base merge + audio path + egress bit packing + send sums) and
    # passed in — the dense [T,K,S] masks never materialize. The SN/TS/
    # VP8 value rewrites happen host-side (runtime/munge.py) from the
    # send/drop/switch bits + host-owned offset state; NACK/RTX replay is
    # likewise host-side (HostSequencer), and probe padding synthesis
    # (WritePaddingRTP, downtrack.go:764) rides the same host state.

    # ---- BWE per subscriber (uses this tick's actual send counts) ------
    # Released slots reset their per-sub state first: the next occupant
    # must not inherit a decayed rate or a sticky feedback latch.
    def _reset_rows(cur_tree, init_tree, mask):
        def f(c, i):
            m = mask.reshape(mask.shape + (1,) * (c.ndim - mask.ndim))
            return jnp.where(m, i, c)
        return jax.tree.map(f, cur_tree, init_tree)

    bwe_prev = _reset_rows(state.bwe_state, bwe.init_state(S), inp.sub_reset)
    delay_prev = _reset_rows(
        state.delay_bwe, bwe.delay_init_state(S), inp.sub_reset
    )
    pacer_prev = _reset_rows(
        state.pacer_state, pacer.init_state(S), inp.sub_reset
    )
    pkts_sent = pkts_sent_i.astype(jnp.float32)                 # [S]
    bwe_state, congested, trend, budget = bwe.update_tick(
        bwe_prev, bwe_params, inp.estimate, inp.estimate_valid,
        pkts_sent, inp.nacks,
    )
    # TWCC send-side estimate (transport.go:253-374 seat): where active,
    # it CAPS the budget — allocation then never exceeds what the sender
    # itself measured from feedback, however optimistic (or absent) the
    # client's volunteered estimates are.
    delay_bwe, delay_rate, delay_over, delay_active = bwe.delay_update_tick(
        delay_prev, bwe.DelayBWEParams(), inp.fb_delay_ms,
        inp.fb_recv_bps, inp.fb_valid, inp.fb_enabled, pkts_sent, inp.tick_ms,
    )
    budget = jnp.where(delay_active, jnp.minimum(budget, delay_rate), budget)
    congested = congested | delay_over

    # ---- leaky-bucket egress pacing (pacer/leaky_bucket.go:47-200) ------
    # Budgets from the allocator's committed rate gate the HOST egress
    # (runtime/udp.py _pacer_gate) when rtc.pacer == "leaky-bucket"; in
    # other modes the output is simply unused.
    pacer_state, pacer_allowed, _pacer_backlog = pacer.update_tick(
        pacer_prev, pacer.PacerParams(), sent_bytes_i.astype(jnp.float32),
        budget, inp.tick_ms,
    )

    # (Cross-track allocation happens in media_plane_tick's phase 2 as one
    # room-batched Pallas kernel; this core returns `bitrates` for it.)

    # ---- connection quality (scorer.go E-model; room.go:1318 worker) ----
    # Scored every tick over the accumulating stats window; the host rolls
    # the window ~1/s via inp.roll_quality.
    expected = rtpstats.expected_packets(stats)                       # [T*L]
    exp_d = jnp.maximum(expected - stats.snap_expected, 0).reshape(T, L)
    rcv_d = jnp.maximum(stats.received - stats.snap_received, 0).reshape(T, L)
    exp_t = jnp.sum(exp_d, axis=-1)
    rcv_t = jnp.sum(rcv_d, axis=-1)
    loss_pct = jnp.where(
        exp_t > 0, 100.0 * (exp_t - rcv_t) / jnp.maximum(exp_t, 1), 0.0
    ).astype(jnp.float32)
    jitter_rtp = jnp.max((stats.jitter_q4 >> 4).reshape(T, L), axis=-1)
    clock_khz = jnp.where(state.meta.is_video, 90.0, 48.0)
    jitter_ms = jitter_rtp.astype(jnp.float32) / clock_khz
    has_pkts = (rcv_t > 0) & state.meta.published
    track_mos, track_q = quality.connection_quality(
        loss_pct, inp.pub_rtt_ms, jitter_ms, has_pkts
    )
    # A pub-muted track legitimately sends nothing — it must not read as
    # LOST (connectionstats.go excludes muted tracks from LOST detection).
    track_mos = jnp.where(state.meta.pub_muted, 5.0, track_mos)
    track_q = jnp.where(
        state.meta.pub_muted, quality.QUALITY_EXCELLENT, track_q
    )
    track_q = jnp.where(state.meta.published, track_q, quality.QUALITY_LOST)
    roll = inp.roll_quality > 0
    stats = stats._replace(
        snap_received=jnp.where(roll, stats.received, stats.snap_received),
        snap_expected=jnp.where(roll, expected, stats.snap_expected),
    )

    # ---- RED encapsulation plan (redreceiver.go) -----------------------
    # Audio-only: which previous packets can ride as RFC 2198 redundancy
    # blocks on each primary; the host assembles bytes per RED subscriber.
    # Statically gated: with audio/red not in the enabled codecs, the plan
    # tensors are zero-K so the per-tick device→host transfer pays nothing.
    if red_enabled:
        red_state, red_sn, red_off, _red_len, red_ok = red.encode_plan_tick(
            state.red_state, inp.sn, inp.ts, inp.size,
            inp.valid & ~state.meta.is_video[:, None],
        )
    else:
        red_state = state.red_state
        red_sn = jnp.zeros((T, 0, red.RED_DISTANCE), jnp.int32)
        red_off = jnp.zeros((T, 0, red.RED_DISTANCE), jnp.int32)
        red_ok = jnp.zeros((T, 0, red.RED_DISTANCE), jnp.bool_)

    # ---- 7. audio levels + active speakers -----------------------------
    is_audio_pkt = inp.valid & ~state.meta.is_video[:, None]
    audio_state, linear, is_active = audio.observe_tick(
        state.audio_state, audio_params,
        jnp.where(is_audio_pkt, inp.audio_level, 127),
        inp.frame_ms,
        is_audio_pkt,
        inp.tick_ms,
    )
    k = min(SPEAKER_TOP_K, T)
    spk_levels, spk_tracks = audio.top_speakers(
        jnp.where(is_active & state.meta.published, linear, 0.0), k
    )
    if k < SPEAKER_TOP_K:
        pad = SPEAKER_TOP_K - k
        spk_levels = jnp.pad(spk_levels, (0, pad))
        spk_tracks = jnp.pad(spk_tracks, (0, pad), constant_values=-1)

    new_state = PlaneState(
        meta=state.meta,
        ctrl=state.ctrl,
        stats=stats,
        audio_state=audio_state,
        sel=state.sel,  # phase 2 installs the post-selection, re-targeted
                        # selector state (this leaf is replaced there)
        bwe_state=bwe_state,
        delay_bwe=delay_bwe,
        tracker=tracker,
        pacer_state=pacer_state,
        red_state=red_state,
        temporal_bytes=temporal_bytes,
    )
    zero_s = jnp.zeros((S,), jnp.int32)
    outputs = TickOutputs(
        send_bits=send_bits,
        drop_bits=drop_bits,
        switch_bits=switch_bits,
        need_keyframe=need_kf,
        speaker_levels=spk_levels,
        speaker_tracks=spk_tracks,
        congested=congested,
        target_layers=jnp.zeros((S, T), jnp.int32),  # phase 2
        fwd_packets=fwd_packets_i,
        fwd_bytes=fwd_bytes_i,
        track_mos=track_mos,
        track_quality=track_q,
        sub_quality=zero_s,                          # phase 2
        layer_live=layer_status.reshape(T, L),
        layer_fps=layer_fps.reshape(T, L),
        track_loss_pct=loss_pct,
        track_jitter_ms=jitter_ms,
        track_bps=jnp.sum(layer_bps, axis=-1),
        committed_bps=budget,
        pacer_allowed=pacer_allowed,
        deficient=zero_s.astype(bool),               # phase 2
        red_sn=red_sn.astype(jnp.int32),
        red_off=red_off.astype(jnp.int32),
        red_ok=red_ok,
    )
    return new_state, outputs, bitrates


@device_entry("plane.media_plane_tick")
def media_plane_tick(
    state: PlaneState,
    inp: TickInputs,
    audio_params: audio.AudioLevelParams = audio.AudioLevelParams(),
    bwe_params: bwe.BWEParams = bwe.BWEParams(),
    red_enabled: bool = True,
):
    """One tick of the full media plane.

    Three phases: (0) room-BATCHED layer selection (Pallas kernel, rooms
    on the vector lanes — a vmapped per-room kernel pays per-grid-step
    fixed costs ×R); (1) the per-room core, vmapped; (2) room-BATCHED
    cross-track allocation, whose targets feed the NEXT tick's selection
    (the reference's allocator lags forwarding the same way —
    streamallocator.go ticks at 100 ms).

    jit this (donating `state`) and step it from the runtime loop;
    `red_enabled` is static per compile. The [R] axis is the mesh-sharded
    axis (see livekit_server_tpu.parallel.mesh — sharded via shard_map,
    so the Pallas grids stay shard-local).
    """
    L = MAX_LAYERS

    # ---- phase 0: forward decision over all rooms ----------------------
    # ONE room-batched Pallas kernel: selection, subscription/mute base
    # merge, audio path, egress bit packing, and the per-subscriber send
    # sums — dense [R,T,K,S] masks never exist in HBM.
    base = (
        state.ctrl.subscribed
        & ~state.ctrl.sub_muted
        & (state.meta.published & ~state.meta.pub_muted)[:, :, None]
    )                                                           # [R, T, S]
    (sel_state, send_bits, drop_bits, switch_bits, need_kf,
     pkts_sent, sent_bytes, fwd_packets, fwd_bytes) = selector.decide_rooms(
        state.sel, state.meta.is_svc, state.meta.is_video, base,
        inp.layer, inp.temporal, inp.keyframe, inp.layer_sync,
        inp.end_frame, inp.valid, inp.size,
        wire_overhead=pacer.WIRE_OVERHEAD_BYTES,
    )

    # ---- phase 1: per-room core (vmapped) ------------------------------
    def tick_one(st, i, sb, db, wb, nk, ps, sby, fp, fby):
        return _room_tick(st, i, sb, db, wb, nk, ps, sby, fp, fby,
                          audio_params, bwe_params, red_enabled)

    inp_axes = TickInputs(**{f: 0 for f in TickInputs._fields})._replace(
        tick_ms=None, roll_quality=None
    )
    new_state, outputs, bitrates = jax.vmap(
        tick_one, in_axes=(0, inp_axes, 0, 0, 0, 0, 0, 0, 0, 0)
    )(state, inp, send_bits, drop_bits, switch_bits, need_kf,
      pkts_sent, sent_bytes, fwd_packets, fwd_bytes)

    # ---- phase 2: allocation over all rooms → next tick's targets ------
    video_active = (
        state.meta.is_video & state.meta.published & ~state.meta.pub_muted
    )
    alloc_muted = ~(
        state.ctrl.subscribed & video_active[:, :, None]
        & ~state.ctrl.sub_muted
    ).transpose(0, 2, 1)                                        # [R, S, T]
    target_flat, _used, deficient = allocation.allocate_budget_rooms(
        bitrates,
        state.ctrl.max_spatial.transpose(0, 2, 1),
        state.ctrl.max_temporal.transpose(0, 2, 1),
        alloc_muted,
        outputs.committed_bps,
        allow_pause=bwe_params.allow_pause,
    )                                                           # [R, S, T]
    tgt_ts = target_flat.transpose(0, 2, 1)                     # [R, T, S]
    sel_state = selector.set_target(
        sel_state,
        jnp.clip(allocation.spatial_of(tgt_ts), -1, L - 1),
        allocation.temporal_of(tgt_ts),
    )
    any_deficient = jnp.any(deficient, axis=-1)                 # [R, S]
    sub_q = jnp.where(
        outputs.congested,
        quality.QUALITY_POOR,
        jnp.where(any_deficient, quality.QUALITY_GOOD,
                  quality.QUALITY_EXCELLENT),
    ).astype(jnp.int32)
    new_state = new_state._replace(sel=sel_state)
    outputs = outputs._replace(
        target_layers=target_flat,
        deficient=any_deficient,
        sub_quality=sub_q,
    )
    return new_state, outputs


# ---------------------------------------------------------------------------
# Wire packing: one upload + one fetch per tick.
#
# Every host↔device transfer pays a fixed latency, so the
# runtime ships TickInputs as ONE stacked int32 array (+ one float32 feedback
# array) and receives TickOutputs as ONE flat int32 buffer, unpacked by known
# offsets on host. The reference has no analog — its packets stay in host
# memory — this is the TPU build's host↔HBM DMA discipline (SURVEY.md §7
# "double-buffered DMA").
# ---------------------------------------------------------------------------

# Fields uploaded to the device. TickInputs also carries HOST-ONLY fields
# (pid / tl0 / keyidx / ts_jump / pad_num / pad_track) consumed by the
# host munger + padding synthesis (runtime/munge.py) — the device tick
# never reads them, so they are not packed onto the wire.
PKT_FIELDS = (
    "sn", "ts", "layer", "temporal", "keyframe", "layer_sync", "begin_pic",
    "end_frame", "size", "frame_ms", "audio_level", "arrival_rtp", "valid",
)
_BOOL_FIELDS = {"keyframe", "layer_sync", "begin_pic", "end_frame", "valid"}
HOST_ONLY_PKT_FIELDS = ("pid", "tl0", "keyidx", "ts_jump")


def pack_tick_inputs(inp: TickInputs):
    """Host-side: TickInputs → (pkt [F,R,T,K] i32, fb [8,R,S] f32,
    tf [1,R,T] f32, tick_ms, roll_quality)."""
    import numpy as np

    pkt = np.stack([np.asarray(getattr(inp, f)).astype(np.int32) for f in PKT_FIELDS])
    fb = np.stack(
        [
            np.asarray(inp.estimate, np.float32),
            np.asarray(inp.estimate_valid).astype(np.float32),
            np.asarray(inp.nacks, np.float32),
            np.asarray(inp.fb_delay_ms, np.float32),
            np.asarray(inp.fb_recv_bps, np.float32),
            np.asarray(inp.fb_valid).astype(np.float32),
            np.asarray(inp.fb_enabled).astype(np.float32),
            np.asarray(inp.sub_reset).astype(np.float32),
        ]
    )
    tf = np.asarray(inp.pub_rtt_ms, np.float32)[None]
    return (
        pkt, fb, tf,
        np.int32(inp.tick_ms), np.int32(inp.roll_quality),
    )


def unpack_tick_inputs(
    pkt: jax.Array, fb: jax.Array, tf: jax.Array,
    tick_ms: jax.Array, roll_quality: jax.Array,
) -> TickInputs:
    """Device-side (traced): stacked arrays → TickInputs.

    Host-only fields are filled with zeros: the device algebra never reads
    them (XLA dead-code-eliminates the placeholders)."""
    fields = {}
    for i, name in enumerate(PKT_FIELDS):
        x = pkt[i]
        fields[name] = x.astype(jnp.bool_) if name in _BOOL_FIELDS else x
    z_pkt = jnp.zeros_like(pkt[0])
    for name in HOST_ONLY_PKT_FIELDS:
        fields[name] = z_pkt
    z_sub = jnp.zeros(fb.shape[1:], jnp.int32)
    return TickInputs(
        **fields,
        estimate=fb[0],
        estimate_valid=fb[1] > 0.5,
        nacks=fb[2],
        pub_rtt_ms=tf[0],
        pad_num=z_sub,
        pad_track=z_sub - 1,
        fb_delay_ms=fb[3],
        fb_recv_bps=fb[4],
        fb_valid=fb[5] > 0.5,
        fb_enabled=fb[6] > 0.5,
        sub_reset=fb[7] > 0.5,
        tick_ms=tick_ms,
        roll_quality=roll_quality,
    )


def pack_ctrl_rows(meta: TrackMeta, ctrl: SubControl, rows, pad_to: int | None = None):
    """Host-side half of the dirty-row control upload: gather the dirtied
    room rows of the host mirrors into two stacked int32 arrays.

    Returns (rows [n] i32, meta_rows [4, n, T] i32, ctrl_rows [4, n, T, S]
    i32) — O(dirty rows) bytes, not O(R·T·S). `pad_to` repeats the first
    row up to a bucket size so the device scatter compiles once per
    bucket instead of once per distinct dirty count (duplicate indices
    carry identical values, so the scatter stays deterministic).
    """
    import numpy as np

    rows = np.asarray(sorted(rows), np.int32)
    if pad_to is not None and len(rows) < pad_to:
        rows = np.concatenate([rows, np.repeat(rows[:1], pad_to - len(rows))])
    meta_rows = np.stack([np.asarray(m)[rows].astype(np.int32) for m in meta])
    ctrl_rows = np.stack([np.asarray(c)[rows].astype(np.int32) for c in ctrl])
    return rows, meta_rows, ctrl_rows


@device_entry("plane.apply_ctrl_delta")
def apply_ctrl_delta(state: PlaneState, rows, meta_rows, ctrl_rows) -> PlaneState:
    """Device-side (traced) half: scatter the dirtied rows into the
    control tensors via `.at[rows].set(...)` — the delta-upload analog of
    the full `_replace` in PlaneRuntime._upload_ctrl. Jitted with the
    state donated, so the row writes are in-place in HBM."""
    meta = TrackMeta(
        *[
            leaf.at[rows].set(meta_rows[i].astype(leaf.dtype))
            for i, leaf in enumerate(state.meta)
        ]
    )
    ctrl = SubControl(
        *[
            leaf.at[rows].set(ctrl_rows[i].astype(leaf.dtype))
            for i, leaf in enumerate(state.ctrl)
        ]
    )
    return state._replace(meta=meta, ctrl=ctrl)


def pack_tick_outputs(out: TickOutputs) -> jax.Array:
    """Device-side (traced): TickOutputs → one flat int32 buffer.

    float32 leaves travel as bit patterns (bitcast), bools as 0/1.
    """
    def flat(x):
        if x.dtype == jnp.float32:
            x = jax.lax.bitcast_convert_type(x, jnp.int32)
        return x.astype(jnp.int32).reshape(-1)

    return jnp.concatenate([flat(getattr(out, f)) for f in TickOutputs._fields])


def unpack_tick_outputs(
    buf, dims: PlaneDims, red_enabled: bool = True
) -> TickOutputs:
    """Host-side: flat int32 numpy buffer → TickOutputs of numpy arrays."""
    import numpy as np

    R, T, K, S = dims
    W = mask_words(S)
    shapes = {
        "send_bits": (R, T, K, W),
        "drop_bits": (R, T, K, W),
        "switch_bits": (R, T, K, W),
        "need_keyframe": (R, T, S),
        "speaker_levels": (R, SPEAKER_TOP_K),
        "speaker_tracks": (R, SPEAKER_TOP_K),
        "congested": (R, S),
        "target_layers": (R, S, T),
        "fwd_packets": (R,),
        "fwd_bytes": (R,),
        "track_mos": (R, T),
        "track_quality": (R, T),
        "sub_quality": (R, S),
        "layer_live": (R, T, MAX_LAYERS),
        "layer_fps": (R, T, MAX_LAYERS),
        "track_loss_pct": (R, T),
        "track_jitter_ms": (R, T),
        "track_bps": (R, T),
        "committed_bps": (R, S),
        "pacer_allowed": (R, S),
        "deficient": (R, S),
        "red_sn": (R, T, K if red_enabled else 0, red.RED_DISTANCE),
        "red_off": (R, T, K if red_enabled else 0, red.RED_DISTANCE),
        "red_ok": (R, T, K if red_enabled else 0, red.RED_DISTANCE),
    }
    floats = {"speaker_levels", "track_mos", "track_loss_pct", "track_jitter_ms",
              "track_bps", "committed_bps", "pacer_allowed", "layer_fps"}
    bools = {"need_keyframe", "congested", "deficient", "red_ok"}
    buf = np.asarray(buf)
    pieces, off = {}, 0
    for name in TickOutputs._fields:
        n = int(np.prod(shapes[name]))
        x = buf[off : off + n].reshape(shapes[name])
        off += n
        if name in floats:
            x = x.view(np.float32)
        elif name in bools:
            x = x.astype(bool)
        pieces[name] = x
    return TickOutputs(**pieces)


def masks_to_dense(out: TickOutputs, dims: PlaneDims):
    """Unpack the bit-packed egress masks to dense [R,T,K,S] bools
    (host/test helper; the runtime's fan-out uses the same expansion)."""
    S = dims.subs
    return (
        unpack_bits(out.send_bits, S),
        unpack_bits(out.drop_bits, S),
        unpack_bits(out.switch_bits, S),
    )
