"""The tick loop: control mutations in, device step, outputs fanned out.

This is the TPU replacement for the reference's always-on goroutine mesh:
where pkg/sfu runs one forwardRTP loop per (track, layer) plus per-
subscriber allocator/transport loops, this runtime advances the ENTIRE
node in one jitted call per tick (models/plane.media_plane_tick, room axis
sharded over the mesh — parallel/mesh.py).

Per tick:
  1. apply queued control mutations to the host mirrors of TrackMeta /
     SubControl (subscription churn lands at tick boundaries — the
     reference serializes the same churn with locks + shadow slices,
     downtrackspreader.go:110)
  2. drain the IngestBuffer → TickInputs
  3. step the device plane
  4. fan out TickOutputs: egress writes (send mask × munged headers +
     payload slab), speaker updates, keyframe/PLI requests, congestion →
     registered async callbacks

Checkpoint/resume (§5.4): snapshot()/restore() serialize the full device
state tree — the analog of the reference's ForwarderState/RTPMungerState
migration seeding (forwarder.go:340-376).
"""

from __future__ import annotations

import asyncio
import functools
import time
from dataclasses import dataclass, field
from typing import Any, Awaitable, Callable

import jax
import numpy as np

from livekit_server_tpu.models import plane
from livekit_server_tpu.runtime import trace as trace_mod
from livekit_server_tpu.runtime.ingest import IngestBuffer
from livekit_server_tpu.runtime.munge import HostMunger
from livekit_server_tpu.runtime.probe import PAD_BYTES, ProbeController
from livekit_server_tpu.runtime.slots import SlotAllocator

# `PlaneRuntime.choose_depth`'s thresholds, shares of the period and ticks:
# constants, not configuration.
CHAIN_TICKS = 8           # ticks the chain's running estimate averages over
DEPTH_ENTER_SHARE = 0.95  # at depth 0, chain + lag over it: behind, pipeline
DEPTH_FIT_SHARE = 0.85    # a chain over it, once behind, is what did not fit
DEPTH_LAG_GONE = 0.15     # pipelined, a lag under it: caught up
DEPTH_TRY_SLACK = 0.25    # a try needs to have slept this much before its edge
RETRY_TICKS = 32          # a chain that did not fit: ticks before depth 0 is
RETRY_MAX_TICKS = 1024    # tried again, doubling up to this a failed try


@dataclass
class EgressPacket:
    """One packet to deliver to one subscriber (host egress unit)."""

    room: int
    track: int
    sub: int
    sn: int
    ts: int
    pid: int
    tl0: int
    keyidx: int
    size: int
    payload: bytes
    marker: bool = False
    padding: bool = False  # probe padding (RTP P-bit; no media payload)
    dd: bytes = b""       # dependency-descriptor ext bytes (SVC tracks)
    t_arr: float = 0.0    # rx stamp (forward-latency probe; 0 = unstamped)


@dataclass
class EgressBatch:
    """One tick's egress as column arrays — the vectorized host-egress
    unit (no per-packet Python objects on the wire path). All arrays are
    [N] over egress entries; payload bytes stay in the ingest slab and
    are gathered by (room, track, k) index math."""

    rooms: np.ndarray     # int32
    tracks: np.ndarray    # int32
    ks: np.ndarray        # int32 — packet slot within the tick
    subs: np.ndarray      # int32
    sn: np.ndarray        # int32 (16-bit munged)
    ts: np.ndarray        # int32 (32-bit munged, two's complement)
    pid: np.ndarray       # int32
    tl0: np.ndarray       # int32
    keyidx: np.ndarray    # int32
    payloads: Any         # PayloadSlab
    # Attribution stamps (runtime/trace.py LatencyAttribution): when the
    # owning tick was dispatched to the device and when its step
    # committed — the stage boundaries the sampled wire-latency
    # decomposition splits on. 0.0 = unstamped (tracing off / tests).
    t_dispatch: float = 0.0
    t_device_end: float = 0.0

    def __len__(self) -> int:
        return len(self.rooms)

    def to_packets(self, mask: np.ndarray | None = None) -> list[EgressPacket]:
        """Materialize EgressPacket objects (WS delivery / tests); `mask`
        selects a subset of entries."""
        idx = np.nonzero(mask)[0] if mask is not None else range(len(self.rooms))
        out = []
        ta = self.payloads.t_arr
        for i in idx:
            r, t, k = int(self.rooms[i]), int(self.tracks[i]), int(self.ks[i])
            payload, marker = self.payloads.get(r, t, k)
            out.append(
                EgressPacket(
                    room=r, track=t, sub=int(self.subs[i]),
                    sn=int(self.sn[i]) & 0xFFFF,
                    ts=int(self.ts[i]) & 0xFFFFFFFF,
                    pid=int(self.pid[i]),
                    tl0=int(self.tl0[i]),
                    keyidx=int(self.keyidx[i]),
                    size=len(payload),
                    payload=payload,
                    marker=marker,
                    dd=self.payloads.get_dd(r, t, k),
                    t_arr=float(ta[r, t, k]) if ta is not None else 0.0,
                )
            )
        return out


class HostSequencer:
    """Host-side NACK/RTX replay ring (pkg/sfu/sequencer.go:82-370 seat).

    The device's egress batch already hands the host every send's munged
    SN/TS/descriptor, so the replay ring lives in numpy and NACKs resolve
    at RTCP time — one tick-cadence device round trip fewer, and the
    device tick carries no scatter-heavy sequencer state (a TPU scatter
    serializes per element; the device-side ring was measured at ~80% of
    the whole tick).

    One ring per (room, sub); slot = munged SN & (RING-1); cross-track
    collisions evict (a miss makes the client re-NACK, exactly like an
    evicted reference ring entry). Replays are RTT-throttled per slot
    (sequencer.go:263 getExtPacketMetas semantics).
    """

    RING = 512
    # Retransmit-amplification bounds: one compound NACK (BLP masks) can
    # name the whole slab window — tiny RTCP in must not buy full-history
    # media out. Per-resolve burst cap + per-subscriber replay budget that
    # refills each second (sequencer.go bounds the same pressure via its
    # per-tick staging slots).
    BURST_CAP = 16
    BUDGET_PER_S = 256

    def __init__(self, dims: plane.PlaneDims):
        R, S = dims.rooms, dims.subs
        self._tk = dims.tracks * dims.pkts
        self._k = dims.pkts
        self._s = S
        self.budget = np.full((R, S), self.BUDGET_PER_S, np.int32)
        self._budget_refill_ms = np.zeros((R, S), np.int64)
        shape = (R, S, self.RING)
        self.key = np.full(shape, -1, np.int32)       # slab history key
        self.sn = np.full(shape, -1, np.int32)
        self.track = np.full(shape, -1, np.int32)
        self.ts = np.zeros(shape, np.int64)
        self.pid = np.zeros(shape, np.int32)
        self.tl0 = np.zeros(shape, np.int32)
        self.keyidx = np.zeros(shape, np.int32)
        self.at_tick = np.full(shape, -(1 << 30), np.int64)
        self.last_ms = np.full(shape, -(1 << 60), np.int64)

    def record(self, batch: "EgressBatch", tick_idx: int) -> None:
        """Vectorized ring update from one tick's egress batch (the push
        half of sequencer.go; duplicate slots resolve last-write-wins)."""
        if not len(batch):
            return
        slot = batch.sn & (self.RING - 1)
        r, s = batch.rooms, batch.subs
        w = tick_idx % plane.SLAB_WINDOW
        # One flat index shared by all eight scatters (recomputing the
        # 3-D index math per field costs more than the writes themselves).
        flat = (r.astype(np.int64) * self._s + s) * self.RING + slot
        self.key.reshape(-1)[flat] = (
            w * self._tk + batch.tracks * self._k + batch.ks
        )
        self.sn.reshape(-1)[flat] = batch.sn & 0xFFFF
        self.track.reshape(-1)[flat] = batch.tracks
        self.ts.reshape(-1)[flat] = batch.ts.astype(np.int64) & 0xFFFFFFFF
        self.pid.reshape(-1)[flat] = batch.pid
        self.tl0.reshape(-1)[flat] = batch.tl0
        self.keyidx.reshape(-1)[flat] = batch.keyidx
        self.at_tick.reshape(-1)[flat] = tick_idx

    def clear_room(self, room: int) -> None:
        self.sn[room] = -1
        self.key[room] = -1
        self.track[room] = -1
        # A recycled row must not inherit the previous room's drained
        # replay budget OR its per-slot RTT throttle stamps (record()
        # never rewrites last_ms, so stale stamps would gate the new
        # room's first retransmits for up to one RTT).
        self.budget[room] = self.BUDGET_PER_S
        self._budget_refill_ms[room] = 0
        self.last_ms[room] = -(1 << 60)


@dataclass
class TickResult:
    """Host-visible outputs of one tick."""

    tick_index: int
    egress_batch: EgressBatch
    speakers: dict[int, list[tuple[int, float]]]     # room → [(track, level)]
    need_keyframe: list[tuple[int, int, int]]        # (room, track, sub)
    congested: dict[int, list[int]]                  # room → [sub]
    fwd_packets: int
    fwd_bytes: int
    tick_s: float                                    # wall time of the step
    # NACK retransmits are no longer tick-cadence: HostSequencer resolves
    # and transports send them at RTCP time (kept for API compat).
    replays: list[EgressPacket] = field(default_factory=list)
    padding: list[EgressPacket] = field(default_factory=list)  # probe padding
    # Quality / stats tensors (numpy views of TickOutputs; consumers index
    # by room row). None until the first tick completes.
    track_quality: Any = None     # [R, T] int32 ConnectionQuality enum
    track_mos: Any = None         # [R, T] float32
    sub_quality: Any = None       # [R, S] int32
    layer_live: Any = None        # [R, T, L] int32
    layer_fps: Any = None         # [R, T, L] float32 (measured fps)
    track_loss_pct: Any = None    # [R, T] float32
    track_jitter_ms: Any = None   # [R, T] float32
    # RED plan (ops/red): per-packet redundancy candidates for the host
    # egress to assemble (redreceiver.go seat).
    red_sn: Any = None            # [R, T, K, D] int32
    red_off: Any = None           # [R, T, K, D] int32
    red_ok: Any = None            # [R, T, K, D] bool
    pacer_allowed: Any = None     # [R, S] float32 — leaky-bucket byte budgets
    target_layers: Any = None     # [R, S, T] int32 flat layer targets (-1 = paused)
    track_bps: Any = None         # [R, T] float32
    quality_window_closed: bool = False  # this tick rolled the stats window
    _egress_cache: list[EgressPacket] | None = None

    @property
    def egress(self) -> list[EgressPacket]:
        """Lazy object view of egress_batch (WS fan-out, tests). The UDP
        wire path consumes egress_batch directly and never builds this."""
        if self._egress_cache is None:
            self._egress_cache = self.egress_batch.to_packets()
        return self._egress_cache


def _packed_tick(audio_params, bwe_params, red_enabled=True):
    """Packed-wire step: ONE input upload, ONE output fetch per tick
    (plane.pack_tick_inputs / pack_tick_outputs)."""

    def tick(state, pkt, fb, tf, tick_ms, roll_quality):
        inp = plane.unpack_tick_inputs(pkt, fb, tf, tick_ms, roll_quality)
        state, out = plane.media_plane_tick(
            state, inp, audio_params, bwe_params, red_enabled=red_enabled,
        )
        return state, plane.pack_tick_outputs(out)

    return tick


@functools.lru_cache(maxsize=None)
def _build_step(audio_params, bwe_params, red_enabled=True):
    return jax.jit(
        _packed_tick(audio_params, bwe_params, red_enabled),
        donate_argnums=(0,),
    )


@functools.lru_cache(maxsize=None)
def _build_ctrl_delta(sharding=None):
    """Dirty-row control upload (plane.apply_ctrl_delta), state donated so
    the row scatters run in-place in HBM. One instance per sharding,
    shared across runtimes like _build_step; jax caches per padded row
    count (the caller pads to power-of-two buckets to bound variants)."""
    if sharding is None:
        return jax.jit(plane.apply_ctrl_delta, donate_argnums=(0,))
    return jax.jit(
        plane.apply_ctrl_delta, donate_argnums=(0,), out_shardings=sharding
    )


def _set_rows(state, rows, row_tree):
    return jax.tree.map(lambda leaf, r: leaf.at[rows].set(r), state, row_tree)


@functools.lru_cache(maxsize=None)
def _build_row_write(sharding=None):
    """Whole-tree row scatter (restore_room / repair_room_row and the
    paged page-row write): one program instead of an eager scatter per
    leaf, so the server's warm-up can compile it and a migration's first
    adoption stays inside its ACK timeout. jit turns host leaves into
    device arrays, whatever form the state or the snapshot arrives in."""
    kw = {} if sharding is None else {"out_shardings": sharding}
    return jax.jit(_set_rows, donate_argnums=(0,), **kw)


@functools.lru_cache(maxsize=None)
def _build_row_read():
    """Whole-tree row gather (snapshot_room): the read half of
    `_build_row_write`, one program for the same reason."""
    return jax.jit(lambda state, row: jax.tree.map(lambda x: x[row], state))


def _row_buckets(top: int, first: int = 16, factor: int = 8) -> list[int]:
    """The few row counts a padded scatter is compiled at: `first`, then
    steps of `factor`, then `top` itself. The usual churn (a join, a leave)
    fits the first; a program per power of two would be a compile each at
    every server start for sizes no deployment reaches."""
    out, b = [], first
    while b < top:
        out.append(b)
        b *= factor
    return out + [top]


def _row_bucket(n: int, buckets: list[int]) -> int:
    """The smallest bucket that holds `n` rows."""
    return next(b for b in buckets if n <= b)


@dataclass
class StagedTick:
    """One tick's host-staged inputs, carried through the three-stage
    pipeline (stage N+1 ‖ device N ‖ fan-out N-1) with its per-stage
    timings. `packed` holds the pre-packed device arrays (non-mesh path):
    packing happens at STAGE time, so the staging set's field arrays are
    fully consumed before the set is recycled, and the worker thread's
    span shrinks to the device round trip alone."""

    inp: plane.TickInputs
    payloads: Any
    idx: int
    roll: bool
    packed: tuple | None = None
    stage_s: float = 0.0
    device_s: float = 0.0
    # The mapped pages the live-extent tick's grid ran over
    # (runtime/paged_runtime.py); 0 on the stock tick.
    live_pages: int = 0
    edge: float = 0.0      # scheduled dispatch edge (perf_counter)
    deadline: float = 0.0  # owning-tick egress deadline; 0 = unaccounted
    depth: int = 0         # pipeline depth this tick ran at
    # Express-lane handoff (runtime/express.py): rooms whose fast-path
    # subscribers were already served on arrival during this tick's
    # window (their bits are masked at fan-out), the packed sub-bit
    # words to clear, and the window's send log for the replay ring.
    express_rows: Any = None
    express_words: Any = None
    express_log: Any = None
    edge_over_us: float = 0.0  # wake overshoot past the dispatch edge
    # Span start stamps + extra durations for the trace ring
    # (runtime/trace.py): staging start, the express retier's slice of
    # it, the ctrl-upload window, and the device dispatch time.
    stage_t0: float = 0.0
    retier_s: float = 0.0
    upload_t0: float = 0.0
    upload_s: float = 0.0
    device_t0: float = 0.0
    # The loop's waits before the dispatch (the sleep to the edge, the
    # wait for state_lock), the device call's parts on the worker thread
    # (laid end to end from device_t0, read off their boundaries) and how
    # long the finished step waited for the event loop.
    sleep_t0: float = 0.0
    sleep_s: float = 0.0
    lock_t0: float = 0.0
    lock_s: float = 0.0
    dispatch_s: float = 0.0
    fetch_s: float = 0.0
    mirror_s: float = 0.0
    audit_s: float = 0.0
    handoff_s: float = 0.0


class PlaneRuntime:
    """Owns the device plane state + the host mirrors and tick loop."""

    def __init__(
        self,
        dims: plane.PlaneDims,
        tick_ms: int = 10,
        mesh=None,
        audio_params=None,
        bwe_params=None,
        red_enabled: bool = True,
        egress_shards: int = 0,
        egress_multicast: bool = True,
        express_max_subs: int = 0,
        express_max_rooms: int = 16,
        trace_enabled: bool = True,
        trace_ring_ticks: int = 512,
        trace_sample_every: int = 64,
        blackbox_events: int = 64,
    ):
        from livekit_server_tpu.ops import audio as audio_ops, bwe as bwe_ops

        self.dims = dims
        self.tick_ms = tick_ms
        self.red_enabled = red_enabled
        self.slots = SlotAllocator(dims.rooms, dims.tracks, dims.subs)
        self.ingest = IngestBuffer(dims, tick_ms)
        self.tick_index = 0
        self._ap = audio_params or audio_ops.AudioLevelParams()
        self._bp = bwe_params or bwe_ops.BWEParams()

        R, T, S = dims.rooms, dims.tracks, dims.subs
        # Host mirrors of control tensors; mutated by the control plane,
        # uploaded at tick boundaries when dirty.
        self.meta = plane.TrackMeta(
            is_video=np.zeros((R, T), bool),
            published=np.zeros((R, T), bool),
            pub_muted=np.zeros((R, T), bool),
            is_svc=np.zeros((R, T), bool),
        )
        self.ctrl = plane.SubControl(
            subscribed=np.zeros((R, T, S), bool),
            sub_muted=np.zeros((R, T, S), bool),
            max_spatial=np.full((R, T, S), plane.MAX_LAYERS - 1, np.int32),
            max_temporal=np.full((R, T, S), 3, np.int32),
        )
        # Control-upload dirty tracking: mutations record their room row;
        # the upload ships only those rows unless the full flag is set
        # (init/restore) or the count crosses ctrl_delta_max_rows.
        self._ctrl_dirty = True          # full [R, T, S] upload needed
        self._dirty_rows: set[int] = set()
        self.ctrl_delta_max_rows = max(1, dims.rooms // 8)
        # Governor shed overlay (runtime/governor.py): applied to the
        # EFFECTIVE control tensors at upload time, never written into
        # the authoritative `self.ctrl` mirrors — snapshots, failover
        # restores, and recovery all keep every subscriber's true
        # desired caps, and un-shedding is just a re-upload.
        self.shed_spatial_cap = plane.MAX_LAYERS - 1   # no clamp
        self.shed_pause_video = False
        # Subscriptions exempt from the L3 video pause (screen-share /
        # active-speaker pins via update_track_settings).
        self.pinned = np.zeros((R, T, S), bool)
        # Optional OverloadGovernor; None unless RoomManager attaches
        # one. _complete feeds it each finished tick's verdict.
        self.governor = None

        self.state = self._init_device_state()
        # Host-owned SN/TS/VP8 rewrite state (the round-5 decide-on-
        # device / rewrite-on-host split; see runtime/munge.py).
        self.munger = HostMunger(dims)
        # Sharded native egress plane (runtime/egress_plane.py): one
        # shared instance plans the room-aligned shard cuts for BOTH the
        # munge walk (here, _fan_out) and the send walk (udp.py attaches
        # via attach_egress_plane) and aggregates per-shard stats.
        from livekit_server_tpu.runtime.egress_plane import EgressPlane

        self.egress_plane = EgressPlane(egress_shards, egress_multicast)
        self._munge_shard_plan = self.egress_plane.room_plan(dims.rooms)
        # Two-tier latency plane (runtime/express.py): when enabled,
        # small/interactive rooms are forwarded on packet arrival from
        # the last device selector mirror instead of waiting for the
        # batched tick. None when express_max_subs == 0.
        self.express = None
        if express_max_subs > 0:
            from livekit_server_tpu.runtime.express import ExpressLane

            self.express = ExpressLane(self, express_max_subs, express_max_rooms)
        self._mesh = mesh
        self._init_step()

        # Rolling payload history for NACK replay (slab keys reference slot
        # tick % SLAB_WINDOW; resolve_nacks age-gates so a recycled slot is
        # never dereferenced) + the host-side replay ring it feeds.
        self._slab_history: list = [None] * plane.SLAB_WINDOW
        self.host_seq = HostSequencer(dims)
        # BWE probe controller (probe_controller.go) + its inputs mirrored
        # from the previous tick's outputs.
        self.prober = ProbeController(dims, tick_ms)
        self._last_committed = np.zeros((R, S), np.float32)
        self._last_congested = np.zeros((R, S), bool)
        self._last_deficient = np.zeros((R, S), bool)
        self._task: asyncio.Task | None = None
        self._complete_task: asyncio.Task | None = None
        # The receive path's read schedule (runtime/udp.py RxSchedule;
        # `attach_rx`): `_run` tells it when a tick's chain begins and
        # ends, and it reads the socket around them.
        self.rx = None
        # Bumped by PlaneSupervisor on restart: a device step that started
        # before the bump must not commit its result over restored state
        # (the stale step ran — or is still wedged — on the abandoned
        # executor thread).
        self.run_epoch = 0
        # Optional FaultInjector (runtime/faultinject.py); None on the
        # default config path — chaos tests and soak runs attach one.
        self.fault = None
        # Optional IntegrityMonitor (runtime/integrity.py); None unless
        # RoomManager attaches one. _device_step runs its audit on the
        # cadence; _complete drains its row-repair queue; quarantined
        # rows are masked at fan-out and muted in the effective ctrl.
        self.integrity = None
        # Guards self.state across the donated device step vs. host-side
        # snapshot/restore (room migration): donation deletes the old
        # buffers mid-step, so concurrent readers would see dead arrays.
        self.state_lock = asyncio.Lock()
        self._on_tick: list[Callable[[TickResult], Awaitable[None] | None]] = []
        self.stats = {
            "ticks": 0, "fwd_packets": 0, "fwd_bytes": 0, "late_ticks": 0,
            # Ticks the serving loop ran at depth 0 (`choose_depth`); the
            # rest of `ticks` ran pipelined, a tick deep.
            "depth0_ticks": 0,
            # Pipeline shape: cumulative per-stage seconds + stall count
            # (a window that found the previous fan-out still running).
            "stage_s": 0.0, "device_s": 0.0, "fanout_s": 0.0,
            "pipeline_stalls": 0,
            # Control-upload accounting (the dirty-row protocol's receipt).
            "ctrl_full_uploads": 0, "ctrl_delta_uploads": 0,
            "ctrl_delta_rows": 0, "ctrl_upload_bytes": 0,
        }
        from collections import deque

        # Per-tick stage breakdown dicts (idx/stage_ms/device_ms/fanout_ms/
        # total_ms/depth/late and the waits between them) — the
        # /debug/ticks pipeline view.
        self.recent_ticks: deque = deque(maxlen=120)
        # Tick-edge sleep calibration: measured coarse-sleep overshoot
        # for this host (seconds; <0 = not yet calibrated — falls back
        # to the historical fixed 1.5 ms margin), and the last wake's
        # overshoot past its edge (surfaced per tick in recent_ticks).
        self._sleep_bias = -1.0
        self._edge_overshoot_us = 0.0
        # Single worker: device steps are strictly ordered (donated state).
        from concurrent.futures import ThreadPoolExecutor

        self._executor = ThreadPoolExecutor(max_workers=1, thread_name_prefix="plane")

        # Recompile watchdog: process-wide XLA compile counter. The
        # server marks the warmup watermark after its warm step; the
        # steady-state tick path must not compile past it (GC11's
        # runtime half — see runtime/compile_ledger.py).
        from livekit_server_tpu.runtime.compile_ledger import LEDGER

        self.compile_ledger = LEDGER.install()

        # Flight-recorder tracing plane (runtime/trace.py): fixed ring of
        # per-tick span records, the sampled wire-latency attribution
        # stage decomposer, and the per-room black-box event recorder.
        # trace/wire_stages are None when disabled, and the spans then
        # stamp without annotating or totalling; the black box is
        # always on (cold-path emits only, bounded per-room rings).
        self.spans = trace_mod.Spans(trace_enabled)
        self.trace = None
        self.wire_stages = None
        if trace_enabled:
            self.trace = trace_mod.TickTraceRing(trace_ring_ticks, self.spans)
            self.wire_stages = trace_mod.LatencyAttribution(trace_sample_every)
        self.blackbox = trace_mod.BlackBox(R, blackbox_events)

    # -- device-layout seams (overridden by PagedPlaneRuntime) ------------
    # The host side of the runtime — mirrors, munger, sequencer, express,
    # fan-out, governor — speaks LOGICAL dense [R, T, S] shapes. These
    # four hooks are the only places the device layout leaks in, so a
    # subclass can swap the dense plane for the pooled paged plane
    # (runtime/paged_runtime.py) without touching the tick loop.

    def _init_device_state(self):
        """Allocate the device-resident plane state (dense layout)."""
        return plane.init_state(self.dims)

    def _init_step(self) -> None:
        """Build the jitted device step + ctrl-delta appliers."""
        if self._mesh is not None:
            from livekit_server_tpu.parallel import make_sharded_tick, shard_tree
            from livekit_server_tpu.parallel.mesh import room_sharding

            self.state = shard_tree(self.state, self._mesh)
            self._step = make_sharded_tick(
                self._mesh, self._ap, self._bp, donate=True,
                red_enabled=self.red_enabled,
            )
            self._apply_delta = _build_ctrl_delta(room_sharding(self._mesh))
            self._row_write = _build_row_write(room_sharding(self._mesh))
        else:
            # Shared across PlaneRuntime instances with identical params so
            # repeated construction (tests, restarts) reuses the XLA
            # compilation cache instead of re-tracing a fresh closure.
            self._step = _build_step(self._ap, self._bp, self.red_enabled)
            self._apply_delta = _build_ctrl_delta()
            self._row_write = _build_row_write()

    def _pack_inputs(self, inp: plane.TickInputs) -> tuple:
        """Logical TickInputs → the device-step upload arrays."""
        return plane.pack_tick_inputs(inp)

    def _unpack_outputs(self, buf) -> plane.TickOutputs:
        """Device-step output buffer → LOGICAL-shape TickOutputs."""
        return plane.unpack_tick_outputs(
            np.asarray(buf), self.dims, self.red_enabled
        )

    def _sel_mirror(self, state) -> tuple:
        """The express lane's post-step selector mirror, in LOGICAL
        [R, T, S] shape: (current_spatial, current_temporal,
        target_spatial, target_temporal) numpy arrays."""
        sel = state.sel
        return (
            np.asarray(sel.current_spatial),
            np.asarray(sel.current_temporal),
            np.asarray(sel.target_spatial),
            np.asarray(sel.target_temporal),
        )

    def occupancy(self) -> dict:
        """Per-resource occupancy (rooms/tracks/subs used vs pool) for
        admission gating and /debug — the capacity accounting the slot
        allocator keeps. `admittable_rooms` is how many more MINIMAL
        rooms this plane could accept (the governor's L4 headroom key)."""
        return self.slots.occupancy()

    # -- control-plane mutation API (host mirrors; applied at tick edge) --
    def set_track(self, room: int, track: int, *, published: bool, is_video: bool,
                  pub_muted: bool = False, is_svc: bool = False,
                  pub_sub: int | None = None) -> None:
        self.meta.published[room, track] = published
        self.meta.is_video[room, track] = is_video
        self.meta.pub_muted[room, track] = pub_muted
        self.meta.is_svc[room, track] = is_svc
        # pub_sub: the publishing participant's subscriber slot — lets the
        # tick score this track's MOS with the publisher-path RTT. None
        # leaves the existing mapping (mute toggles re-call set_track).
        if pub_sub is not None:
            self.ingest.track_pub_sub[room, track] = pub_sub
        if not published:
            # Free the columns' subscriber state implicitly: masks go false.
            self.ctrl.subscribed[room, track, :] = False
            self.ingest.track_pub_sub[room, track] = -1
        self._dirty_rows.add(room)

    def set_subscription(self, room: int, track: int, sub: int, *,
                         subscribed: bool, sub_muted: bool = False) -> None:
        self.ctrl.subscribed[room, track, sub] = subscribed
        self.ctrl.sub_muted[room, track, sub] = sub_muted
        self._dirty_rows.add(room)

    def set_layer_caps(self, room: int, track: int, sub: int,
                       max_spatial: int, max_temporal: int = 3) -> None:
        self.ctrl.max_spatial[room, track, sub] = max_spatial
        self.ctrl.max_temporal[room, track, sub] = max_temporal
        self._dirty_rows.add(room)

    def set_pinned(self, room: int, track: int, sub: int, pinned: bool) -> None:
        """Exempt one subscription from the governor's L3 video pause
        (screen shares, active speakers). Dirty-row like any ctrl edit:
        the pin participates in the effective upload."""
        self.pinned[room, track, sub] = pinned
        self._dirty_rows.add(room)

    def set_express_pin(self, room: int, pin: bool | None) -> None:
        """Pin one room's latency tier: True = express lane, False =
        batched tick, None = automatic (subscriber-count eligibility).
        No-op when the express lane is disabled. Takes effect at the
        next tick boundary (re-tier runs with staging)."""
        if self.express is not None:
            self.express.set_pin(room, pin)

    def set_shed(self, *, spatial_cap: int | None = None,
                 pause_video: bool | None = None) -> None:
        """Governor actuator: set the shed overlay. A change forces a
        full ctrl upload at the next tick edge — transitions are rare
        (ladder moves), so the O(R·T·S) copy is fine; the authoritative
        mirrors stay untouched."""
        changed = False
        if spatial_cap is not None and spatial_cap != self.shed_spatial_cap:
            self.shed_spatial_cap = int(spatial_cap)
            changed = True
        if pause_video is not None and pause_video != self.shed_pause_video:
            self.shed_pause_video = bool(pause_video)
            changed = True
        if changed:
            self._ctrl_dirty = True

    def _effective_ctrl(self) -> plane.SubControl:
        """The SubControl actually uploaded: desired caps with the shed
        overlay applied (spatial clamp; L3 mutes non-pinned video subs)
        and integrity-quarantined rooms fully muted. Reads only host
        mirrors — callable without the state lock."""
        cap = self.shed_spatial_cap
        quarantined = (
            self.integrity.quarantined if self.integrity is not None else None
        )
        if (
            cap >= plane.MAX_LAYERS - 1
            and not self.shed_pause_video
            and not quarantined
        ):
            return self.ctrl
        sub_muted = self.ctrl.sub_muted
        if self.shed_pause_video:
            vid = (self.meta.is_video & self.meta.published)[:, :, None]
            sub_muted = sub_muted | (vid & ~self.pinned)
        if quarantined:
            # Quarantine mutes the WHOLE flagged room row (its state is
            # suspect end to end); other rooms keep full audio + video.
            qmask = np.zeros_like(self.ctrl.sub_muted)
            qmask[sorted(quarantined)] = True
            sub_muted = sub_muted | qmask
        return plane.SubControl(
            subscribed=self.ctrl.subscribed,
            sub_muted=sub_muted,
            max_spatial=np.minimum(self.ctrl.max_spatial, cap),
            max_temporal=self.ctrl.max_temporal,
        )

    def clear_room(self, room: int) -> None:
        self.meta.published[room, :] = False
        self.meta.pub_muted[room, :] = False
        self.ctrl.subscribed[room, :, :] = False
        self.ingest.track_pub_sub[room, :] = -1
        self.ingest.fb_enabled[room, :] = False
        self.ingest.sub_reset[room, :] = True  # next tenant: fresh BWE state
        # Stale replay-ring entries must not survive row reuse: a new
        # room's NACK aliasing an old slot would retransmit the PREVIOUS
        # room's media bytes (cross-room leak).
        self.host_seq.clear_room(room)
        # Munger offsets likewise: the next tenant's streams must anchor
        # fresh, not continue a dead room's SN/TS spaces.
        self.munger.clear_room(room)
        if self.express is not None:
            # Tier state (pin, activation, selector mirror) must not leak
            # to the next tenant or past a migration snapshot.
            self.express.clear_room(room)
        self._dirty_rows.add(room)

    def on_tick(self, cb: Callable[[TickResult], Awaitable[None] | None]) -> None:
        self._on_tick.append(cb)

    def attach_rx(self, schedule) -> None:
        """Bind the transport's RxSchedule: it reads `serving` and
        `express` off this plane, `_run` signals it."""
        self.rx = schedule
        schedule.plane = self

    @property
    def serving(self) -> bool:
        """The serving loop (`_run`) is running, so ticks come at their
        edges by themselves (not `step_once`-driven)."""
        return self._task is not None and not self._task.done()

    # (The r4 egress-cap auto-widening machinery is gone: the bit-packed
    # mask egress has no capacity to overflow — every send is one bit.)

    # -- tick ------------------------------------------------------------
    def _upload_ctrl(self) -> None:
        """Ship pending host-mirror control mutations to the device.

        Dirty-row delta by default: the dirtied room rows go up as a
        stacked row-gather + `.at[rows].set(...)` scatter (O(dirty rows)
        bytes), so subscription churn in one room no longer costs an
        [R, T, S] host→HBM copy at north-star dims. Full `_replace`
        upload when the full flag is set (init/restore) or the dirty
        count crosses ctrl_delta_max_rows. No-op when clean."""
        import jax.numpy as jnp

        rows = self._dirty_rows
        if not self._ctrl_dirty and not rows:
            return
        if self._ctrl_dirty or len(rows) > self.ctrl_delta_max_rows:
            if self._mesh is None:
                put = jnp.asarray
            else:
                from livekit_server_tpu.parallel.mesh import room_sharding

                sharding = room_sharding(self._mesh)
                put = lambda x: jax.device_put(jnp.asarray(x), sharding)
            self.state = self.state._replace(
                meta=jax.tree.map(lambda x: put(x.copy()), plane.TrackMeta(*self.meta)),
                ctrl=jax.tree.map(
                    lambda x: put(x.copy()),
                    plane.SubControl(*self._effective_ctrl()),
                ),
            )
            self.stats["ctrl_full_uploads"] += 1
        else:
            # Pad the row count to a bucket so the scatter compiles once
            # per bucket (all of them in warm_compile), not once per count.
            pad_to = _row_bucket(
                len(rows), _row_buckets(self.ctrl_delta_max_rows)
            )
            r, meta_rows, ctrl_rows = plane.pack_ctrl_rows(
                self.meta, self._effective_ctrl(), rows, pad_to=pad_to
            )
            self.state = self._apply_delta(self.state, r, meta_rows, ctrl_rows)
            self.stats["ctrl_delta_uploads"] += 1
            self.stats["ctrl_delta_rows"] += len(rows)
            self.stats["ctrl_upload_bytes"] += meta_rows.nbytes + ctrl_rows.nbytes
        self._dirty_rows = set()
        self._ctrl_dirty = False

    def _tick_rec_extras(self, st: StagedTick) -> dict:
        """Subclass hook: extra fields for this tick's `recent_ticks`
        record (event loop, after the device step committed). The paged
        runtime adds the live pages and their fraction here."""
        return {}

    def _device_step(self, st: StagedTick):
        """The blocking device round trip; runs off the event loop.
        Inputs were pre-packed at stage time (non-mesh), so this thread's
        span is the device call alone — its wall time lands in
        `st.device_s`.

        Returns None (instead of outputs) when a supervisor restart
        abandoned this step mid-flight: the epoch check straddles the
        injected stall so a woken stale thread never consumes — or
        donates — state the restart already restored."""
        epoch = self.run_epoch
        span = self.spans.span
        with span(trace_mod.SP_DEVICE_CALL) as call:
            st.device_t0 = call.t0
            if self.fault is not None:
                self.fault.maybe_stall()
            if epoch != self.run_epoch:
                return None
            if self.fault is not None:
                self.fault.maybe_bitflip(self, st.idx)
            # The call's parts are read off their boundaries, so they
            # add up to it: a wait for the interpreter lock between two
            # of them counts to the later one. dispatch: until the jitted
            # call returns (asynchronous on a TPU).
            with span(trace_mod.SP_DEVICE_DISPATCH) as part:
                if self._mesh is not None:
                    state, out = self._step(self.state, st.inp)
                else:
                    state, buf = self._step(self.state, *st.packed)
            st.dispatch_s = part.t1 - call.t0
            # fetch: the wait for the device, the copy out and the commit.
            with span(trace_mod.SP_DEVICE_FETCH) as part:
                if self._mesh is not None:
                    # The mesh path's one per-tick drain: outputs land
                    # host-side here (the non-mesh path drains in
                    # _unpack_outputs instead).
                    out = jax.tree.map(np.asarray, out)  # graftcheck: disable=GC12
                else:
                    out = self._unpack_outputs(buf)
                if epoch != self.run_epoch:
                    return None  # restarted mid-step: result belongs to a dead run
                self.state = state
            done = part.t1
            st.fetch_s = done - call.t0 - st.dispatch_s
            if self.express is not None and self.express.wants_mirror():
                # Post-commit selector mirror for the express lane: fetched
                # here (same device sync as `out`), consumed at the next
                # retier on the event loop — decisions made from it are
                # bounded ≤1 tick stale.
                with span(trace_mod.SP_DEVICE_MIRROR) as part:
                    self.express.post_mirror(*self._sel_mirror(state))
                st.mirror_s = part.t1 - done
                done = part.t1
            if self.integrity is not None:
                # Audit the committed state on the cadence; the fetched mask
                # is a few dozen bytes riding the same device sync as `out`.
                with span(trace_mod.SP_DEVICE_AUDIT) as part:
                    self.integrity.maybe_audit(st.idx)
                st.audit_s = part.t1 - done
        st.device_s = call.dt
        return out

    def _stage_host(self) -> StagedTick:
        """Pipelined host staging: claim a tick index, drain the ingest
        buffer, pre-pack the device input arrays. Touches ONLY host-owned
        state (ingest staging sets, slab history) — never self.state — so
        it needs no lock and can overlap an in-flight device step. Probe
        scheduling happens later, at dispatch (_schedule_probe), where the
        freshest device mirrors are available."""
        with self.spans.span(trace_mod.SP_STAGE_HOST) as stage:
            idx = self.tick_index
            self.tick_index += 1
            # Close the quality/stats window about once per second
            # (connectionquality windows; room.go:1318 worker cadence).
            q_ticks = max(1, 1000 // self.tick_ms)
            roll = (idx + 1) % q_ticks == 0
            ex_rows = ex_words = ex_log = None
            retier_s = 0.0
            if self.express is not None:
                # Tier boundary, in the same synchronous event-loop slice
                # as the drain (atomic w.r.t. arrivals and migration
                # freezes): close the ending window, re-tier, and take
                # over the closing window for freshly promoted rooms.
                # Returns the rooms whose fast-path subscriber bits this
                # tick's fan-out must skip.
                with self.spans.span(trace_mod.SP_STAGE_RETIER) as retier:
                    ex_rows, ex_words, ex_log = self.express.tick_boundary(
                        self.ingest)
                retier_s = retier.dt
            inp, payloads = self.ingest.drain(
                roll_quality=roll, tick_index=idx,
                reuse_fields=(self._mesh is None),
            )
            # Retain the slab for the RTX window: replay keys minted this
            # tick reference slot (tick % SLAB_WINDOW) until it recycles.
            self._slab_history[idx % plane.SLAB_WINDOW] = payloads
            packed = None
            if self._mesh is None:
                # Pack here — NOT in the worker — so the drained staging
                # set's zero-copy field views are consumed before the set
                # recycles, and the packing memcpys overlap the previous
                # device step.
                packed = self._pack_inputs(inp)
            st = StagedTick(inp=inp, payloads=payloads, idx=idx, roll=roll,
                            packed=packed, express_rows=ex_rows,
                            express_words=ex_words, express_log=ex_log)
        st.stage_t0 = stage.t0
        st.retier_s = retier_s
        st.stage_s = stage.dt
        return st

    def _schedule_probe(self, st: StagedTick) -> None:
        """Probe scheduling (probe_controller.go) for `st`, at dispatch
        time: padding rides the first live video track each subscriber is
        actually SUBSCRIBED to (its munger lane must be started for
        padding_tick to emit anything); results return as estimate
        samples. Runs against the latest device mirrors (one tick stale,
        same as the pre-split staging) and the tick's own drained
        estimate snapshot. pad_num/pad_track are host-only fields — the
        device never reads them — so injecting them after pre-pack is
        sound; they feed _assemble_padding at fan-out."""
        vid = self.meta.is_video & self.meta.published & ~self.meta.pub_muted
        cand = vid[:, :, None] & self.ctrl.subscribed          # [R, T, S]
        pad_track = np.where(
            cand.any(axis=1), cand.argmax(axis=1), -1
        ).astype(np.int32)                                     # [R, S]
        pad_num = self.prober.update(
            now_ms=st.idx * self.tick_ms,
            committed=self._last_committed,
            congested=self._last_congested,
            deficient=self._last_deficient,
            estimate=np.asarray(st.inp.estimate),
            estimate_valid=np.asarray(st.inp.estimate_valid),
            pad_track=pad_track,
        )
        if self.ingest.frozen_rows:
            # Probe padding also advances munger SN lanes; a row mid-
            # migration must stay byte-for-byte at its snapshot.
            pad_num[list(self.ingest.frozen_rows)] = 0
        st.inp = st.inp._replace(
            pad_num=np.asarray(pad_num, np.int32),
            pad_track=np.asarray(pad_track, np.int32),
        )

    def _mirror_probe_inputs(self, out) -> None:
        """Probe-controller inputs for the NEXT stage; must land as soon
        as the device step resolves (a congested flag one tick stale
        already delays padding shutdown; two would be worse)."""
        self._last_committed = np.asarray(out.committed_bps)
        self._last_congested = np.asarray(out.congested)
        self._last_deficient = np.asarray(out.deficient)

    async def _complete(self, out, st: StagedTick) -> TickResult:
        """Host post-step: fan out + callbacks. Per-stage work times
        (stage/device/fan-out) sum into tick_s — the deferred fan-out
        never bills the scheduler sleep between windows as work — and
        lateness is judged against the OWNING tick's deadline (dispatch
        edge + (1 + depth) periods), checked after the delivery callbacks
        have actually run."""
        with self.spans.span(trace_mod.SP_FANOUT_ASSEMBLE) as assemble:
            result = self._fan_out(
                out, st.payloads, st.inp, 0.0, st.idx,
                express=(st.express_rows, st.express_words, st.express_log),
            )
        c0, fanout_s = assemble.t0, assemble.dt
        # Attribution stamps for the wire-latency stage decomposer: the
        # egress consumer (udp.send_egress_batch's do_send — possibly on
        # a pacer thread) reads these off the batch, so they must land
        # before the callbacks run.
        result.egress_batch.t_dispatch = st.device_t0
        result.egress_batch.t_device_end = st.device_t0 + st.device_s
        result.tick_s = st.stage_s + st.device_s + fanout_s
        result.quality_window_closed = st.roll
        self.stats["ticks"] += 1
        if not st.depth:
            self.stats["depth0_ticks"] += 1
        self.stats["fwd_packets"] += result.fwd_packets
        self.stats["fwd_bytes"] += result.fwd_bytes
        self.stats["stage_s"] += st.stage_s
        self.stats["device_s"] += st.device_s
        self.stats["fanout_s"] += fanout_s
        # The callbacks may await (a stamp pair, no annotation: one held
        # across an await would take in other coroutines' spans).
        s0 = time.perf_counter()
        for cb in self._on_tick:
            r = cb(result)
            if asyncio.iscoroutine(r):
                await r
        send_s = time.perf_counter() - s0
        # Egress leaves inside the callbacks (wire tx), so the deadline
        # check runs after them: a tick is late when its sends left after
        # the end of the window its pipeline depth entitles it to.
        late = bool(st.deadline) and time.perf_counter() > st.deadline
        if late:
            self.stats["late_ticks"] += 1
        tick_rec = {
            "idx": st.idx, "depth": st.depth,
            "stage_ms": round(st.stage_s * 1000.0, 3),
            "device_ms": round(st.device_s * 1000.0, 3),
            "fanout_ms": round(fanout_s * 1000.0, 3),
            "total_ms": round(result.tick_s * 1000.0, 3),
            # What the tick asks of its window (`_run`'s docstring): the
            # device step overlaps the event loop's staging and fan-out
            # when pipelined, and follows them when not.
            "work_ms": round(1000.0 * (
                max(st.device_s, st.stage_s + fanout_s) if st.depth
                else result.tick_s), 3),
            "late": late,
            "edge_overshoot_us": round(st.edge_over_us, 1),
            # Where the tick waited, and the parts of the stages above:
            # asleep to the edge (the rx handlers run in it), edge to the
            # worker thread's first statement (lock_wait and upload lie
            # in it), the device call's dispatch and fetch, the finished
            # step's wait for the event loop, then for its deferred
            # fan-out (a tick deep by design), and the send callbacks.
            "sleep_ms": round(st.sleep_s * 1000.0, 3),
            "dispatch_delay_ms": round(
                trace_mod.between(st.edge, st.device_t0) * 1000.0, 3),
            "lock_wait_ms": round(st.lock_s * 1000.0, 3),
            "upload_ms": round(st.upload_s * 1000.0, 3),
            "device_dispatch_ms": round(st.dispatch_s * 1000.0, 3),
            "device_fetch_ms": round(st.fetch_s * 1000.0, 3),
            "handoff_ms": round(st.handoff_s * 1000.0, 3),
            "egress_wait_ms": round(trace_mod.between(
                st.device_t0 + st.device_s, c0) * 1000.0, 3),
            "send_ms": round(send_s * 1000.0, 3),
        }
        # Per-shard egress timing: the send callbacks above just ran, so
        # the plane's last-send snapshot is THIS tick's (munge likewise).
        ep = self.egress_plane
        if ep.last_munge:
            tick_rec["munge_shard_ms"] = ep.last_munge.get("ms")
        if ep.last_send:
            tick_rec["egress_shard_ms"] = [
                s["ms"] for s in ep.last_send.get("shards", [])
            ]
        tick_rec.update(self._tick_rec_extras(st))
        self.recent_ticks.append(tick_rec)
        if self.trace is not None:
            # Trace ring: scalar stores into preallocated columns only
            # (GC07 — no allocation on the hot path).
            slot = self.trace.record_tick(
                st.idx, st.edge, st.stage_t0, st.stage_s, st.retier_s,
                st.upload_t0, st.upload_s, st.device_t0, st.device_s,
                c0, fanout_s, send_s, st.edge_over_us, st.depth, late,
                sleep_t0=st.sleep_t0, sleep_s=st.sleep_s,
                lock_t0=st.lock_t0, lock_s=st.lock_s,
                dispatch_s=st.dispatch_s,
                fetch_s=st.fetch_s, mirror_s=st.mirror_s,
                audit_s=st.audit_s, handoff_s=st.handoff_s,
            )
            if ep.last_send:
                shards = ep.last_send.get("shards", ())
                munge_ms = ep.last_munge.get("ms", ()) if ep.last_munge else ()
                for i in range(len(shards)):
                    self.trace.set_shard(
                        slot, i,
                        munge_ms[i] if i < len(munge_ms) else 0.0,
                        shards[i]["ms"],
                    )
        # Tick-edge calibration gauges (telemetry scrapes these).
        self.stats["sleep_bias_us"] = round(max(self._sleep_bias, 0.0) * 1e6, 1)
        self.stats["edge_overshoot_us"] = round(self._edge_overshoot_us, 1)
        if self.governor is not None:
            # Close the overload loop on the finished tick's verdict.
            self.governor.on_tick(self.recent_ticks[-1])
        return result

    def mark_warm(self) -> None:
        """Close the warmup window: XLA compiles after this are
        steady-state recompiles the watchdog reports (and the seeded
        drills fail on). Call after the warm step(s) have run."""
        self.compile_ledger.mark_warm()

    async def step_once(self) -> TickResult:
        """One sequential tick (tests, warmup, manual stepping); the device
        round trip runs in a worker thread so the event loop (signal
        sessions) never blocks on the device round trip. This is depth 0,
        the serving loop's (`_run`) normal order, without its edges; the
        loop also pipelines (depth 1) while it runs behind.

        step_once must NOT interleave with a RUNNING serving loop: the
        device steps serialize safely under state_lock, but this path's
        immediate fan-out can land before the loop's deferred fan-out of
        an EARLIER tick, which then rewrites munger lanes backwards
        (last-writer-wins) and emits egress out of wire order — hence the
        hard RuntimeError below instead of a docstring plea."""
        if self._task is not None and not self._task.done():
            raise RuntimeError(
                "step_once() while the serving loop is running: its "
                "immediate fan-out would land ahead of the loop's deferred "
                "fan-out of an earlier tick and rewrite munger lanes "
                "backwards (out-of-wire-order egress). Stop the loop first "
                "or consume ticks via on_tick()."
            )
        loop = asyncio.get_running_loop()
        # Staging reads only host mirrors — no lock needed. The ctrl
        # upload and the device step touch (and donate) self.state, so
        # they run under the lock: a concurrent snapshot/restore (room
        # migration) must never observe donated-and-deleted buffers.
        st = self._stage_host()
        self._schedule_probe(st)
        st.lock_t0 = time.perf_counter()
        async with self.state_lock:
            with self.spans.span(trace_mod.SP_CTRL_UPLOAD) as upload:
                self._upload_ctrl()
            st.lock_s = upload.t0 - st.lock_t0
            st.upload_t0, st.upload_s = upload.t0, upload.dt
            out = await loop.run_in_executor(self._executor, self._device_step, st)
            st.handoff_s = trace_mod.between(
                st.device_t0 + st.device_s, time.perf_counter())
        if out is None:
            raise asyncio.CancelledError("device step abandoned by restart")
        self._mirror_probe_inputs(out)
        self.ingest.scrub_retired()
        result = await self._complete(out, st)
        if self.integrity is not None:
            # Sequential path: repair right after the tick that audited.
            await self.integrity.process()
        return result

    def resolve_nacks(self, room: int, sub: int, track: int, sns) -> list[EgressPacket]:
        """NACKed munged SNs → replay EgressPackets, at RTCP time (the
        resolve half of sequencer.go:263 getExtPacketMetas; cold path —
        loss events only, so per-packet objects are fine here).

        Misses (evicted slot, wrong track, slab recycled) return nothing —
        the client re-NACKs. A hit within one RTT of its last replay is
        throttled."""
        hs = self.host_seq
        now_ms = int(time.monotonic() * 1000)
        if now_ms - int(hs._budget_refill_ms[room, sub]) >= 1000:
            hs.budget[room, sub] = hs.BUDGET_PER_S
            hs._budget_refill_ms[room, sub] = now_ms
        rtt = max(1, int(self.ingest.rtt_ms[room, sub]))
        K = self.dims.pkts
        budget_before = int(hs.budget[room, sub])
        replays: list[EgressPacket] = []
        for sn in sns:
            if len(replays) >= hs.BURST_CAP or hs.budget[room, sub] <= 0:
                break  # amplification bound; the client re-NACKs what's left
            sn &= 0xFFFF
            slot = sn & (hs.RING - 1)
            if int(hs.sn[room, sub, slot]) != sn:
                continue
            if int(hs.track[room, sub, slot]) != track:
                continue
            # Age gate: the slab slot recycles after SLAB_WINDOW ticks.
            if self.tick_index - int(hs.at_tick[room, sub, slot]) > plane.SLAB_WINDOW - 2:
                continue
            if now_ms - int(hs.last_ms[room, sub, slot]) < rtt:
                continue  # RTT replay throttle
            w, tk = divmod(int(hs.key[room, sub, slot]), hs._tk)
            t, k = divmod(tk, K)
            slab = self._slab_history[w]
            if slab is None:
                continue
            payload, marker = slab.get(room, t, k)
            if not payload:
                continue
            hs.last_ms[room, sub, slot] = now_ms
            hs.budget[room, sub] -= 1
            replays.append(
                EgressPacket(
                    room=room, track=t, sub=sub,
                    sn=sn,
                    ts=int(hs.ts[room, sub, slot]) & 0xFFFFFFFF,
                    pid=int(hs.pid[room, sub, slot]),
                    tl0=int(hs.tl0[room, sub, slot]),
                    keyidx=int(hs.keyidx[room, sub, slot]),
                    size=len(payload), payload=payload, marker=marker,
                    dd=slab.get_dd(room, t, k),
                )
            )
        if replays:
            self.stats["rtx_packets"] = self.stats.get("rtx_packets", 0) + len(replays)
        if budget_before > 0 and int(hs.budget[room, sub]) <= 0:
            # Replay budget newly exhausted: a NACK storm on this
            # (room, sub) pair. Cold path (loss events only) — black-box
            # the event and dump the room's recorder for the post-mortem.
            from livekit_server_tpu.runtime.trace import EV_NACK_STORM

            self.blackbox.emit(room, EV_NACK_STORM, float(sub), float(len(sns)))
            self.blackbox.dump_to(room, "nack_storm")
        return replays

    def _assemble_padding(self, inp) -> list[EgressPacket]:
        """Probe padding synthesis (the host half of WritePaddingRTP;
        cold path — probing windows only). Advances the host munger's SN
        lanes after this tick's real sends, exactly like the former
        device-side rtpmunger.padding_tick."""
        pads = self.munger.padding(
            inp.pad_num, inp.pad_track, ts_advance=self.tick_ms * 90
        )
        return [
            EgressPacket(
                room=r, track=t, sub=s, sn=sn, ts=ts,
                pid=0, tl0=0, keyidx=0,
                size=PAD_BYTES, payload=b"", padding=True,
            )
            for (r, t, s, sn, ts) in pads
        ]

    def _fan_out(self, out, payloads, inp, tick_s: float, tick_idx: int | None = None,
                 express: tuple | None = None) -> TickResult:
        # Bit-packed egress masks → host munge (runtime/munge.py) →
        # column arrays. The device ships one bit per (track, pkt, sub)
        # send; the SN/TS/VP8 value rewrites run here with host-owned
        # offset state (the rewrite half of DownTrack.WriteRTP,
        # rtpmunger.go + codecmunger/vp8.go) — via the native C++ walker
        # when built, numpy otherwise.
        send_bits, drop_bits, switch_bits = (
            out.send_bits, out.drop_bits, out.switch_bits,
        )
        if self.integrity is not None and self.integrity.quarantined:
            # Same-tick quarantine: a room flagged by THIS tick's audit
            # must not fan out its (suspect) sends even once — the ctrl
            # mute only lands at the next upload edge. Zeroing the row's
            # egress bits also freezes its munger lanes at their last
            # good values, exactly like a migration freeze.
            rows = [
                r for r in self.integrity.quarantined
                if r < send_bits.shape[0]
            ]
            if rows:
                send_bits = np.array(send_bits)
                drop_bits = np.array(drop_bits)
                switch_bits = np.array(switch_bits)
                send_bits[rows] = 0
                drop_bits[rows] = 0
                switch_bits[rows] = 0
        ex_rows = ex_words = ex_log = None
        if express is not None:
            ex_rows, ex_words, ex_log = express
        if ex_rows is not None and len(ex_rows):
            # Express-handled rooms: their fast-path subscribers were
            # served (and their munger lanes advanced) on arrival during
            # this tick's window — clear exactly those subscriber bits so
            # the batched walk neither re-sends nor re-advances them.
            # WS/TCP/RED subscribers of the same rooms keep their bits.
            send_bits = np.array(send_bits)
            drop_bits = np.array(drop_bits)
            switch_bits = np.array(switch_bits)
            clear = ~ex_words[:, None, None, :]
            send_bits[ex_rows] &= clear
            drop_bits[ex_rows] &= clear
            switch_bits[ex_rows] &= clear
        rr, tt, kk, ss, b_sn, b_ts, b_pid, b_tl0, b_ki = (
            self.munger.apply_columns(
                inp.sn, inp.ts, inp.ts_jump, inp.pid, inp.tl0, inp.keyidx,
                inp.begin_pic, inp.valid,
                send_bits, drop_bits, switch_bits,
                shard_plan=self._munge_shard_plan,
            )
        )
        if len(self.munger.last_shard_ns):
            self.egress_plane.record_munge(
                self.munger.last_shard_counts, self.munger.last_shard_ns
            )
            self.munger.last_shard_ns = self.munger.last_shard_ns[:0]
        batch = EgressBatch(
            rooms=rr, tracks=tt, ks=kk, subs=ss,
            sn=b_sn, ts=b_ts, pid=b_pid, tl0=b_tl0, keyidx=b_ki,
            payloads=payloads,
        )
        speakers: dict[int, list[tuple[int, float]]] = {}
        lv, tr = out.speaker_levels, out.speaker_tracks
        for r in range(lv.shape[0]):
            row = [
                (int(tr[r, i]), float(lv[r, i]))
                for i in range(lv.shape[1])
                if tr[r, i] >= 0 and lv[r, i] > 0
            ]
            if row:
                speakers[r] = row
        nk = [
            (int(r), int(t), int(s))
            for r, t, s in zip(*np.nonzero(out.need_keyframe))
        ]
        congested: dict[int, list[int]] = {}
        for r, s in zip(*np.nonzero(out.congested)):
            congested.setdefault(int(r), []).append(int(s))
        # Feed the host replay ring from this tick's sends (the push half
        # of the sequencer, now host-side — NACKs resolve at RTCP time).
        eff_idx = self.tick_index if tick_idx is None else tick_idx
        self.host_seq.record(batch, eff_idx)
        if ex_log is not None and len(ex_log):
            # Express sends of this window, recorded against the SAME
            # slab now that it is retained in _slab_history. The drain's
            # reorder pass can permute staging slots within a (room,
            # track) after the log was written, so entries whose slot no
            # longer holds their wire SN are dropped — a replay miss the
            # client re-NACKs, never a wrong payload.
            T, K = self.dims.tracks, self.dims.pkts
            lflat = (
                ex_log.rooms.astype(np.int64) * T + ex_log.tracks
            ) * K + ex_log.ks
            ok = (
                np.asarray(inp.sn).reshape(-1)[lflat] & 0xFFFF
            ) == ex_log.orig_sn
            if not ok.all():
                if self.express is not None:
                    self.express.stats["replay_drops"] += int((~ok).sum())
                ex_log = ex_log.take(ok)
            self.host_seq.record(ex_log, eff_idx)
        padding = self._assemble_padding(inp)
        if padding:
            self.stats["pad_packets"] = self.stats.get("pad_packets", 0) + len(padding)
        return TickResult(
            tick_index=self.tick_index if tick_idx is None else tick_idx,
            egress_batch=batch,
            padding=padding,
            speakers=speakers,
            need_keyframe=nk,
            congested=congested,
            # `out` is post-drain host numpy by the time _fan_out runs
            # (materialized in _device_step), so these casts are host
            # no-ops the device-name heuristic cannot see through.
            fwd_packets=int(out.fwd_packets.sum()),  # graftcheck: disable=GC12
            fwd_bytes=int(out.fwd_bytes.sum()),  # graftcheck: disable=GC12
            tick_s=tick_s,
            track_quality=out.track_quality,
            track_mos=out.track_mos,
            sub_quality=out.sub_quality,
            layer_live=out.layer_live,
            layer_fps=out.layer_fps,
            track_loss_pct=out.track_loss_pct,
            track_jitter_ms=out.track_jitter_ms,
            track_bps=out.track_bps,
            red_sn=out.red_sn,
            red_off=out.red_off,
            red_ok=out.red_ok,
            pacer_allowed=out.pacer_allowed,
            target_layers=out.target_layers,
        )

    # -- loop ------------------------------------------------------------
    def start(self) -> None:
        if self._task is None:
            self.egress_plane.warm()  # spawn shard workers off the hot path
            self._task = asyncio.ensure_future(self._run())

    async def _calibrate_sleep(self) -> None:
        """Measure this host's asyncio coarse-sleep overshoot once at
        loop start: epoll timer slop + event-loop lag, typically
        0.3-2 ms, previously approximated by a fixed 1.5 ms margin. The
        median of a short burst (plus a small spin cushion) becomes the
        pre-edge margin _sleep_until subtracts before its yield-spin
        tail — a low-slop host stops burning 1.5 ms of spin per tick,
        and a high-slop host stops self-inflicting lateness at tick 2."""
        if self._sleep_bias >= 0:
            return
        samples = []
        for _ in range(8):
            t0 = time.perf_counter()
            await asyncio.sleep(0.001)
            samples.append(time.perf_counter() - t0 - 0.001)
        self._sleep_bias = min(max(float(np.median(samples)) + 2e-4, 3e-4), 4e-3)

    async def _sleep_until(self, when: float) -> None:
        """Window-edge sleep: coarse asyncio.sleep to just short of the
        edge, then a yield loop for the tail. An epoll-backed sleep
        overshoots by the event-loop lag (hundreds of µs under rx load)
        — at a 5 ms tick that alone costs 5-10% of the cadence. The
        sleep(0) tail lands the dispatch within ~50 µs of the edge and
        lets the loop's other handlers run (the receive path's reads
        among them, as far as their own pacing allows: RxSchedule); the
        spin is bounded by the calibrated margin and only burns the
        window's idle slack.
        The wake overshoot is recorded (edge_overshoot_us per tick in
        recent_ticks) and a coarse sleep that blows THROUGH the edge
        widens the margin for the next windows (EWMA, capped)."""
        bias = self._sleep_bias if self._sleep_bias >= 0 else 0.0015
        delay = when - time.perf_counter() - bias
        if delay > 0:
            await asyncio.sleep(delay)
        while time.perf_counter() < when:
            await asyncio.sleep(0)
        over = time.perf_counter() - when
        self._edge_overshoot_us = over * 1e6
        if over > 2.5e-4 and self._sleep_bias >= 0:
            self._sleep_bias = min(self._sleep_bias + 0.25 * over, 4e-3)

    @staticmethod
    def choose_depth(chain_s: float, lag_s: float, slack_s: float, period: float,
                     depth: int, stay: int, retry: int) -> tuple[int, int]:
        """The pipeline's depth for the tick about to be dispatched, and
        the ticks to stay pipelined before depth 0 is tried again: a pure
        function, called once a tick by `_run`.

        `chain_s` is the running estimate of what a tick asks of its
        window at depth 0: stage, device call, fan-out, send and what
        `rx` and the other handlers took between them (`_run` measures
        it whole, from the wake at the edge until it sleeps again, at
        depth 0 only: pipelined, nothing the loop can measure says what
        the device call would cost beside `rx`). `lag_s` is how far
        behind its edge this dispatch runs, `slack_s` how long the loop
        slept before it, `stay` the ticks spent at `depth` so far.

        At depth 0 a tick has to end before the next edge: once chain +
        lag passes DEPTH_ENTER_SHARE of the period the loop pipelines,
        which takes the device call and the host's work off each other's
        path and wins the lag back sooner. If the chain fits and only
        the lag does not (a hold, such as the supervisor's checkpoint),
        it returns as soon as the lag is gone. If the chain itself did
        not fit, on a stay shorter than RETRY_TICKS, depth 0 was a try
        that failed: it is tried again after `retry` ticks, twice as
        many a failure, and only from a tick that slept DEPTH_TRY_SLACK
        of the period (a try puts the device call on the loop's path: at
        a node's knee there is no room for it, and a failed try is a
        small hold of its own).

        Tests that need a fixed depth replace this on the instance."""
        if depth:
            back = stay >= retry and lag_s < DEPTH_LAG_GONE * period and (
                not retry or slack_s > DEPTH_TRY_SLACK * period)
            return int(not back), retry
        if chain_s + max(lag_s, 0.0) <= DEPTH_ENTER_SHARE * period:
            return 0, retry
        failed = chain_s > DEPTH_FIT_SHARE * period and stay < RETRY_TICKS
        return 1, min(max(2 * retry, RETRY_TICKS), RETRY_MAX_TICKS) if failed else 0

    async def _run(self) -> None:
        """The serving loop; each tick runs at the depth `choose_depth`
        gives it.

        Depth 0, the normal order: at the window edge the loop drains and
        stages the tick, uploads the (delta) ctrl and dispatches the
        device step to the worker thread; when the step returns it fans
        out and sends that tick, then sleeps to the next edge.

        Depth 1, the catch-up: stage N+1  ‖  device N  ‖  fan-out N-1.
        Tick N — staged during the PREVIOUS window — is dispatched at the
        edge; while the device crunches, the event loop stages tick N+1
        (into the other ingest ping-pong set) and runs tick N-1's fan-out
        + egress. A tick's wall budget is max(device, stage + fan-out) +
        dispatch ε instead of their sum; a packet waits two windows more.

        A change of depth keeps the wire order. 1 → 0: the deferred
        fan-out of the tick before starts after this dispatch, as ever,
        and is awaited before this tick's own; the tick pre-staged for
        this edge is used as it is. 0 → 1: the loop pre-stages after this
        dispatch and holds this tick's fan-out, nothing else.

        Who reads the socket when: nobody from the wake at the edge
        (`woke`) until the loop goes to sleep again (`sleep_t0`), the
        window `chain_s` measures. `self.rx` (udp.RxSchedule) takes the
        reader off the selector at `chain_begin`, after reading what an
        unfinished pause of its own had left readable, so that a packet
        in the socket before the edge is in this edge's tick; at
        `chain_end` it drains what the chain let gather (that read
        counts in `chain_s`, as everything before the sleep does) and
        paces its reads through the sleep by their own cost. So no
        `rx` handler runs beside the awaited device call, between the
        send callbacks or beside the pre-staging at depth 1.

        The completion queue is bounded at 1: if host egress can't keep
        up, the loop degrades to sequential (counted in pipeline_stalls)
        instead of queueing stale sends, and a stalled device future
        simply holds the loop at `await fut` — no new tick is staged past
        the one already prepared, so depth is bounded by construction.

        self.state stays single-owner: only the ctrl upload + dispatched
        device step touch the donated state, and exactly that span runs
        under state_lock; staging reads host mirrors only (the GC01
        split)."""
        period = self.tick_ms / 1000.0
        await self._calibrate_sleep()
        next_at = time.perf_counter() + period
        loop = asyncio.get_running_loop()
        pending: tuple | None = None   # (out, StagedTick) awaiting fan-out
        pending_task: asyncio.Task | None = None
        staged: StagedTick | None = None  # pre-staged next tick
        depth = stay = retry = 0       # `choose_depth`'s state
        chain_s = woke = 0.0           # its running estimate; the last wake
        try:
            while True:
                if staged is not None:
                    # Edge surgery: probe scheduling for a pre-staged tick
                    # happens BEFORE the sleep — no device step completes
                    # while the loop sleeps, so the mirrors
                    # _schedule_probe reads cannot change — leaving the
                    # post-wake path dispatch-only.
                    self._schedule_probe(staged)
                if self.rx is not None:
                    self.rx.chain_end()
                sleep_t0 = time.perf_counter()
                if woke and not depth:
                    # What the last tick asked of its window; a sample
                    # past the period says "does not fit" as well as any
                    # larger one (a hold, a first tick that compiles).
                    chain_s += (min(sleep_t0 - woke, period) - chain_s) / CHAIN_TICKS
                await self._sleep_until(next_at)
                sleep_s = time.perf_counter() - sleep_t0
                if self.integrity is not None and self.integrity._pending_repair:
                    # Drain the row-repair queue filled by the last audit,
                    # at the window edge and OUTSIDE the lock region below:
                    # each repair takes state_lock itself, and the repaired
                    # row's dirtied ctrl re-uploads in this very tick.
                    # (Guarded: the empty-queue case stays off the wake
                    # path.)
                    await self.integrity.process()
                if pending_task is not None:
                    # Backpressure: previous fan-out still running ⇒ wait
                    # (sequential under overload; no unbounded queue).
                    if not pending_task.done():
                        self.stats["pipeline_stalls"] += 1
                    await pending_task
                    pending_task = self._complete_task = None
                woke = time.perf_counter()
                if self.rx is not None:
                    self.rx.chain_begin()
                want, retry = self.choose_depth(
                    chain_s, woke - next_at, sleep_s, period, depth, stay, retry)
                if want != depth:
                    depth, stay = want, 0
                    if not depth:
                        # A try starts from a chain that fits, and learns.
                        chain_s = min(chain_s, DEPTH_FIT_SHARE * period)
                stay += 1
                if staged is None:
                    # Depth 0, cold start or post-resync: stage at the
                    # window edge, the freshest possible drain.
                    staged = self._stage_host()
                    self._schedule_probe(staged)
                cur, staged = staged, None
                cur.depth = depth
                cur.edge = next_at
                cur.deadline = next_at + (1 + depth) * period
                cur.edge_over_us = self._edge_overshoot_us
                cur.sleep_t0, cur.sleep_s = sleep_t0, sleep_s
                if self.ingest.frozen_rows:
                    # A migration freeze can land during the sleep, after
                    # the pre-edge probe scheduling: re-zero frozen rows'
                    # probe padding at dispatch (pads advance munger
                    # lanes; a frozen row must stay at its snapshot).
                    np.asarray(cur.inp.pad_num)[list(self.ingest.frozen_rows)] = 0
                cur.lock_t0 = time.perf_counter()
                await self.state_lock.acquire()
                try:
                    with self.spans.span(trace_mod.SP_CTRL_UPLOAD) as upload:
                        self._upload_ctrl()
                    cur.lock_s = upload.t0 - cur.lock_t0
                    cur.upload_t0, cur.upload_s = upload.t0, upload.dt
                    fut = loop.run_in_executor(self._executor, self._device_step, cur)
                    if pending is not None:
                        pending_task = self._complete_task = asyncio.ensure_future(
                            self._complete(pending[0], pending[1])
                        )
                        pending = None
                    if depth:
                        # Stage N+1 while device N runs in the worker:
                        # the drain flips to the other ingest ping-pong
                        # set and the pre-pack memcpys overlap the device
                        # step. Staging touches host mirrors only; the
                        # lock we hold here guards the in-flight donated
                        # state, not this.
                        staged = self._stage_host()
                    # Fan-out N-1 (the task above) runs on the event loop
                    # during this await; the receive path does not (its
                    # reader is off for the chain).
                    out = await fut
                    cur.handoff_s = trace_mod.between(
                        cur.device_t0 + cur.device_s, time.perf_counter())
                finally:
                    self.state_lock.release()
                if out is None:
                    # Abandoned by a supervisor restart racing our cancel:
                    # bail to the drain handler without touching state.
                    raise asyncio.CancelledError("device step abandoned by restart")
                self._mirror_probe_inputs(out)
                self.ingest.scrub_retired()
                if depth:
                    pending = (out, cur)
                else:
                    # Fan out THIS tick's egress now: the sends leave
                    # within the same tick period, after the deferred
                    # fan-out of a tick before that ran at depth 1. The
                    # tick never becomes `pending`: a cancellation landing
                    # inside _complete must not let the drain handler
                    # re-run it (double egress + munger state advanced
                    # twice).
                    if pending_task is not None:
                        await pending_task
                        pending_task = self._complete_task = None
                    await self._complete(out, cur)
                next_at += period
                if next_at < time.perf_counter() - 5 * period:
                    next_at = time.perf_counter() + period  # resync after stall
        except asyncio.CancelledError:
            # Drain: the final tick's device step already ran — its egress,
            # callbacks, and stats must not silently vanish at shutdown.
            if pending_task is not None:
                await pending_task
                self._complete_task = None
            if pending is not None:
                await self._complete(pending[0], pending[1])
            raise
        finally:
            if self.rx is not None:
                self.rx.chain_end()    # the reader goes back on the selector

    async def stop(self) -> None:
        if self._task is not None:
            self._task.cancel()
            try:
                await self._task
            except asyncio.CancelledError:
                pass
            self._task = None
        if self._complete_task is not None:
            self._complete_task.cancel()
            try:
                await self._complete_task
            except asyncio.CancelledError:
                pass
            self._complete_task = None

    # -- checkpoint / resume (§5.4) --------------------------------------
    def snapshot(self) -> dict[str, Any]:
        """Serializable plane snapshot: device decision state + the
        host-side munger offsets (migration seeding analog)."""
        flat, treedef = jax.tree.flatten(self.state)
        return {
            "tick_index": self.tick_index,
            "arrays": [np.asarray(x) for x in flat],
            "munger": self.munger.snapshot(),
        }

    def snapshot_room(self, row: int) -> dict[str, Any]:
        """One room row's slice of the plane state — the cross-node room
        handoff payload (participant.go:823 MaybeStartMigration seeds the
        same per-forwarder state on the destination node).

        Control tensors come from the HOST mirrors (authoritative: they may
        hold un-uploaded mutations newer than the device copy); everything
        else slices on device first so only one row crosses HBM→host. The
        host munger's row (SN/TS/VP8 offsets — RTPMungerState seeding,
        rtpmunger.go:53-69) rides along after the device leaves."""
        tree = jax.tree.map(
            np.asarray, _build_row_read()(self.state, np.int32(row))
        )
        tree = tree._replace(
            meta=plane.TrackMeta(*[np.array(m[row]) for m in self.meta]),
            ctrl=plane.SubControl(*[np.array(c[row]) for c in self.ctrl]),
        )
        return {
            "arrays": jax.tree.flatten(tree)[0]
            + self.munger.snapshot_room(row)
        }

    @staticmethod
    def encode_room_snapshot(snap: dict[str, Any]) -> str:
        """Room snapshot → checksummed npz frame, base64 (rides the KV
        bus). The utils/checksum frame (GC06) lets every restore path
        verify the bytes before any `.at[]` scatter."""
        import io

        from livekit_server_tpu.utils import checksum

        buf = io.BytesIO()
        np.savez_compressed(buf, *snap["arrays"])
        return checksum.encode_frame_b64(buf.getvalue())

    @staticmethod
    def decode_room_snapshot(payload: str) -> dict[str, Any]:
        """Verify + decode a room checkpoint; raises ChecksumError on a
        corrupt frame BEFORE np.load touches the bytes."""
        import io

        from livekit_server_tpu.utils import checksum

        z = np.load(io.BytesIO(checksum.decode_frame_b64(payload)))
        # savez names leaves arr_0..arr_N; z.files sorts lexically (arr_10
        # before arr_2), so index numerically.
        return {"arrays": [z[f"arr_{i}"] for i in range(len(z.files))]}

    @staticmethod
    def encode_snapshot(snap: dict[str, Any]) -> bytes:
        """Full-plane snapshot → checksummed npz frame (the supervisor's
        checkpoint-generation format)."""
        import io

        from livekit_server_tpu.utils import checksum

        arrays = list(snap["arrays"]) + list(snap.get("munger", []))
        buf = io.BytesIO()
        np.savez_compressed(
            buf, *arrays,
            tick_index=np.int64(snap["tick_index"]),
            n_state=np.int64(len(snap["arrays"])),
        )
        return checksum.encode_frame(buf.getvalue())

    @staticmethod
    def decode_snapshot(blob: bytes) -> dict[str, Any]:
        """Verify + decode a full-plane checkpoint into the snapshot()
        dict shape; ChecksumError on corruption, ValueError/KeyError on a
        malformed archive."""
        import io

        from livekit_server_tpu.utils import checksum

        z = np.load(io.BytesIO(checksum.decode_frame(blob)))
        n_arrays = sum(1 for f in z.files if f.startswith("arr_"))
        n_state = int(z["n_state"])
        arrays = [z[f"arr_{i}"] for i in range(n_arrays)]
        return {
            "tick_index": int(z["tick_index"]),
            "arrays": arrays[:n_state],
            "munger": arrays[n_state:],
        }

    def _check_row_leaves(self, flat: list, arrays: list) -> None:
        """Validate a row snapshot's leaves against the LIVE plane spec
        (count, per-leaf row shape, dtype compatibility) before anything
        scatters into donated device state."""
        n_munger = len(HostMunger.FIELDS)
        if len(arrays) != len(flat) + n_munger:
            raise ValueError(
                f"snapshot has {len(arrays)} leaves, plane has "
                f"{len(flat)} + {n_munger} munger fields — "
                f"source/destination plane versions differ"
            )
        for i, (leaf, a) in enumerate(zip(flat, arrays)):
            a = np.asarray(a)
            want = tuple(leaf.shape[1:])
            if tuple(a.shape) != want:
                raise ValueError(
                    f"snapshot leaf {i} row shape {tuple(a.shape)} != "
                    f"plane row shape {want} — dims mismatch"
                )
            if not np.can_cast(a.dtype, np.dtype(leaf.dtype), casting="same_kind"):
                raise ValueError(
                    f"snapshot leaf {i} dtype {a.dtype} incompatible with "
                    f"plane dtype {np.dtype(leaf.dtype)}"
                )

    @staticmethod
    def row_snapshot_from_full(snap: dict[str, Any], row: int) -> dict[str, Any]:
        """Slice one room's row out of a FULL snapshot() dict, in the
        snapshot_room() wire shape (state leaves then munger fields) —
        how the integrity monitor turns the supervisor's last verified
        checkpoint into a row-repair payload."""
        return {
            "arrays": [np.asarray(a[row]) for a in snap["arrays"]]
            + [np.asarray(m[row]) for m in snap.get("munger", [])]
        }

    def _write_row(self, row: int, flat: list, treedef, dev_arrays: list) -> None:
        row_tree = jax.tree.unflatten(treedef, [
            np.asarray(a, leaf.dtype) for leaf, a in zip(flat, dev_arrays)
        ])
        self.state = self._row_write(self.state, np.int32(row), row_tree)

    def _warm_ctrl_delta(self, buckets):
        """Run the dirty-row control scatter once per row-count bucket,
        writing row 0's own values back; returns row 0 as host arrays."""
        row0 = jax.tree.map(
            np.asarray, _build_row_read()(self.state, np.int32(0))
        )
        meta0 = np.stack([np.asarray(m, np.int32) for m in row0.meta])
        ctrl0 = np.stack([np.asarray(c, np.int32) for c in row0.ctrl])
        for n in buckets:
            self.state = self._apply_delta(
                self.state, np.zeros(n, np.int32),
                np.repeat(meta0[:, None], n, axis=1),
                np.repeat(ctrl0[:, None], n, axis=1),
            )
        return row0

    def warm_compile(self) -> None:
        """Compile, inside the warm-up window, the programs whose first
        use would otherwise fall in steady state: the dirty-row control
        scatter at every row bucket `_upload_ctrl` can ask for
        (a join would compile one mid-session) and the row read / write
        of room handoff and integrity repair (a migration's first
        adoption would outlast its ACK timeout). Each runs on the live
        state with the values already there, so the state is unchanged.
        Callers hold state_lock (GC01)."""
        row0 = self._warm_ctrl_delta(_row_buckets(self.ctrl_delta_max_rows))
        flat, treedef = jax.tree.flatten(self.state)
        self._write_row(0, flat, treedef, jax.tree.leaves(row0))

    def repair_room_row(self, row: int, snap: dict[str, Any]) -> None:
        """Integrity row repair: overwrite ONE corrupt room row from a
        verified checkpoint, in place, without disturbing any other row.

        Unlike restore_room (cross-node migration), the HOST mirrors stay
        authoritative: this node's meta/ctrl were never suspect — only
        the device row was — so the row's current subscriptions survive
        and the dirty-row upload re-asserts them over the checkpoint's
        older device copy at the next tick edge. Callers hold state_lock
        (GC01)."""
        flat, treedef = jax.tree.flatten(self.state)
        self._check_row_leaves(flat, snap["arrays"])
        dev_arrays = snap["arrays"][: len(flat)]
        self.munger.restore_room(row, snap["arrays"][len(flat):])
        self._write_row(row, flat, treedef, dev_arrays)
        # The replay ring references pre-repair munger SN spaces; replaying
        # across the rewind would emit wrong-SN bytes. Clients re-NACK.
        self.host_seq.clear_room(row)
        self._dirty_rows.add(row)

    def restore_room(self, row: int, snap: dict[str, Any]) -> None:
        """Seed `row` from a snapshot taken on another node: munger/VP8
        offsets continue mid-stream, so migrated subscribers see
        contiguous SN/TS instead of a stream reset. The host-side replay
        ring is NOT carried: NACKs of pre-migration packets miss (the
        payload slab did not travel either) until the destination ring
        repopulates — clients simply re-request via PLI on a sustained
        gap, like the reference's post-migration behavior.

        Subscription masks are NOT carried over: the destination's slot
        allocator hands out sub columns fresh, and a restored subscribed
        bit on a column later given to a different participant would leak
        media to someone who never subscribed. Rejoining subscribers
        re-subscribe; their (track, sub) munger lanes resume intact."""
        # The destination row's replay ring starts empty (see docstring) —
        # and must not retain entries from whatever used the row before.
        self.host_seq.clear_room(row)
        flat, treedef = jax.tree.flatten(self.state)
        self._check_row_leaves(flat, snap["arrays"])
        dev_arrays = snap["arrays"][: len(flat)]
        self.munger.restore_room(row, snap["arrays"][len(flat):])
        self._write_row(row, flat, treedef, dev_arrays)
        # Mirror the migrated row's track metadata back to the host copies
        # (other rows' possibly-dirty host state stays untouched)…
        snap_tree = jax.tree.unflatten(treedef, dev_arrays)
        for host_arr, snap_arr in zip(self.meta, snap_tree.meta):
            host_arr[row] = snap_arr
        # …but clear the subscriber-facing control masks (see docstring);
        # the next ctrl upload clears them on device too.
        self.ctrl.subscribed[row] = False
        self.ctrl.sub_muted[row] = False
        self.ctrl.max_spatial[row] = plane.MAX_LAYERS - 1
        self.ctrl.max_temporal[row] = 3
        self._dirty_rows.add(row)
        if self.integrity is not None:
            # A legitimate row rewrite: drop quarantine history and
            # re-baseline the audit cursors (they rewound on purpose).
            self.integrity.on_row_restore(row)

    def restore(self, snap: dict[str, Any]) -> None:
        flat, treedef = jax.tree.flatten(self.state)
        arrays = snap.get("arrays")
        if arrays is None or len(arrays) != len(flat):
            raise ValueError(
                f"full snapshot has {0 if arrays is None else len(arrays)} "
                f"leaves, plane has {len(flat)} — snapshot/plane versions "
                "differ"
            )
        for i, (leaf, a) in enumerate(zip(flat, arrays)):
            a = np.asarray(a)
            if tuple(a.shape) != tuple(leaf.shape):
                raise ValueError(
                    f"full snapshot leaf {i} shape {tuple(a.shape)} != "
                    f"plane shape {tuple(leaf.shape)} — dims mismatch"
                )
            if not np.can_cast(a.dtype, np.dtype(leaf.dtype), casting="same_kind"):
                raise ValueError(
                    f"full snapshot leaf {i} dtype {a.dtype} incompatible "
                    f"with plane dtype {np.dtype(leaf.dtype)}"
                )
        self.state = jax.tree.unflatten(treedef, [a for a in snap["arrays"]])
        if self._mesh is not None:
            from livekit_server_tpu.parallel import shard_tree

            self.state = shard_tree(self.state, self._mesh)
        if "munger" in snap:
            self.munger.restore(snap["munger"])
        else:
            # A munger-less snapshot (pre-round-5 format, or a producer
            # that stripped host state) must not pair restored device
            # decisions with STALE SN/TS offsets — every lane would keep
            # rewriting against the wrong anchor. Reset so lanes anchor
            # fresh instead (a one-time stream reset, like a new room).
            self.munger = HostMunger(self.dims)
        self.tick_index = snap["tick_index"]
        self._ctrl_dirty = True
        if self.integrity is not None:
            self.integrity.on_full_restore()
