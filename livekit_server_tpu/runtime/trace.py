"""Flight-recorder tracing plane: tick spans, sampled wire-latency
attribution, and per-room black-box event rings.

Every diagnosis before it (late-tick causes, the egress wall, the
wire-p99 floor analysis) was reconstructed by hand from scattered
`recent_ticks` fields and bench printouts. This module turns that into a
standing capability with a hard overhead budget — everything on the
per-tick path is a handful of scalar stores into preallocated numpy
arrays (no dict/f-string/list construction; graftcheck GC07 enforces the
discipline at the call sites):

- **TickTraceRing** — one record per tick in a fixed ring: the dispatch
  edge, per-stage start/duration pairs (stage_host with its nested
  express retier, ctrl upload, device step, fan-out, egress send), wake
  overshoot, depth, lateness, and per-egress-shard munge/send walls.
  `telemetry/trace_export.py` renders the ring as Chrome/Perfetto
  trace-event JSON (/debug/trace?ticks=N, tools/trace).
- **LatencyAttribution** — a deterministic 1-in-K sample of egress
  packets (sampled on the munged SN, so the set is stable across runs)
  whose arrival stamp (`IngestBuffer.t_arr`) is decomposed at the wire
  into staging / device / egress stage latencies, plus the express
  tier's arrival→wire latency. Feeds `livekit_wire_latency_stage_ms`
  and the previously-unfed `livekit_forward_latency_ms` histograms.
- **BlackBox** — per-room ring of the last M lifecycle / governor /
  integrity / migration / express events, dumped to the log (and kept
  for /debug/blackbox/{room}) on quarantine, repair failure, supervisor
  restart, migration rollback, or a NACK storm — the post-mortem no
  longer depends on whatever counters happened to be scraped.
- **Spans** — the named host spans of the serving loop (`SPANS`), each
  with cumulative totals (`n`, `items`, `busy_s`, `max_s`) served at
  /debug/rooms. `Spans.span(id)` is the one helper: a
  `jax.profiler.TraceAnnotation("sfu/<name>")`, so that a profiler
  session holds the span beside the device's operations on the
  profiler's own clock, and a `perf_counter` stamp pair. Spans of a
  tick are totalled from the ring's record (`TickTraceRing.record_tick`)
  so totals and ring agree tick for tick; the checkpoint's spans
  total themselves on exit. A span whose ends lie on two
  threads, or that crosses an `await`, is a stamp pair only
  (`Spans.add`): a `TraceAnnotation` is a per-thread stack, and other
  coroutines' spans would interleave with one held across an `await`.
"""

from __future__ import annotations

import threading
import time
from typing import Any

import jax
import numpy as np

# Egress-shard lanes a tick record can hold (EgressPlane caps at 16).
MAX_SHARDS = 16

# -- black-box event codes -------------------------------------------------
# Scalar int codes so the hot-path emit is a pure store; names resolve at
# dump time only.
EV_ROOM_OPEN = 1        # a = room row
EV_ROOM_CLOSE = 2       # a = room row
EV_JOIN = 3             # a = participant count after join
EV_LEAVE = 4            # a = participant count after leave
EV_GOV_LEVEL = 10       # a = old level, b = new level
EV_QUARANTINE = 20      # a = tick index
EV_REPAIR_OK = 21       # a = tick index
EV_REPAIR_FAIL = 22     # a = repair failures total
EV_ESCALATE = 23        # node lane; a = escalations total
EV_RESTART = 30         # node lane; a = attempt number
EV_MIG_FREEZE = 40      # a = epoch
EV_MIG_COMMIT = 41      # a = epoch
EV_MIG_ABORT = 42       # a = epoch
EV_NACK_STORM = 50      # a = subscriber, b = NACKed SN count
EV_PROMOTE = 60         # express tier promotion
EV_DEMOTE = 61          # express tier demotion

EVENT_NAMES = {
    EV_ROOM_OPEN: "room_open", EV_ROOM_CLOSE: "room_close",
    EV_JOIN: "join", EV_LEAVE: "leave",
    EV_GOV_LEVEL: "governor_level",
    EV_QUARANTINE: "quarantine", EV_REPAIR_OK: "repair_ok",
    EV_REPAIR_FAIL: "repair_fail", EV_ESCALATE: "escalate",
    EV_RESTART: "restart",
    EV_MIG_FREEZE: "migration_freeze", EV_MIG_COMMIT: "migration_commit",
    EV_MIG_ABORT: "migration_abort",
    EV_NACK_STORM: "nack_storm",
    EV_PROMOTE: "express_promote", EV_DEMOTE: "express_demote",
}

# Wire-latency stages in attribution order. `staging` is arrival →
# device dispatch (slab wait + tick-queueing), `device` the step itself,
# `egress` device commit → kernel send (munge/assemble/seal/send plus the
# pipeline's deferred fan-out wait); `total` is their measured (not
# composed) arrival→wire sum and `express` the arrival-driven tier's
# whole path — kept separate so the batched tail never buries it.
STAGES = ("staging", "device", "egress", "total", "express")
_S_STAGING, _S_DEVICE, _S_EGRESS, _S_TOTAL, _S_EXPRESS = range(len(STAGES))


# -- host spans ---------------------------------------------------------------
# Names as they appear in a profiler trace (`sfu/<name>`) and under
# /debug/rooms `spans`. `/` and never `.`: the benchmark's readers split a
# path on dots. The spans of a tick come first (ids below N_TICK_SPANS):
# `record_tick` totals those, `Spans.span` totals the rest on exit.
SPANS = (
    "loop/sleep", "loop/dispatch_delay", "loop/lock_wait", "loop/handoff",
    "stage/host", "stage/retier", "ctrl/upload",
    "device/call", "device/dispatch", "device/fetch", "device/mirror",
    "device/audit",
    "egress/wait", "fanout/assemble", "egress/send",
    "rx",
    "supervisor/checkpoint", "supervisor/checkpoint/snapshot",
    "supervisor/checkpoint/encode", "supervisor/checkpoint/callback",
)
(SP_SLEEP, SP_DISPATCH_DELAY, SP_LOCK_WAIT, SP_HANDOFF,
 SP_STAGE_HOST, SP_STAGE_RETIER, SP_CTRL_UPLOAD,
 SP_DEVICE_CALL, SP_DEVICE_DISPATCH, SP_DEVICE_FETCH, SP_DEVICE_MIRROR,
 SP_DEVICE_AUDIT,
 SP_EGRESS_WAIT, SP_FANOUT_ASSEMBLE, SP_EGRESS_SEND,
 SP_RX,
 SP_CHECKPOINT, SP_CKPT_SNAPSHOT, SP_CKPT_ENCODE, SP_CKPT_CALLBACK,
 ) = range(len(SPANS))
N_TICK_SPANS = SP_RX
_ANNOTATIONS = tuple("sfu/" + name for name in SPANS)


def between(t_from: float, t_to: float) -> float:
    """Seconds from one stamp to a later one; 0 where either was not
    taken (0.0) or they lie the other way round (stamps of two threads)."""
    return t_to - t_from if t_to > t_from > 0.0 else 0.0


class _Span:
    """One use of `Spans.span`: `t0` on entry, `t1` and `dt` on exit
    (`perf_counter`), whether or not tracing is on — the tick record
    reads them either way."""

    __slots__ = ("_spans", "_sid", "_annotation", "t0", "t1", "dt")

    def __init__(self, spans: "Spans", sid: int):
        self._spans = spans
        self._sid = sid
        self._annotation = None
        self.t0 = self.t1 = self.dt = 0.0

    def __enter__(self) -> "_Span":
        if self._spans.enabled:
            # A C++ no-op unless a profiler session is on.
            self._annotation = jax.profiler.TraceAnnotation(
                _ANNOTATIONS[self._sid])
            self._annotation.__enter__()
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> None:
        self.t1 = time.perf_counter()
        self.dt = self.t1 - self.t0
        if self._annotation is not None:
            self._annotation.__exit__(*exc)
        if self._sid >= N_TICK_SPANS:
            self._spans.add(self._sid, self.dt)


class Spans:
    """Cumulative totals of the named host spans: preallocated lists,
    scalar stores only (the GC07 rule the ring keeps). Each span has one
    writer thread (`device/*` reach here through the ring's record, on
    the event loop), so no lock. `enabled` False (`trace.enabled:
    false`): no annotation, no totals, `snapshot()` empty."""

    def __init__(self, enabled: bool = True):
        self.enabled = bool(enabled)
        k = len(SPANS)
        self.n = [0] * k
        self.items = [0] * k
        self.busy_s = [0.0] * k
        self.max_s = [0.0] * k

    def span(self, sid: int) -> _Span:
        return _Span(self, sid)

    def add(self, sid: int, dt: float, items: int = 0) -> None:
        if not self.enabled:
            return
        self.n[sid] += 1
        self.items[sid] += items
        self.busy_s[sid] += dt
        if dt > self.max_s[sid]:
            self.max_s[sid] = dt

    def snapshot(self) -> dict[str, dict[str, float]]:
        """{name: {n, items, busy_s, max_ms}}, every span, so that a
        reader by difference finds its key at both ends of a window
        (cold path: /debug/rooms)."""
        if not self.enabled:
            return {}
        return {
            name: {"n": self.n[i], "items": self.items[i],
                   "busy_s": round(self.busy_s[i], 6),
                   "max_ms": round(self.max_s[i] * 1e3, 3)}
            for i, name in enumerate(SPANS)
        }


class TickTraceRing:
    """Fixed ring of per-tick span records, preallocated columns.

    Single writer (the event loop's `_complete`); `record_tick` and
    `set_shard` are scalar stores only — the GC07-checked bounded API.
    `record_tick` also feeds `spans` (the cumulative totals) from the
    same values, in this one place, so totals and ring agree tick for
    tick. `snapshot` (cold path: /debug/trace, tools/trace)
    materializes the newest records as dicts for the exporter."""

    def __init__(self, cap: int = 512, spans: Spans | None = None):
        cap = max(8, int(cap))
        self.cap = cap
        self.spans = spans
        self.idx = np.full(cap, -1, np.int64)
        self.edge = np.zeros(cap, np.float64)
        self.stage_t0 = np.zeros(cap, np.float64)
        self.stage_dur = np.zeros(cap, np.float64)
        self.retier_dur = np.zeros(cap, np.float64)
        self.upload_t0 = np.zeros(cap, np.float64)
        self.upload_dur = np.zeros(cap, np.float64)
        self.device_t0 = np.zeros(cap, np.float64)
        self.device_dur = np.zeros(cap, np.float64)
        self.fanout_t0 = np.zeros(cap, np.float64)
        self.fanout_dur = np.zeros(cap, np.float64)
        self.send_dur = np.zeros(cap, np.float64)
        # The loop's waits and the device call's parts (all 0 where the
        # path has none: step_once does not sleep, the stock tick has no
        # mirror). The device call's parts are laid end to end from
        # device_t0; the hand-off starts where the device span ends.
        self.sleep_t0 = np.zeros(cap, np.float64)
        self.sleep_dur = np.zeros(cap, np.float64)
        self.lock_t0 = np.zeros(cap, np.float64)
        self.lock_dur = np.zeros(cap, np.float64)
        self.dispatch_dur = np.zeros(cap, np.float64)
        self.fetch_dur = np.zeros(cap, np.float64)
        self.mirror_dur = np.zeros(cap, np.float64)
        self.audit_dur = np.zeros(cap, np.float64)
        self.handoff_dur = np.zeros(cap, np.float64)
        self.wake_over_us = np.zeros(cap, np.float32)
        self.depth = np.zeros(cap, np.int8)
        self.late = np.zeros(cap, np.int8)
        self.n_shards = np.zeros(cap, np.int8)
        self.shard_munge_ms = np.zeros((cap, MAX_SHARDS), np.float32)
        self.shard_send_ms = np.zeros((cap, MAX_SHARDS), np.float32)
        self._pos = 0
        self.recorded = 0

    def record_tick(self, idx: int, edge: float, stage_t0: float,
                    stage_s: float, retier_s: float, upload_t0: float,
                    upload_s: float, device_t0: float, device_s: float,
                    fanout_t0: float, fanout_s: float, send_s: float,
                    wake_over_us: float, depth: int, late: bool,
                    sleep_t0: float = 0.0, sleep_s: float = 0.0,
                    lock_t0: float = 0.0, lock_s: float = 0.0,
                    dispatch_s: float = 0.0, fetch_s: float = 0.0,
                    mirror_s: float = 0.0, audit_s: float = 0.0,
                    handoff_s: float = 0.0) -> int:
        slot = self._pos
        self.idx[slot] = idx
        self.edge[slot] = edge
        self.stage_t0[slot] = stage_t0
        self.stage_dur[slot] = stage_s
        self.retier_dur[slot] = retier_s
        self.upload_t0[slot] = upload_t0
        self.upload_dur[slot] = upload_s
        self.device_t0[slot] = device_t0
        self.device_dur[slot] = device_s
        self.fanout_t0[slot] = fanout_t0
        self.fanout_dur[slot] = fanout_s
        self.send_dur[slot] = send_s
        self.sleep_t0[slot] = sleep_t0
        self.sleep_dur[slot] = sleep_s
        self.lock_t0[slot] = lock_t0
        self.lock_dur[slot] = lock_s
        self.dispatch_dur[slot] = dispatch_s
        self.fetch_dur[slot] = fetch_s
        self.mirror_dur[slot] = mirror_s
        self.audit_dur[slot] = audit_s
        self.handoff_dur[slot] = handoff_s
        self.wake_over_us[slot] = wake_over_us
        self.depth[slot] = depth
        self.late[slot] = late
        self.n_shards[slot] = 0
        self._pos = (slot + 1) % self.cap
        self.recorded += 1
        sp = self.spans
        if sp is not None:
            # A span that did not run in this tick (duration 0) is not
            # counted; the two waits read off other stamps are.
            device_end = device_t0 + device_s
            sp.add(SP_STAGE_HOST, stage_s)
            sp.add(SP_CTRL_UPLOAD, upload_s)
            sp.add(SP_DEVICE_CALL, device_s)
            sp.add(SP_FANOUT_ASSEMBLE, fanout_s)
            sp.add(SP_EGRESS_SEND, send_s)
            sp.add(SP_EGRESS_WAIT, between(device_end, fanout_t0))
            if edge > 0.0:
                sp.add(SP_DISPATCH_DELAY, between(edge, device_t0))
            if sleep_s > 0.0:
                sp.add(SP_SLEEP, sleep_s)
            if lock_t0 > 0.0:
                sp.add(SP_LOCK_WAIT, lock_s)
            if retier_s > 0.0:
                sp.add(SP_STAGE_RETIER, retier_s)
            if dispatch_s > 0.0:
                sp.add(SP_DEVICE_DISPATCH, dispatch_s)
                sp.add(SP_DEVICE_FETCH, fetch_s)
                sp.add(SP_HANDOFF, handoff_s)
            if mirror_s > 0.0:
                sp.add(SP_DEVICE_MIRROR, mirror_s)
            if audit_s > 0.0:
                sp.add(SP_DEVICE_AUDIT, audit_s)
        return slot

    def set_shard(self, slot: int, lane: int, munge_ms: float,
                  send_ms: float) -> None:
        if lane >= MAX_SHARDS:
            return
        self.shard_munge_ms[slot, lane] = munge_ms
        self.shard_send_ms[slot, lane] = send_ms
        if lane + 1 > self.n_shards[slot]:
            self.n_shards[slot] = lane + 1

    def snapshot(self, n: int | None = None) -> list[dict[str, Any]]:
        """Newest `n` records (all when None), oldest first — cold path."""
        have = min(self.recorded, self.cap)
        take = have if n is None else max(0, min(int(n), have))
        out: list[dict[str, Any]] = []
        for i in range(take):
            slot = (self._pos - take + i) % self.cap
            if self.idx[slot] < 0:
                continue
            ns = int(self.n_shards[slot])
            out.append({
                "tick": int(self.idx[slot]),
                "edge": float(self.edge[slot]),
                "stage_t0": float(self.stage_t0[slot]),
                "stage_s": float(self.stage_dur[slot]),
                "retier_s": float(self.retier_dur[slot]),
                "upload_t0": float(self.upload_t0[slot]),
                "upload_s": float(self.upload_dur[slot]),
                "device_t0": float(self.device_t0[slot]),
                "device_s": float(self.device_dur[slot]),
                "fanout_t0": float(self.fanout_t0[slot]),
                "fanout_s": float(self.fanout_dur[slot]),
                "send_s": float(self.send_dur[slot]),
                "sleep_t0": float(self.sleep_t0[slot]),
                "sleep_s": float(self.sleep_dur[slot]),
                "lock_t0": float(self.lock_t0[slot]),
                "lock_s": float(self.lock_dur[slot]),
                "dispatch_s": float(self.dispatch_dur[slot]),
                "fetch_s": float(self.fetch_dur[slot]),
                "mirror_s": float(self.mirror_dur[slot]),
                "audit_s": float(self.audit_dur[slot]),
                "handoff_s": float(self.handoff_dur[slot]),
                "wake_over_us": float(self.wake_over_us[slot]),
                "depth": int(self.depth[slot]),
                "late": bool(self.late[slot]),
                "shard_munge_ms": [
                    float(x) for x in self.shard_munge_ms[slot, :ns]
                ],
                "shard_send_ms": [
                    float(x) for x in self.shard_send_ms[slot, :ns]
                ],
            })
        return out


class LatencyAttribution:
    """Deterministic 1-in-K sampled per-stage wire-latency recorder.

    The sample predicate is `sn % sample_every == 0` on the munged
    sequence number of already-stamped entries (`t_arr > 0`): no RNG on
    the hot path, the same packets sample on every run, and the cost is
    one vectorized mask per send call. Sampled stage latencies land in
    small per-stage rings of raw millisecond values; `drain()` hands the
    new samples to telemetry (histograms), `summary()` computes exact
    percentiles over the retained window for bench/debug.

    Thread-safety: observe_* are called from the event loop AND the
    pacer worker (udp.do_send runs off-loop when paced), so pushes
    serialize on a lock — one uncontended acquire per send call."""

    CAP = 4096  # retained samples per stage (at 1-in-64 this is minutes)

    def __init__(self, sample_every: int = 64):
        self.sample_every = max(1, int(sample_every))
        n = len(STAGES)
        self.ring = np.zeros((n, self.CAP), np.float32)
        self.total = np.zeros(n, np.int64)       # samples pushed since reset()
        self._drained = np.zeros(n, np.int64)    # consumed watermark
        # Monotone pair per stage: samples ever pushed and their sum.
        # reset() and drain() leave it alone, so a mean over any window
        # reads by difference (/debug/rooms `wire_stages`).
        self.pushed = np.zeros(n, np.int64)
        self.sum_ms = np.zeros(n, np.float64)
        self._lock = threading.Lock()

    def _push(self, stage: int, vals_ms: np.ndarray) -> None:
        m = len(vals_ms)
        if not m:
            return
        pushed, sum_ms = m, float(vals_ms.sum(dtype=np.float64))
        if m > self.CAP:
            vals_ms = vals_ms[-self.CAP:]
            m = self.CAP
        with self._lock:
            self.pushed[stage] += pushed
            self.sum_ms[stage] += sum_ms
            pos = int(self.total[stage]) % self.CAP
            end = pos + m
            if end <= self.CAP:
                self.ring[stage, pos:end] = vals_ms
            else:
                k = self.CAP - pos
                self.ring[stage, pos:] = vals_ms[:k]
                self.ring[stage, : end - self.CAP] = vals_ms[k:]
            self.total[stage] += m

    def _mask(self, sn: np.ndarray, t_arr: np.ndarray) -> np.ndarray:
        return (sn % self.sample_every == 0) & (t_arr > 0.0)

    def observe_batch(self, sn, t_arr, t_dispatch: float,
                      t_device_end: float, now: float) -> None:
        """Batched-tier send: decompose each sampled entry's arrival→wire
        latency at the tick's dispatch and device-commit boundaries.
        No-ops when the batch predates the stamps (t_dispatch == 0)."""
        if t_arr is None or t_dispatch <= 0.0 or t_device_end <= 0.0:
            return
        sn = np.asarray(sn)
        t_arr = np.asarray(t_arr, np.float64)
        m = self._mask(sn, t_arr)
        if not m.any():
            return
        ta = t_arr[m]
        # A packet can arrive after the tick it rides was dispatched
        # (late slab stragglers): clip, the stage split stays >= 0.
        staging = np.maximum(t_dispatch - ta, 0.0) * 1e3
        device_ms = max(t_device_end - t_dispatch, 0.0) * 1e3
        egress_ms = max(now - t_device_end, 0.0) * 1e3
        self._push(_S_STAGING, staging.astype(np.float32))
        self._push(_S_DEVICE, np.full(len(ta), device_ms, np.float32))
        self._push(_S_EGRESS, np.full(len(ta), egress_ms, np.float32))
        self._push(_S_TOTAL, ((now - ta) * 1e3).astype(np.float32))

    def observe_express(self, sn, t_arr, now: float) -> None:
        """Express-tier send: one arrival→wire stage (the lane skips the
        tick entirely); also feeds `total` so the combined forward-latency
        histogram covers both tiers."""
        sn = np.asarray(sn)
        t_arr = np.asarray(t_arr, np.float64)
        m = self._mask(sn, t_arr)
        if not m.any():
            return
        lat = ((now - t_arr[m]) * 1e3).astype(np.float32)
        self._push(_S_EXPRESS, lat)
        self._push(_S_TOTAL, lat)

    def reset(self) -> None:
        """Discard the retained window (bench measurement-window start:
        warmup/compile-era samples would poison the percentiles)."""
        with self._lock:
            self.total[:] = 0
            self._drained[:] = 0

    def drain(self) -> dict[str, np.ndarray]:
        """New samples per stage since the last drain (telemetry scrape).
        A burst past CAP between drains keeps the newest CAP."""
        out: dict[str, np.ndarray] = {}
        with self._lock:
            for s, name in enumerate(STAGES):
                total = int(self.total[s])
                new = total - int(self._drained[s])
                if new <= 0:
                    continue
                new = min(new, self.CAP)
                pos = total % self.CAP
                lo = (pos - new) % self.CAP
                if lo + new <= self.CAP:
                    vals = self.ring[s, lo:lo + new].copy()
                else:
                    vals = np.concatenate(
                        [self.ring[s, lo:], self.ring[s, : pos]]
                    )
                self._drained[s] = total
                out[name] = vals
        return out

    def cumulative(self) -> dict[str, dict[str, float]]:
        """{stage: {n, sum_ms}} since the process began, every stage
        (cold path: /debug/rooms)."""
        with self._lock:
            return {
                name: {"n": int(self.pushed[s]),
                       "sum_ms": round(float(self.sum_ms[s]), 3)}
                for s, name in enumerate(STAGES)
            }

    def summary(self) -> dict[str, dict[str, float]]:
        """Exact percentiles over each stage's retained window (bench and
        /debug/trace sidecar; cold path)."""
        out: dict[str, dict[str, float]] = {}
        with self._lock:
            for s, name in enumerate(STAGES):
                n = int(min(self.total[s], self.CAP))
                if not n:
                    continue
                w = self.ring[s, :n].astype(np.float64)
                out[name] = {
                    "n": int(self.total[s]),
                    "p50_ms": round(float(np.percentile(w, 50)), 3),
                    "p90_ms": round(float(np.percentile(w, 90)), 3),
                    "p99_ms": round(float(np.percentile(w, 99)), 3),
                    "mean_ms": round(float(w.mean()), 3),
                }
        return out


class BlackBox:
    """Per-room flight recorder: ring of the last M (t, code, a, b)
    events per room row, plus one node lane (row R) for room-less events
    (governor level moves, supervisor restarts).

    `emit` is the GC07-checked hot-path API: four scalar stores and a
    monotonic stamp, no allocation. `dump`/`dump_to` are cold paths that
    materialize a lane as dicts, log it, and retain the last few dumps
    for /debug/blackbox/{room}."""

    NODE = -1  # emit(room=NODE, ...) targets the node lane

    def __init__(self, rooms: int, events: int = 64, log=None):
        self.rooms = int(rooms)
        self.events = max(4, int(events))
        lanes = self.rooms + 1
        self.t = np.zeros((lanes, self.events), np.float64)
        self.code = np.zeros((lanes, self.events), np.int16)
        self.a = np.zeros((lanes, self.events), np.float64)
        self.b = np.zeros((lanes, self.events), np.float64)
        self.pos = np.zeros(lanes, np.int32)
        self.total = np.zeros(lanes, np.int64)
        self.log = log
        from collections import deque

        # Bounded dump retention for /debug/blackbox (GC05: explicit cap).
        self.last_dumps: deque = deque(maxlen=8)
        self.dumps = 0

    def _lane(self, room: int) -> int:
        if 0 <= room < self.rooms:
            return room
        return self.rooms

    def emit(self, room: int, code: int, a: float = 0.0,
             b: float = 0.0) -> None:
        lane = self._lane(room)
        slot = self.pos[lane]
        self.t[lane, slot] = time.monotonic()
        self.code[lane, slot] = code
        self.a[lane, slot] = a
        self.b[lane, slot] = b
        self.pos[lane] = (slot + 1) % self.events
        self.total[lane] += 1

    def dump(self, room: int) -> list[dict[str, Any]]:
        """One lane's events, oldest first (cold path)."""
        lane = self._lane(room)
        have = int(min(self.total[lane], self.events))
        pos = int(self.pos[lane])
        out = []
        for i in range(have):
            slot = (pos - have + i) % self.events
            code = int(self.code[lane, slot])
            out.append({
                "t": round(float(self.t[lane, slot]), 6),
                "event": EVENT_NAMES.get(code, str(code)),
                "a": float(self.a[lane, slot]),
                "b": float(self.b[lane, slot]),
            })
        return out

    def dump_to(self, room: int, reason: str) -> list[dict[str, Any]]:
        """Dump a lane on a trigger (quarantine, repair failure, restart,
        migration rollback, NACK storm): log it and retain it for
        /debug/blackbox. Returns the dumped events."""
        events = self.dump(room)
        record = {
            "room": int(room),
            "reason": reason,
            "at": round(time.monotonic(), 6),
            "events": events,
        }
        self.last_dumps.append(record)
        self.dumps += 1
        if self.log is not None:
            self.log.warn(
                "black-box dump", room=int(room), reason=reason,
                n_events=len(events), events=events[-16:],
            )
        return events
