"""Benchmark: batched selective-forwarding on one chip, device + host path.

Primary metric: RTP packet *writes* per second — one write = actually
forwarding one packet to one subscriber, the unit of the reference's hot
path (`DownTrack.WriteRTP`, pkg/sfu/downtrack.go:680). The reference's own
in-code measurement is ~50 µs per write on a server CPU core
(pkg/sfu/downtrackspreader.go:96-98) ⇒ baseline 20,000 writes/sec/core.
Only packets the selector actually forwards are counted (drops are not).

Also reported in the same JSON line:
  - p99_forward_ms / p50_forward_ms — ingest→wire forward latency through
    the REAL host path (UDP datagram dispatch → native batch parse →
    IngestBuffer → device tick → egress rewrite → socket writes), the
    BASELINE.md stated metric, measured wall-clock on real sockets.
  - configs — BASELINE.md ladder configs 1-4 (device throughput each).
    Config 5 (multi-node) is exercised by the driver's dryrun_multichip.
  - mem_1k_rooms_50subs_ok — a 1k-room × 50-sub plane allocates and ticks
    on this chip (north-star memory feasibility: 10k rooms / v5e-8).

Runs on the TPU only: every record carries `device` (platform, kind,
count as JAX reports them), and without a TPU it exits non-zero before
measuring anything — a CPU time is never filed under a device metric.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline", ...}.
"""

from __future__ import annotations

import argparse
import asyncio
import json
import os
import signal
import sys
import time

import numpy as np

BASELINE_WRITES_PER_SEC = 20_000.0  # reference: ~50 µs per WriteRTP, 1 core

# -- un-killable result emission -------------------------------------------
#
# The driver runs `python bench.py` under a deadline and keeps the LAST
# complete JSON line of stdout. Round 4's bench emitted one line at the
# very end and was killed first — every measured number died with it. Now:
#   * RESULT is global and re-emitted (one flushed JSON line) after every
#     section, so a kill at any point loses at most the section in flight;
#   * a total budget (BENCH_BUDGET_S env, --budget flag) is checked before
#     each section, with explicit *_skipped markers when it runs out;
#   * SIGTERM/SIGINT (what `timeout` sends first) re-emit and exit 0.

RESULT: dict = {}
_SECTION = ["startup"]
_T0 = time.perf_counter()
_BUDGET = [float(os.environ.get("BENCH_BUDGET_S", "480"))]


def emit() -> None:
    # Leading newline terminates any partial line an interrupted print
    # left behind, keeping the last stdout line parseable.
    sys.stdout.write("\n" + json.dumps(RESULT) + "\n")
    sys.stdout.flush()


def _emit_raw() -> None:
    """Async-signal-safe emit: the handler may interrupt a buffered
    sys.stdout.write, and a reentrant call into BufferedWriter raises —
    os.write to fd 1 cannot."""
    os.write(1, ("\n" + json.dumps(RESULT) + "\n").encode())


def _remaining() -> float:
    return _BUDGET[0] - (time.perf_counter() - _T0)


def section_ok(name: str, est_s: float) -> bool:
    """Gate a section on the remaining budget; record the skip if not."""
    if _remaining() < est_s:
        RESULT.setdefault("skipped", {})[name] = (
            f"budget: {_remaining():.0f}s left < ~{est_s:.0f}s needed"
        )
        emit()
        return False
    _SECTION[0] = name
    return True


def section_done(name: str, t_start: float) -> None:
    RESULT.setdefault("section_s", {})[name] = round(
        time.perf_counter() - t_start, 1
    )
    emit()


def _on_kill(signum, frame):  # noqa: ARG001
    RESULT["killed_in_section"] = _SECTION[0]
    try:
        _emit_raw()
    finally:
        os._exit(0)


# -- device throughput ------------------------------------------------------

def device_bench(dims, spec, ticks: int, warmup: int) -> dict:
    """PRODUCTION tick graph (unpack_tick_inputs → media_plane_tick →
    pack_tick_outputs, state donated), one dispatch per tick as the
    runtime issues them, timed over `ticks` ticks ending in
    block_until_ready.

    Inputs land in HBM once as a pool of distinct ticks (the runtime
    uploads per tick; that cost is the wire sections' to measure). The
    packed output buffer is consumed on-device into a checksum so nothing
    dead-code-eliminates.
    """
    import jax
    from livekit_server_tpu.models import plane, synth

    R, T, K, S = dims
    state = synth.make_state(dims, spec)
    traffic = synth.init_traffic(dims, spec)

    # Distinct ticks up to ~128 MB of HBM (floor min(ticks, 8)); the loop
    # wraps beyond the pool (SN replays read as late packets — selection
    # and allocation work, the measured quantity, is unaffected).
    per_tick = (len(plane.PKT_FIELDS) * R * T * K + 8 * R * S + R * T) * 4
    pool_n = max(min(ticks, 8),
                 min(warmup + ticks, int(128e6 // max(per_tick, 1))))
    pool = []
    for i in range(pool_n):
        traffic, inp = synth.next_tick(traffic, dims, spec, tick_index=i)
        pkt, fb, tf, _, _ = plane.pack_tick_inputs(inp)
        pool.append((jnp.asarray(pkt), jnp.asarray(fb), jnp.asarray(tf)))
    tick_ms_c = jnp.int32(spec.tick_ms)
    roll_c = jnp.int32(0)

    def step(state, acc, pkt, fb, tf):
        inp = plane.unpack_tick_inputs(pkt, fb, tf, tick_ms_c, roll_c)
        ev = jnp.sum(
            inp.valid[:, :, :, None] & state.ctrl.subscribed[:, :, None, :],
            dtype=jnp.int32,
        )
        state, out = plane.media_plane_tick(state, inp)
        buf = plane.pack_tick_outputs(out)
        # acc[2] wraps in int32 — it exists to defeat DCE, not as a
        # checksum of record.
        return state, acc + jnp.stack([out.fwd_packets.sum(), ev, buf.sum()])

    step = jax.jit(step, donate_argnums=(0, 1))

    def run(state, n, start):
        acc = jnp.zeros((3,), jnp.int32)
        t0 = time.perf_counter()
        for i in range(n):
            state, acc = step(state, acc, *pool[(start + i) % pool_n])
        acc = np.asarray(jax.block_until_ready(acc))
        return state, int(acc[0]), int(acc[1]), time.perf_counter() - t0

    state, _, _, _ = run(state, max(1, warmup), 0)   # compile + settle
    state, fwd, ev, dt = run(state, ticks, warmup)
    return {
        "fwd_writes_per_s": round(fwd / dt, 1),
        "evaluated_per_s": round(ev / dt, 1),
        "device_tick_ms": round(dt / ticks * 1000.0, 3),
    }


# -- real-time wire bench ---------------------------------------------------
#
# Replaces the r3 composed p99 (VERDICT r3 missing #2 / next #1 and #4):
# the production serving loop runs at real tick cadence; publishers put
# raw RTP on the server's actual UDP socket; 1-in-6 subscribers is a
# sealed "modern" client whose egress carries TWCC counters and whose
# reader task acks them with RTPFB fmt-15 frames through the server's
# real RTCP path (_handle_twcc exercised on every feedback); the rest are
# cleartext "legacy" clients driving the estimate channel with REMB
# frames — no direct ingest._estimate injection anywhere. Per-packet
# forward latency comes from the always-on ForwardLatencyProbe
# (recvmmsg-return → native-send-return), so the reported p50/p99 are
# wall-clock measurements that INCLUDE tick-queueing wait.

def _vp8_descriptor(pid: int, tl0: int, tid: int, sbit: bool, keyframe: bool) -> bytes:
    """Minimal VP8 payload descriptor (X, I 15-bit pid, L, T) + the first
    payload byte whose P bit conveys keyframe-ness."""
    return bytes(
        [0x80 | (0x10 if sbit else 0), 0xE0, 0x80 | ((pid >> 8) & 0x7F),
         pid & 0xFF, tl0 & 0xFF, ((tid & 0x3) << 6) | 0x20,
         0x00 if keyframe else 0x01]
    )


def _build_traffic_lib(ssrcs, tick_ms: int, n_ticks: int, video_kbps: float):
    """A cyclable library of per-tick publisher datagram batches.

    Each tick entry: a writable blob + per-datagram (offset, length,
    stream index, built-in SN/TS). On every reuse cycle the publisher
    patches SN/TS in place (vectorized big-endian writes) so streams stay
    continuous forever — SNs advance by each stream's per-cycle packet
    count, TS by the library's wall span.
    """
    v_pps = video_kbps * 125.0 / 1200.0          # 1200-byte video packets
    kf_every = max(1, 200 // tick_ms)            # keyframe each ~200 ms
    a_every = max(1, 20 // tick_ms)              # Opus: one packet / 20 ms
    sn_next = {i: 0 for i in range(len(ssrcs))}
    lib = []
    for tick in range(n_ticks):
        dgrams, sidx, sns, tss = [], [], [], []
        for i, (r, t, is_video, ssrc) in enumerate(ssrcs):
            if is_video:
                n = int((tick + 1) * v_pps * tick_ms / 1000.0) - int(
                    tick * v_pps * tick_ms / 1000.0
                )
                ts = (tick * 90 * tick_ms) & 0xFFFFFFFF
            else:
                n = 1 if tick % a_every == 0 else 0
                ts = (tick * 48 * tick_ms) & 0xFFFFFFFF
            for k in range(n):
                sn = sn_next[i]
                sn_next[i] += 1
                hdr = bytearray(12)
                hdr[0] = 0x80
                hdr[1] = (0x80 if k == n - 1 else 0) | (96 if is_video else 111)
                hdr[2:4] = (sn & 0xFFFF).to_bytes(2, "big")
                hdr[4:8] = ts.to_bytes(4, "big")
                hdr[8:12] = ssrc.to_bytes(4, "big")
                if is_video:
                    payload = _vp8_descriptor(
                        tick & 0x7FFF, tick & 0xFF, k % 2, sbit=k == 0,
                        keyframe=tick % kf_every == 0 and k == 0,
                    ) + bytes(1100)
                else:
                    payload = bytes(80)
                dgrams.append(bytes(hdr) + payload)
                sidx.append(i)
                sns.append(sn)
                tss.append(ts)
        blob, offs, lens = _stage_frames(dgrams)
        lib.append({
            "blob": blob.copy(),
            "offs": offs, "lens": lens,
            "sidx": np.array(sidx, np.int64),
            "sn0": np.array(sns, np.int64),
            "ts0": np.array(tss, np.int64),
        })
    sn_per_cycle = np.array([sn_next[i] for i in range(len(ssrcs))], np.int64)
    ts_per_cycle = np.array(
        [n_ticks * (90 if v else 48) * tick_ms for (_, _, v, _) in ssrcs],
        np.int64,
    )
    return lib, sn_per_cycle, ts_per_cycle


def _stage_frames(frames: list) -> tuple:
    """frames → (blob, offs int64, lens int32) in native send_raw layout."""
    lens = np.array([len(f) for f in frames], np.int32)
    offs = np.zeros(len(frames), np.int64)
    if len(frames) > 1:
        np.cumsum(lens[:-1].astype(np.int64), out=offs[1:])
    return np.frombuffer(b"".join(frames), np.uint8), offs, lens


def _patch_tick(entry, cycle: int, sn_pc, ts_pc) -> None:
    """Advance one library tick's SN/TS fields for reuse cycle `cycle`."""
    if cycle == 0 or not len(entry["offs"]):
        return
    blob, offs = entry["blob"], entry["offs"]
    s = entry["sidx"]
    sn = (entry["sn0"] + cycle * sn_pc[s]) & 0xFFFF
    ts = (entry["ts0"] + cycle * ts_pc[s]) & 0xFFFFFFFF
    blob[offs + 2] = sn >> 8
    blob[offs + 3] = sn & 0xFF
    blob[offs + 4] = ts >> 24
    blob[offs + 5] = (ts >> 16) & 0xFF
    blob[offs + 6] = (ts >> 8) & 0xFF
    blob[offs + 7] = ts & 0xFF


async def wire_bench(
    dims,
    tick_ms: int = 5,
    duration_s: float = 8.0,
    warm_ticks: int = 30,
    video_tracks: int = 4,
    audio_tracks: int = 4,
    video_kbps: float = 3000.0,
    ack_ms: float = 25.0,
    n_slices: int = 4,
    warm_timeout_s: float = 120.0,
    egress_shards: int = 0,
    express_max_subs: int = 0,
) -> dict:
    """Real-time serving-loop measurement (see module-section comment).

    Everything reported here is measured wall-clock on this process's real
    sockets — publisher → kernel → recvmmsg → parse/stage → device tick →
    egress build/seal → kernel send — with tick-queueing wait included via
    the ForwardLatencyProbe stamps.
    """
    import socket as _socket

    from livekit_server_tpu.runtime import PlaneRuntime
    from livekit_server_tpu.runtime.crypto import (
        MediaCryptoClient,
        MediaCryptoRegistry,
    )
    from livekit_server_tpu.native import egress as native_egress
    from livekit_server_tpu.runtime.udp import (
        build_remb,
        build_twcc_feedback,
        start_udp_transport,
    )

    runtime = PlaneRuntime(dims, tick_ms=tick_ms,
                           egress_shards=egress_shards,
                           express_max_subs=express_max_subs,
                           express_max_rooms=dims.rooms)
    reg = MediaCryptoRegistry()
    udp = await start_udp_transport(
        runtime.ingest, host="127.0.0.1", port=0, crypto=reg
    )
    # Production egress path: the sharded plane orchestrator (room-aligned
    # shards + canonical-group staging), same wiring as service/server.py.
    udp.attach_egress_plane(runtime.egress_plane)
    # Flight-recorder attribution: sampled arrival→wire stage split
    # (same wiring as service/server.py start()).
    udp.wire_stages = runtime.wire_stages
    runtime.attach_rx(udp.rx_schedule)   # reads keep off a tick's chain
    if runtime.express is not None:
        # Two-tier latency plane: eligible rooms forward on arrival.
        udp.attach_express(runtime.express)
    srv_addr = udp.transport.get_extra_info("sockname")
    srv_ip, srv_port = 0x7F000001, srv_addr[1]

    def mk_sock():
        s = _socket.socket(_socket.AF_INET, _socket.SOCK_DGRAM)
        s.bind(("127.0.0.1", 0))
        s.setblocking(False)
        s.setsockopt(_socket.SOL_SOCKET, _socket.SO_RCVBUF, 4 << 20)
        s.setsockopt(_socket.SOL_SOCKET, _socket.SO_SNDBUF, 4 << 20)
        return s

    pub_sock = mk_sock()    # all publisher streams
    ack_sock = mk_sock()    # sealed cohort sink + TWCC feedback source
    sink_sock = mk_sock()   # legacy cohort sink (never read) + REMB source

    nv = min(video_tracks, dims.tracks)
    used = min(nv + audio_tracks, dims.tracks)
    ssrcs = []
    acked = []   # (room, sub, session, client, media_ssrc)
    remb_subs = []
    for r in range(dims.rooms):
        for t in range(used):
            is_video = t < nv
            ssrc = udp.assign_ssrc(r, t, is_video)
            runtime.set_track(r, t, published=True, is_video=is_video)
            ssrcs.append((r, t, is_video, ssrc))
        for s in range(dims.subs):
            for t in range(used):
                runtime.set_subscription(r, t, s, subscribed=True)
            if s == 0:
                # Modern client: sealed egress (TWCC counters on the wire).
                sess = reg.mint()
                udp.bind_sub_session(r, s, sess)
                udp.register_subscriber(r, s, ack_sock.getsockname())
                client = MediaCryptoClient(sess.key_id, sess.key)
                acked.append([r, s, sess, client, 0])
            else:
                udp.register_subscriber(r, s, sink_sock.getsockname())
                remb_subs.append((r, s))
    # The sealed cohort announces itself (client_active latch → fb_enabled);
    # a tiny sealed RTCP RR is the hello real SDK clients send first.
    hello = bytes([0x80, 201, 0, 1]) + (0x1234).to_bytes(4, "big")
    for ent in acked:
        ack_sock.sendto(ent[3].seal(hello), ("127.0.0.1", srv_port))
    await asyncio.sleep(0.1)
    for ent in acked:
        ent[4] = udp.subscriber_ssrc(ent[0], ent[1], 0)
    kid_to_ent = {ent[2].key_id: ent for ent in acked}

    # Publisher library: 1 s of traffic, cycled with in-place SN/TS patch.
    lib, sn_pc, ts_pc = _build_traffic_lib(
        ssrcs, tick_ms, max(1, 1000 // tick_ms), video_kbps
    )
    for e in lib:
        n = len(e["offs"])
        e["ips"] = np.full(n, srv_ip, np.uint32)
        e["ports"] = np.full(n, srv_port, np.uint16)
        # Slice bounds for sub-tick arrival spreading.
        e["cuts"] = np.linspace(0, n, n_slices + 1).astype(np.int64)

    # REMB blob (legacy cohort estimate channel): rebuilt never — the
    # frames are stateless; one send_raw per interval from the sink sock.
    est_bps = 1.25 * 1000.0 * (video_tracks * video_kbps + audio_tracks * 64.0)
    remb_frames = [
        build_remb(0x42, est_bps, [udp.subscriber_ssrc(r, s, 0)])
        for (r, s) in remb_subs
    ]
    remb_blob, remb_offs, remb_lens = _stage_frames(remb_frames)
    remb_ips = np.full(len(remb_frames), srv_ip, np.uint32)
    remb_ports = np.full(len(remb_frames), srv_port, np.uint16)

    # Instrument device wall time (per in-loop call) + per-tick host work.
    dev_s = [0.0]
    orig_step = runtime._device_step

    def timed_step(inp):
        t0 = time.perf_counter()
        out = orig_step(inp)
        dev_s[0] += time.perf_counter() - t0
        return out

    runtime._device_step = timed_step
    tick_acc = [0, 0.0]  # ticks seen, Σ tick_s
    # Late-tick CAUSE breakdown: for each deadline miss, which pipeline
    # term dominated the tick — the wake-edge overshoot, staging, the
    # device step, or fan-out. Classified from the tick record _complete
    # just appended (recent_ticks[-1] is this tick's).
    late_cause = {"edge": 0, "stage": 0, "device": 0, "fanout": 0}

    def on_tick(res):
        udp.send_egress_batch(res.egress_batch, pacer_allowed=res.pacer_allowed)
        tick_acc[0] += 1
        tick_acc[1] += res.tick_s
        rec = runtime.recent_ticks[-1] if runtime.recent_ticks else None
        if rec and rec.get("late"):
            parts = {
                "edge": rec.get("edge_overshoot_us", 0.0) / 1000.0,
                "stage": rec.get("stage_ms", 0.0),
                "device": rec.get("device_ms", 0.0),
                "fanout": rec.get("fanout_ms", 0.0),
            }
            late_cause[max(parts, key=parts.get)] += 1

    runtime.on_tick(on_tick)

    stop = asyncio.Event()
    import threading

    stop_thr = threading.Event()
    pub_stats = {"sent": 0, "skipped_ticks": 0}

    def publisher_thread():
        """Real-time load generator in its own OS thread: the asyncio
        loop's long synchronous spans (rx callbacks, staging, fan-out)
        would starve a task-based pacer. Behind-schedule slices are sent
        in a burst; if the generator falls >0.5 s behind (overloaded
        rig), whole ticks are skipped and counted rather than building an
        unbounded backlog."""
        period = tick_ms / 1000.0
        slice_p = period / n_slices
        i, cycle = 0, 0
        next_at = time.perf_counter() + slice_p
        pf = pub_sock.fileno()
        while not stop_thr.is_set():
            behind = time.perf_counter() - next_at
            if behind > 0.5:
                n_skip = int(behind / period)
                pub_stats["skipped_ticks"] += n_skip
                for _ in range(n_skip):
                    next_at += period
                    i += 1
                    if i == len(lib):
                        i, cycle = 0, cycle + 1
                continue
            e = lib[i]
            _patch_tick(e, cycle, sn_pc, ts_pc)
            cuts = e["cuts"]
            for sl in range(n_slices):
                lag = next_at - time.perf_counter()
                if lag > 0:
                    time.sleep(lag)
                lo, hi = int(cuts[sl]), int(cuts[sl + 1])
                if hi > lo:
                    pub_stats["sent"] += native_egress.send_raw(
                        pf, e["blob"], e["offs"][lo:hi], e["lens"][lo:hi],
                        e["ips"][lo:hi], e["ports"][lo:hi],
                    )
                next_at += slice_p
            i += 1
            if i == len(lib):
                i, cycle = 0, cycle + 1

    async def acker():
        """Sealed-cohort reader: drain egress, ack counters as RTPFB
        fmt-15 through the server's real RTCP path."""
        MAXN, MAXD = 2048, 2048
        scratch = np.zeros(MAXN * MAXD, np.uint8)
        offs = np.zeros(MAXN, np.int32)
        lens = np.zeros(MAXN, np.int32)
        ips = np.zeros(MAXN, np.uint32)
        ports = np.zeros(MAXN, np.uint16)
        af = ack_sock.fileno()
        while not stop.is_set():
            await asyncio.sleep(ack_ms / 1000.0)
            frames = []
            while True:
                nn = native_egress.rx_batch(af, scratch, offs, lens, ips, ports, MAXD)
                if nn <= 0:
                    break
                now_us = int(time.perf_counter() * 1e6)
                o = offs[:nn].astype(np.int64)
                sealed = scratch[o] == 0x01
                if sealed.any():
                    so = o[sealed]
                    kid = (
                        (scratch[so + 1].astype(np.int64) << 24)
                        | (scratch[so + 2].astype(np.int64) << 16)
                        | (scratch[so + 3].astype(np.int64) << 8)
                        | scratch[so + 4]
                    )
                    ctr = np.zeros(len(so), np.int64)
                    for b in range(8):
                        ctr = (ctr << 8) | scratch[so + 6 + b].astype(np.int64)
                    for k in np.unique(kid):
                        ent = kid_to_ent.get(int(k))
                        if ent is None:
                            continue
                        sel = np.sort(ctr[kid == k])
                        # Counters in one feedback frame must span < 2^16
                        # (ctr_off is u16): a kernel-drop gap can exceed
                        # that — split at the discontinuity.
                        lo = 0
                        while lo < len(sel):
                            hi = int(np.searchsorted(sel, sel[lo] + 0xFFFF))
                            frames.append(build_twcc_feedback(
                                0x42, ent[4],
                                [(int(c), now_us) for c in sel[lo:hi]],
                            ))
                            lo = hi
                if nn < MAXN:
                    break
            if frames:
                fb_blob, fb_offs, fb_lens = _stage_frames(frames)
                native_egress.send_raw(
                    af, fb_blob, fb_offs, fb_lens,
                    np.full(len(frames), srv_ip, np.uint32),
                    np.full(len(frames), srv_port, np.uint16),
                )

    async def remb_pump():
        while not stop.is_set():
            native_egress.send_raw(
                sink_sock.fileno(), remb_blob, remb_offs, remb_lens,
                remb_ips, remb_ports,
            )
            await asyncio.sleep(0.2)

    task_errors: list[str] = []

    async def guarded(coro, name):
        """A helper task dying mid-window must surface in the record, not
        silently degrade the measurement."""
        try:
            await coro
        except asyncio.CancelledError:
            raise
        except Exception as e:  # noqa: BLE001
            task_errors.append(f"{name}: {type(e).__name__}: {e}")

    tasks = [
        asyncio.ensure_future(guarded(acker(), "acker")),
        asyncio.ensure_future(guarded(remb_pump(), "remb")),
    ]
    pub_thr = threading.Thread(target=publisher_thread, daemon=True)
    pub_thr.start()
    try:
        runtime.start()

        # Warm-up: first ticks pay jit compile; wait for steady state.
        t0 = time.perf_counter()
        while (
            runtime.stats["ticks"] < warm_ticks
            and time.perf_counter() - t0 < warm_timeout_s
        ):
            await asyncio.sleep(0.05)

        # Close the recompile watchdog's warmup window with the warm
        # ticks: compiles during the measurement window below are
        # steady-state retraces (reported in the summary; should be 0).
        runtime.mark_warm()
        # Measurement window: reset every counter the report reads.
        udp.fwd_latency.reset()
        udp.fwd_latency_express.reset()
        if runtime.wire_stages is not None:
            # Same window discipline as the probes: compile/warmup-era
            # samples (a 2+ s first device step) would poison the stage
            # percentiles.
            runtime.wire_stages.reset()
        dev_s[0] = 0.0
        tick_acc[0], tick_acc[1] = 0, 0.0
        for key in late_cause:
            late_cause[key] = 0
        base = {
            "ticks": runtime.stats["ticks"],
            "late": runtime.stats["late_ticks"],
            "rx": udp.stats["rx"],
            "tx": udp.stats["tx"],
            "twcc": udp.stats.get("twcc_rx", 0),
            "dropped": runtime.ingest.dropped,
            "fwd": runtime.stats["fwd_packets"],
            # Per-stage pipeline accounting (three-stage tick loop).
            "stage_s": runtime.stats.get("stage_s", 0.0),
            "device_s": runtime.stats.get("device_s", 0.0),
            "fanout_s": runtime.stats.get("fanout_s", 0.0),
            "stalls": runtime.stats.get("pipeline_stalls", 0),
        }
        t_meas = time.perf_counter()
        await asyncio.sleep(duration_s)
        wall = time.perf_counter() - t_meas
        probe = udp.fwd_latency.summary()
        probe_ex = udp.fwd_latency_express.summary()
        ticks = runtime.stats["ticks"] - base["ticks"]
        tx = udp.stats["tx"] - base["tx"]
        host_busy_s = max(tick_acc[1] - dev_s[0], 1e-9)
    finally:
        # The publisher floods ~280k pps: it MUST die even when the
        # measurement throws, or every later bench section is corrupted.
        stop.set()
        stop_thr.set()
        for t in tasks:
            t.cancel()
        await asyncio.gather(*tasks, return_exceptions=True)
        pub_thr.join(timeout=2.0)
        await runtime.stop()
        runtime._device_step = orig_step
        udp.transport.close()
        pub_sock.close()
        ack_sock.close()
        sink_sock.close()

    rx = udp.stats["rx"] - base["rx"]
    dropped = runtime.ingest.dropped - base["dropped"]
    n_ticks = max(ticks, 1)

    def stage_ms(key: str) -> float:
        """Measurement-window per-tick mean of one pipeline stage."""
        return round(
            (runtime.stats.get(key, 0.0) - base[key]) / n_ticks * 1000.0, 3
        )

    out = {
        "tick_ms": tick_ms,
        "p50_wire_ms": probe["p50_ms"],
        "p99_wire_ms": probe["p99_ms"],
        "p999_wire_ms": probe["p999_ms"],
        "mean_wire_ms": probe["mean_ms"],
        "max_wire_ms": probe["max_ms"],
        "lat_samples": probe["n"],
        "late_cause": dict(late_cause),
        "sleep_bias_us": round(max(runtime._sleep_bias, 0.0) * 1e6, 1),
        "ticks": ticks,
        "achieved_tick_hz": round(ticks / wall, 1) if wall else 0.0,
        "late_ticks": runtime.stats["late_ticks"] - base["late"],
        "wire_in_pps": round(rx / wall, 1),
        "wire_out_pps": round(tx / wall, 1),
        "host_ms_per_tick": round(host_busy_s / max(ticks, 1) * 1000.0, 3),
        "dev_ms_per_tick": round(dev_s[0] / max(ticks, 1) * 1000.0, 3),
        # Per-stage pipeline split (runtime.stats deltas): the overlap win
        # is measured per stage, not inferred from host_ms_per_tick.
        "stage_ms_per_tick": stage_ms("stage_s"),
        "device_ms_per_tick": stage_ms("device_s"),
        "fanout_ms_per_tick": stage_ms("fanout_s"),
        "pipeline_stalls": runtime.stats.get("pipeline_stalls", 0) - base["stalls"],
        "host_egress_pps": round(tx / host_busy_s, 1) if tx else 0.0,
        # Sharded-plane view of the same window: EMA of entries over the
        # per-tick critical-path (max-shard) send time, and the share of
        # entries served from a staged canonical instead of a full build.
        "plane_pps": runtime.egress_plane.observe()["host_egress_pps"],
        "plane_shards": runtime.egress_plane.shards,
        "grouped_pct": round(
            100.0 * runtime.egress_plane.stats["grouped_entries"]
            / max(runtime.egress_plane.stats["entries"], 1), 1
        ),
        "twcc_acks": udp.stats.get("twcc_rx", 0) - base["twcc"],
        "ingest_dropped_pct": round(100.0 * dropped / max(rx, 1), 2),
        "fwd_packets": runtime.stats["fwd_packets"] - base["fwd"],
        "pub_skipped_ticks": pub_stats["skipped_ticks"],
        # Sampled per-stage wire-latency decomposition (trace.py
        # LatencyAttribution): where the batched tier's arrival→wire
        # time actually goes — staging wait vs device step vs egress.
        "stages": (runtime.wire_stages.summary()
                   if runtime.wire_stages is not None else {}),
        # Recompile watchdog over the measurement window: >0 means the
        # steady-state tick path retraced mid-run.
        "xla_compiles_post_warmup": runtime.compile_ledger.post_warmup,
        "xla_warmup_compile_ms": round(runtime.compile_ledger.warmup_ms, 1),
        **({"task_errors": task_errors} if task_errors else {}),
    }
    trace_out = os.environ.get("BENCH_TRACE_OUT")
    if trace_out and runtime.trace is not None:
        # Perfetto-loadable dump of the tick-span ring for this wire run
        # (same format as /debug/trace; validated by tools/trace).
        from livekit_server_tpu.telemetry import trace_export

        with open(trace_out, "w", encoding="utf-8") as fh:
            fh.write(trace_export.export_json(
                runtime.trace.snapshot(), tick_ms
            ))
    if runtime.express is not None:
        # Express-tier wire latency (arrival-driven sends; no tick-queue
        # wait) beside the batched tier's, plus the lane's own counters —
        # the two-tier split IS the tentpole measurement.
        out.update({
            "p50_wire_express_ms": probe_ex["p50_ms"],
            "p90_wire_express_ms": probe_ex["p90_ms"],
            "p99_wire_express_ms": probe_ex["p99_ms"],
            "p999_wire_express_ms": probe_ex["p999_ms"],
            "express_samples": probe_ex["n"],
            "express": runtime.express.debug(),
        })
    return out


# -- main -------------------------------------------------------------------

def _run_wire(result_key: str, dims, tick_ms: int, duration_s: float,
              **kw) -> dict:
    """One wire_bench run into RESULT[result_key]. A failure raises: the
    records emitted so far are already on stdout (emit() after every
    section), and a wire path that broke is not a result to carry on
    past."""
    wire = asyncio.run(wire_bench(dims, tick_ms=tick_ms,
                                  duration_s=duration_s, **kw))
    RESULT[result_key] = wire
    return wire


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--rooms", type=int, default=128)
    ap.add_argument("--tracks", type=int, default=8)
    ap.add_argument("--pkts", type=int, default=16)
    ap.add_argument("--subs", type=int, default=16)
    ap.add_argument("--ticks", type=int, default=50)
    ap.add_argument("--warmup", type=int, default=5)
    ap.add_argument("--budget", type=float, default=None,
                    help="total seconds (default: BENCH_BUDGET_S env or 480)")
    ap.add_argument("--quick", action="store_true",
                    help="primary metric only (skip ladder/host/mem)")
    ap.add_argument("--wire-only", action="store_true",
                    help="run only the real-time wire bench; print its JSON")
    ap.add_argument("--wire-seconds", type=float, default=8.0)
    ap.add_argument("--wire-tick-ms", type=str, default="5",
                    help="tick_ms for the wire bench; comma list runs "
                         "multiple variants (--wire-only mode)")
    ap.add_argument("--wire-rooms", type=int, default=32)
    ap.add_argument("--wire-kbps", type=float, default=3000.0)
    args = ap.parse_args()
    if args.budget is not None:
        _BUDGET[0] = args.budget

    signal.signal(signal.SIGTERM, _on_kill)
    signal.signal(signal.SIGINT, _on_kill)

    import jax

    dev = jax.devices()[0]
    RESULT["device"] = {"platform": dev.platform, "kind": dev.device_kind,
                        "count": len(jax.devices())}
    if dev.platform != "tpu":
        print(f"bench.py measures the chip and JAX reports {RESULT['device']}: "
              "nothing was measured", file=sys.stderr)
        sys.exit(2)
    from livekit_server_tpu.utils.compile_cache import setup_compile_cache

    setup_compile_cache()

    from livekit_server_tpu.models import plane, synth

    # Variant specs: "5,2,2e" — a trailing 'e' runs that tick rate with
    # the express lane enabled (express_max_subs = the wire shape's subs,
    # so every room is eligible).
    wire_specs = [s.strip() for s in str(args.wire_tick_ms).split(",")]
    wire_ticks = [int(s.rstrip("e")) for s in wire_specs]

    if args.wire_only:
        # All requested tick variants in ONE process (tick_ms is a traced
        # input, so extra variants cost no recompile).
        for spec, t in zip(wire_specs, wire_ticks):
            key = "wire" if spec == wire_specs[0] else f"wire_tick{spec}"
            _SECTION[0] = key
            dims_w = plane.PlaneDims(args.wire_rooms, 8, 8, 6)
            _run_wire(key, dims_w, t,
                      args.wire_seconds, video_kbps=args.wire_kbps,
                      express_max_subs=(dims_w.subs if spec.endswith("e")
                                        else 0))
            emit()
        return

    # -- primary metric (always; it IS the scoreboard line) ---------------
    _SECTION[0] = "primary"
    t_sec = time.perf_counter()
    dims = plane.PlaneDims(args.rooms, args.tracks, args.pkts, args.subs)
    # Dense, realistic load: 4×3 Mbps simulcast video + 4 Opus tracks per
    # room at a 20 ms tick ≈ 6-7 video pkts/track/tick.
    spec = synth.TrafficSpec(video_tracks=4, audio_tracks=4, tick_ms=20,
                             video_kbps=3000)
    RESULT.update({
        "metric": "sfu_pkt_sub_writes_per_sec_per_chip",
        "value": 0.0,
        "unit": "writes/s",
        "vs_baseline": 0.0,
        "counted": "forwarded (pkt × subscriber) writes; drops excluded",
    })
    emit()  # a diagnosable record exists from the first seconds on
    try:
        primary = device_bench(dims, spec, args.ticks, args.warmup)
        RESULT.update({
            "value": primary["fwd_writes_per_s"],
            "vs_baseline": round(
                primary["fwd_writes_per_s"] / BASELINE_WRITES_PER_SEC, 2
            ),
            "evaluated_per_s": primary["evaluated_per_s"],
            "device_tick_ms": primary["device_tick_ms"],
        })
    except Exception as e:  # noqa: BLE001 — the r4 lesson: a primary
        # crash must still leave a parseable record on stdout.
        RESULT["primary_error"] = f"{type(e).__name__}: {e}"
    section_done("primary", t_sec)
    if args.quick:
        return

    # -- sharded egress plane microbench (host packet walk, no device) ----
    # The number the egress plane exists to move: datagrams/s through the
    # native sharded assemble(+seal) walk on a wire-shaped batch (32 rooms
    # × 6 subs × 4 video tracks × 7 pkts @ 1100 B ≈ the wire bench's video
    # load per tick). Clear vs sealed split makes the AES share visible;
    # room-aligned shards share no state, so multi-core nodes scale the
    # clear/sealed numbers by core count.
    if section_ok("egress_plane", 20):
        t_sec = time.perf_counter()
        try:
            from livekit_server_tpu.runtime.egress_plane import (
                EgressPlane,
                bench_plane,
            )

            ep = EgressPlane(0)  # all local cores
            shape = dict(n_rooms=32, subs_per_room=6, tracks=4, pkts=7)
            # Warm pass (discarded): pool spin-up + page faults on the
            # scratch/out buffers land here, not in the measurement —
            # this section runs right after the JAX-heavy primary and
            # starts cache-cold.
            bench_plane(ep, payload_len=1100, sealed=False, seconds=0.5,
                        **shape)
            clear = max(
                (bench_plane(ep, payload_len=1100, sealed=False,
                             seconds=2.0, **shape) for _ in range(2)),
                key=lambda r: r.get("pps", 0.0),
            )
            sealed = bench_plane(ep, payload_len=1100, sealed=True,
                                 seconds=2.0, **shape)
            audio = bench_plane(ep, payload_len=160, sealed=True,
                                seconds=1.5, **shape)
            RESULT["egress_plane"] = {
                "shards": ep.shards,
                "pps_clear_build": clear.get("pps", 0.0),
                "pps_sealed_build": sealed.get("pps", 0.0),
                "pps_sealed_160B": audio.get("pps", 0.0),
                "grouped_pct": sealed.get("grouped_pct", 0.0),
                "entries_per_call": sealed.get("entries_per_call", 0),
            }
            # Shard-scaling curve: N shards on N cores, sealed walk. On
            # a 1-CPU rig this is a single point (flagged); a multi-core
            # node records the actual knee instead of the "multiply by
            # cores" assumption (BASELINE.md).
            if (os.cpu_count() or 1) > 1 and section_ok("plane_scaling", 10):
                from livekit_server_tpu.runtime.egress_plane import (
                    bench_plane_scaling,
                )

                RESULT["egress_plane"]["scaling"] = bench_plane_scaling(
                    payload_len=1100, sealed=True,
                    seconds_per_point=1.0, **shape,
                )
            # Scoreboard line: host egress packet walk on the wire shape
            # (clear assembly; the sealed and on-wire variants are beside
            # it and in the wire sections — see BASELINE.md round 6).
            RESULT["host_egress_pps"] = clear.get("pps", 0.0)
        except Exception as e:  # noqa: BLE001
            RESULT["egress_plane_error"] = f"{type(e).__name__}: {e}"
        section_done("egress_plane", t_sec)

    # Section order is by information value under the budget: the two
    # headline device shapes (cfg4, north-star) come before the wire runs,
    # the tiny ladder configs and the mix kernel — so a tight deadline
    # starves trivia, not headlines.

    # -- fleet traffic twin (capacity/SLO envelope) -----------------------
    # Deterministic production-shaped load (runtime/traffic_twin): diurnal
    # churn + flash crowd + rolling drain replayed across a 2-node bus,
    # swept over >= 4 offered-load multipliers. In this process (it holds
    # the chip; a child could not): the twin drives virtual time through
    # the full admission → governor → plane → egress stack, so it yields
    # robustness counts (admission rate, audio continuity, rung residency,
    # recovery ticks), not rates. The partial curve is kept after every
    # load step, so a deadline kill loses at most the step in flight.
    if section_ok("fleet_twin", 90):
        from livekit_server_tpu.runtime import traffic_twin

        t_sec = time.perf_counter()
        sc = traffic_twin.Scenario.standard(seed=20, ticks=60)
        traffic_twin.validate_scenario(sc)
        loads = (0.5, 1.0, 2.0, 4.0)

        def on_step(partial):
            RESULT["fleet_twin"] = {"seed": sc.seed, "loads": list(loads),
                                    "steps": partial, "partial": True}
            emit()

        RESULT["fleet_twin"] = asyncio.run(traffic_twin.capacity_curve(
            sc, loads, nodes=2,
            plane={"rooms": 16, "tracks_per_room": 4, "pkts_per_track": 8,
                   "subs_per_room": 4, "tick_ms": 10},
            log=lambda m: print(m, file=sys.stderr, flush=True),
            on_step=on_step,
        ))
        section_done("fleet_twin", t_sec)

    # -- BASELINE.md ladder (device throughput) ---------------------------
    ladder = {
        "cfg1_1room_2p_audio": (
            plane.PlaneDims(1, 2, 8, 2),
            synth.TrafficSpec(video_tracks=0, audio_tracks=2, tick_ms=20),
            25,
        ),
        "cfg2_1room_50p_audio": (
            plane.PlaneDims(1, 50, 8, 50),
            synth.TrafficSpec(video_tracks=0, audio_tracks=50, tick_ms=20),
            25,
        ),
        "cfg3_1room_25p_vp8_simulcast": (
            plane.PlaneDims(1, 25, 16, 25),
            synth.TrafficSpec(video_tracks=25, audio_tracks=0, tick_ms=20,
                              video_kbps=3000),
            25,
        ),
        "cfg4_1krooms_10p_mixed_svc": (
            plane.PlaneDims(1024, 10, 8, 10),
            synth.TrafficSpec(video_tracks=2, audio_tracks=8, tick_ms=20,
                              video_kbps=1500, svc=True),
            40,
        ),
    }
    configs = RESULT.setdefault("configs", {})

    def run_ladder(name):
        d, s, est = ladder[name]
        if not section_ok(name, est):
            return
        t_sec = time.perf_counter()
        try:
            r = device_bench(d, s, ticks=15, warmup=3)
            configs[name] = r["fwd_writes_per_s"]
            configs[name + "_tick_ms"] = r["device_tick_ms"]
        except Exception as e:  # noqa: BLE001
            configs[name] = f"error: {type(e).__name__}"
        section_done(name, t_sec)

    # cfg4 first: it is the ladder's load-bearing rung.
    run_ladder("cfg4_1krooms_10p_mixed_svc")
    RESULT["cfg5_note"] = "multi-node sharding validated by dryrun_multichip"

    # -- device tick at the WIRE shape ------------------------------------
    # The device step alone at the wire bench's 32-room shape, beside the
    # wire section's whole-loop numbers.
    if section_ok("wire_shape_tick", 30):
        t_sec = time.perf_counter()
        try:
            r = device_bench(
                plane.PlaneDims(32, 8, 8, 6),
                synth.TrafficSpec(video_tracks=4, audio_tracks=4, tick_ms=5,
                                  video_kbps=3000),
                ticks=50, warmup=5,
            )
            RESULT["wire_shape_device_tick_ms"] = r["device_tick_ms"]
        except Exception as e:  # noqa: BLE001
            RESULT["wire_shape_error"] = f"{type(e).__name__}"
        section_done("wire_shape_tick", t_sec)

    # -- north-star tick: FULL 10k-rooms × 50-subs plane on ONE chip ------
    # (BASELINE target is 10k×50 on v5e-8; room-sharding divides by mesh
    # size, so single-chip-tick/8 estimates per-chip cost on the pod.)
    if section_ok("northstar", 80):
        t_sec = time.perf_counter()
        try:
            d = plane.PlaneDims(10240, 8, 16, 50)
            s = synth.TrafficSpec(video_tracks=2, audio_tracks=6, tick_ms=20,
                                  video_kbps=1500, svc=True)
            r = device_bench(d, s, ticks=5, warmup=1)
            RESULT["northstar_10240rooms_50subs_tick_ms"] = r["device_tick_ms"]
            RESULT["mem_1k_rooms_50subs_ok"] = True  # 10k×50 subsumes it
        except Exception as e:  # noqa: BLE001
            RESULT["northstar_error"] = f"{type(e).__name__}"
            # 10k failing says nothing about 1k×50 — measure the smaller
            # feasibility claim independently before reporting False.
            try:
                d1 = plane.PlaneDims(1024, 8, 16, 50)
                s1 = synth.TrafficSpec(video_tracks=2, audio_tracks=6,
                                       tick_ms=20)
                device_bench(d1, s1, ticks=2, warmup=1)
                RESULT["mem_1k_rooms_50subs_ok"] = True
            except Exception as e1:  # noqa: BLE001
                RESULT["mem_1k_rooms_50subs_ok"] = False
                RESULT["mem_error"] = f"{type(e1).__name__}"
        section_done("northstar", t_sec)

    # -- real-time wire bench ---------------------------------------------
    # Shape within the kernel UDP path's capacity: 32 rooms × 6 subs
    # ≈ 280k wire pps (the dense primary shape over-subscribes loopback
    # ~10× and would measure socket queueing, not the server).
    if section_ok("wire", 75):
        t_sec = time.perf_counter()
        wire = _run_wire("wire", plane.PlaneDims(32, 8, 8, 6),
                         wire_ticks[0], args.wire_seconds)
        RESULT["p50_wire_ms"] = wire["p50_wire_ms"]
        RESULT["p99_wire_ms"] = wire["p99_wire_ms"]
        # End-to-end (tick-scheduled, socket-backed) egress rate; the
        # isolated packet-walk scoreboard lives in RESULT
        # ["host_egress_pps"] from the egress_plane section.
        RESULT["wire_host_egress_pps"] = wire["host_egress_pps"]
        section_done("wire", t_sec)

    # -- wire bench at 128-room scale -------------------------------------
    # Loopback's sender-inline delivery caps total wire bytes, so scale
    # ROOMS while trimming per-room load (2×500 kbps video + 4 audio × 4
    # subs ≈ 160k wire pps): exercises host ingest/egress + the probe at
    # cfg4-adjacent room/slot counts.
    if section_ok("wire_128rooms", 75):
        t_sec = time.perf_counter()
        wire_big = _run_wire(
            "wire_128rooms", plane.PlaneDims(128, 6, 8, 4),
            wire_ticks[0], args.wire_seconds,
            video_tracks=2, audio_tracks=4, video_kbps=500.0,
        )
        RESULT["p99_wire_128rooms_ms"] = wire_big["p99_wire_ms"]
        section_done("wire_128rooms", t_sec)

    # -- wire-shape ramp: rooms up until the serving loop breaks ----------
    # The per-node capacity claim measured, not extrapolated: run the wire
    # shape at increasing room counts until late ticks exceed 10% of the
    # window or ingest drops exceed 5% — the last clean rung is the "one
    # node serves N rooms of the wire config end-to-end" number
    # (BASELINE.md round 6). Short windows: each rung only has to clear
    # or trip the break thresholds, not produce publication latencies.
    if section_ok("wire_ramp", 120):
        t_sec = time.perf_counter()
        ramp_steps = []
        max_ok = 0
        tick_ramp = wire_ticks[0]
        rungs = [32, 48, 64, 96, 128]
        i = 0
        while i < len(rungs):
            rooms = rungs[i]
            if _remaining() < 35:
                RESULT.setdefault("skipped", {})["wire_ramp_tail"] = (
                    f"budget: stopped before {rooms} rooms"
                )
                break
            w = _run_wire(
                f"wire_ramp_{rooms}_t{tick_ramp}",
                plane.PlaneDims(rooms, 8, 8, 6),
                tick_ramp, min(args.wire_seconds, 4.0),
            )
            ticks_seen = max(w["ticks"], 1)
            late_pct = round(100.0 * w["late_ticks"] / ticks_seen, 1)
            step = {
                "rooms": rooms,
                "tick_ms": tick_ramp,
                "late_pct": late_pct,
                "ingest_dropped_pct": w["ingest_dropped_pct"],
                "wire_out_pps": w["wire_out_pps"],
                "host_egress_pps": w["host_egress_pps"],
            }
            ramp_steps.append(step)
            RESULT["wire_ramp"] = {
                "steps": ramp_steps, "max_rooms_ok": max_ok,
                "tick_ms": tick_ramp,
            }
            emit()
            if late_pct > 10.0 or w["ingest_dropped_pct"] > 5.0:
                # Where the first rung already breaks on tick lateness,
                # relax once to the 20 ms tick — same traffic — and
                # re-measure the same rung, so the ramp reports the
                # serving ceiling rather than the tick deadline.
                if max_ok == 0 and tick_ramp < 20:
                    tick_ramp = 20
                    continue
                break
            max_ok = rooms
            i += 1
        RESULT["wire_ramp"] = {
            "steps": ramp_steps, "max_rooms_ok": max_ok, "tick_ms": tick_ramp,
        }
        section_done("wire_ramp", t_sec)

    # -- ladder configs 1-3 (small shapes) --------------------------------
    run_ladder("cfg1_1room_2p_audio")
    run_ladder("cfg2_1room_50p_audio")
    run_ladder("cfg3_1room_25p_vp8_simulcast")

    # -- paged capacity at a realistic room-size distribution -------------
    # The dense plane charges every room the worst-case [T, K, S] slab;
    # the paged plane charges the page grid the room actually covers.
    # Sample a production-shaped population (80% rooms ≤4 participants,
    # 15% ≤10, 5% the 50-participant north star; each participant = one
    # published track + one subscriber), drive a REAL RoomPager over a
    # fixed page pool, and report rooms-per-chip at EQUAL HBM both ways.
    # Pure host math — no device time.
    if section_ok("paged_capacity", 10):
        t_sec = time.perf_counter()
        try:
            from livekit_server_tpu.models import plane as plane_model
            from livekit_server_tpu.runtime.pager import RoomPager
            from livekit_server_tpu.runtime.slots import CapacityError

            T_MAX, S_MAX, TP, SP = 64, 64, 4, 8  # covers the 50-p room
            POOL = 1024

            def _tree_bytes(tree) -> int:
                import jax

                return int(sum(
                    np.asarray(leaf).nbytes for leaf in jax.tree.leaves(tree)
                ))

            page_bytes = _tree_bytes(
                plane_model.init_state(plane_model.PlaneDims(1, TP, args.pkts, SP))
            )
            dense_room_bytes = _tree_bytes(
                plane_model.init_state(
                    plane_model.PlaneDims(1, T_MAX, args.pkts, S_MAX)
                )
            )

            rng = np.random.default_rng(9)

            def _sample_room() -> int:
                u = rng.random()
                if u < 0.80:
                    return int(rng.integers(2, 5))
                if u < 0.95:
                    return int(rng.integers(5, 11))
                return 50

            pager = RoomPager(rooms=POOL, tracks=T_MAX, subs=S_MAX,
                              tpage=TP, spage=SP, pool_pages=POOL)
            admitted = 0
            hist = {"le4": 0, "le10": 0, "p50": 0}
            while True:
                p = _sample_room()
                try:
                    pager.alloc_room(admitted, tracks=p, subs=p)
                except CapacityError:
                    break
                admitted += 1
                hist["le4" if p <= 4 else "le10" if p <= 10 else "p50"] += 1
            st = pager.stats()
            pool_bytes = POOL * page_bytes
            dense_rooms = pool_bytes // dense_room_bytes
            ratio = round(admitted / max(dense_rooms, 1), 1)
            hbm_bytes = int(16e9 * 0.9)  # v5e chip, 90% usable for state
            RESULT["paged_capacity"] = {
                "distribution": "80% 2-4p / 15% 5-10p / 5% 50p (seed 9)",
                "pool_pages": POOL,
                "page_bytes": page_bytes,
                "dense_room_bytes": dense_room_bytes,
                "rooms_admitted_paged": admitted,
                "rooms_equal_hbm_dense": int(dense_rooms),
                "room_mix": hist,
                "pages_mapped": st["pages_mapped"],
                "internal_slack_pages": st["internal_slack"],
                "fragmentation_ratio": st["fragmentation_ratio"],
            }
            RESULT["paged_vs_dense_rooms_ratio"] = ratio
            RESULT["rooms_per_chip_realistic"] = int(
                hbm_bytes / pool_bytes * admitted
            )
        except Exception as e:  # noqa: BLE001
            RESULT["paged_capacity_error"] = f"{type(e).__name__}: {e}"
        section_done("paged_capacity", t_sec)

    # -- ragged pooled tick: pay compute only for live pages --------------
    # The fused live-extent tick (ops/paged_kernel behind models/paged
    # paged_plane_tick_fused) schedules one grid step per LIVE page; the
    # stock pooled tick charges the full pool every tick. Fill a pool at
    # the same 80/15/5 distribution, time the fused tick at full
    # occupancy, release half the rooms, time again: work should track
    # live pages, not pool size.
    if section_ok("paged_kernel", 120):
        t_sec = time.perf_counter()
        try:
            import jax
            import jax.numpy as jnp

            from livekit_server_tpu.models import paged
            from livekit_server_tpu.models import plane as plane_model
            from livekit_server_tpu.runtime.pager import RoomPager
            from livekit_server_tpu.runtime.slots import CapacityError

            T_MAX, S_MAX, TP, SP, K = 64, 64, 4, 8, 8
            POOL = 512
            dims = paged.PagedDims(rooms=POOL, tracks=T_MAX, pkts=K,
                                   subs=S_MAX, tpage=TP, spage=SP,
                                   pool_pages=POOL)
            rng = np.random.default_rng(9)

            def _sample_room() -> int:
                u = rng.random()
                if u < 0.80:
                    return int(rng.integers(2, 5))
                if u < 0.95:
                    return int(rng.integers(5, 11))
                return 50

            pager = RoomPager(rooms=POOL, tracks=T_MAX, subs=S_MAX,
                              tpage=TP, spage=SP, pool_pages=POOL)
            admitted: list[int] = []
            misses = 0
            while misses < 5:
                p = _sample_room()
                try:
                    pager.alloc_room(len(admitted), tracks=p, subs=p)
                except CapacityError:
                    misses += 1
                    continue
                admitted.append(len(admitted))

            def _snap():
                table = paged.PageTable(
                    rooms_pages=jnp.asarray(pager.rooms_pages),
                    tmembers=jnp.asarray(pager.tmembers),
                    pg_room=jnp.asarray(pager.pg_room),
                    pg_tp=jnp.asarray(pager.pg_tp),
                    pg_sp=jnp.asarray(pager.pg_sp),
                )
                live = np.nonzero(pager.pg_room >= 0)[0].astype(np.int32)
                nl = 1 << max(len(live) - 1, 1).bit_length()
                rows = np.concatenate(
                    [live, np.repeat(live[:1], nl - len(live))]
                ).astype(np.int32)
                inv = np.zeros(POOL, np.int32)
                inv[live] = np.arange(len(live), dtype=np.int32)
                return table, live, rows, inv

            def _inputs(salt: int):
                r = np.random.default_rng(100 + salt)
                P = POOL
                pk = (P, TP, K)
                ii = lambda lo, hi, sh: jnp.asarray(  # noqa: E731
                    r.integers(lo, hi, sh), jnp.int32)
                bb = lambda pr, sh: jnp.asarray(r.random(sh) < pr)  # noqa: E731
                ff = lambda lo, hi, sh: jnp.asarray(  # noqa: E731
                    r.uniform(lo, hi, sh), jnp.float32)
                return plane_model.TickInputs(
                    sn=ii(0, 65536, pk), ts=ii(0, 1 << 30, pk),
                    layer=ii(0, 3, pk), temporal=ii(0, 4, pk),
                    keyframe=bb(0.2, pk), layer_sync=bb(0.3, pk),
                    begin_pic=bb(0.4, pk), end_frame=bb(0.4, pk),
                    pid=ii(0, 100, pk), tl0=ii(0, 100, pk),
                    keyidx=ii(0, 30, pk), size=ii(40, 1200, pk),
                    frame_ms=ii(0, 20, pk), audio_level=ii(0, 127, pk),
                    arrival_rtp=ii(0, 1 << 28, pk),
                    ts_jump=jnp.zeros(pk, jnp.int32), valid=bb(0.8, pk),
                    estimate=ff(1e5, 5e6, (P, SP)),
                    estimate_valid=bb(0.5, (P, SP)),
                    nacks=ff(0, 3, (P, SP)), pub_rtt_ms=ff(0, 80, (P, TP)),
                    fb_delay_ms=ff(0, 30, (P, SP)),
                    fb_recv_bps=ff(1e5, 4e6, (P, SP)),
                    fb_valid=bb(0.6, (P, SP)), fb_enabled=bb(0.8, (P, SP)),
                    sub_reset=jnp.zeros((P, SP), bool),
                    pad_num=jnp.zeros((P, SP), jnp.int32),
                    pad_track=jnp.full((P, SP), -1, jnp.int32),
                    tick_ms=jnp.asarray(10, jnp.int32),
                    roll_quality=jnp.asarray(0, jnp.int32),
                )

            inputs = [_inputs(s) for s in range(6)]

            def _time_fused(table, rows, inv):
                tick = jax.jit(lambda s, i: paged.paged_plane_tick_fused(
                    s, i, table, rows, inv))
                st = plane_model.init_state(dims.pooled())
                st, out = tick(st, inputs[0])
                jax.block_until_ready(out)
                t0 = time.perf_counter()
                for inp in inputs[1:]:
                    st, out = tick(st, inp)
                jax.block_until_ready(out)
                return round(
                    (time.perf_counter() - t0) / (len(inputs) - 1) * 1e3, 3)

            table_f, live_f, rows_f, inv_f = _snap()
            ms_full = _time_fused(table_f, rows_f, inv_f)

            for r in admitted[::2]:
                pager.release_room(r)
            table_h, live_h, rows_h, inv_h = _snap()
            ms_half = _time_fused(table_h, rows_h, inv_h)

            # Flat-cost reference: the stock pooled tick at the same pool.
            stock = jax.jit(lambda s, i: paged.paged_plane_tick(
                s, i, table_f))
            st = plane_model.init_state(dims.pooled())
            st, out = stock(st, inputs[0])
            jax.block_until_ready(out)
            t0 = time.perf_counter()
            for inp in inputs[1:]:
                st, out = stock(st, inp)
            jax.block_until_ready(out)
            ms_stock = round(
                (time.perf_counter() - t0) / (len(inputs) - 1) * 1e3, 3)

            RESULT["paged_kernel"] = {
                "distribution": "80% 2-4p / 15% 5-10p / 5% 50p (seed 9)",
                "mode": "pallas",
                "pool_pages": POOL,
                "live_pages_full": int(len(live_f)),
                "grid_steps_full": int(len(rows_f)),
                "tick_ms_full": ms_full,
                "live_pages_half": int(len(live_h)),
                "grid_steps_half": int(len(rows_h)),
                "tick_ms_half": ms_half,
                "stock_tick_ms": ms_stock,
                "half_over_full_work_ratio": round(
                    ms_half / max(ms_full, 1e-9), 3),
            }
            RESULT["paged_kernel_tick_ms"] = ms_full
        except Exception as e:  # noqa: BLE001
            RESULT["paged_kernel_error"] = f"{type(e).__name__}: {e}"
        section_done("paged_kernel", t_sec)

    # -- batched audio mix (ops/mix — BASELINE config 2's MCU seat) -------
    # G.711 decode + active-speaker einsum mix + µ-law re-encode at the
    # 1-room × 50-participant shape, all 50 subscribers mixed.
    if section_ok("audio_mix", 25):
        t_sec = time.perf_counter()
        try:
            import jax.numpy as jnp

            from livekit_server_tpu.ops import mix as mix_ops

            Rm, Tm, Sm, Nm = 1, 50, 50, 960  # 20 ms @ 48 kHz
            rngm = np.random.default_rng(2)

            @jax.jit
            def mix_step(payload, codec, level, active, sub_track, gain):
                pcm = mix_ops.decode_tick(payload, codec)
                out = mix_ops.mix_tick(pcm, level, active, sub_track, gain)
                return mix_ops.encode_ulaw(out)

            # Distinct payloads per call, as ticks bring.
            margs = [
                (
                    jnp.asarray(rngm.integers(0, 256, (Rm, Tm, Nm)), jnp.uint8),
                    jnp.zeros((Rm, Tm), jnp.int32),
                    jnp.asarray(rngm.random((Rm, Tm)), jnp.float32),
                    jnp.asarray(rngm.random((Rm, Tm)) < 0.5),
                    jnp.asarray(np.arange(Sm)[None, :] % Tm, jnp.int32),
                    jnp.ones((Rm, Tm), jnp.float32),
                )
                for _ in range(17)
            ]
            out = mix_step(*margs[0])
            jax.block_until_ready(out)
            t0 = time.perf_counter()
            trials = 16
            for i in range(trials):
                out = mix_step(*margs[1 + i])
            int(np.asarray(out)[0, 0, 0])
            RESULT["audio_mix_50p_tick_ms"] = round(
                (time.perf_counter() - t0) / trials * 1000.0, 3
            )
        except Exception as e:  # noqa: BLE001
            RESULT["audio_mix_error"] = f"{type(e).__name__}"
        section_done("audio_mix", t_sec)

    # -- batched audio mix at the 1000-room MCU shape ---------------------
    # runtime/mixer.py's device path (_device_mix) batches every enabled
    # room into one presence/self-exclusion einsum once the per-frame
    # room count crosses DEVICE_MIX_MIN_ROOMS. Time that exact
    # contraction at 1000 rooms × 4 tracks × 4 subscribers × 20 ms
    # (the small-room population where a per-room host loop stops
    # holding the frame deadline).
    if section_ok("audio_mix_1kroom", 30):
        t_sec = time.perf_counter()
        try:
            import jax.numpy as jnp

            from livekit_server_tpu.runtime.mixer import _device_mix

            Rk, Tk, Sk, Nk = 1000, 4, 4, 960  # 20 ms @ 48 kHz
            rngk = np.random.default_rng(3)
            mixk = _device_mix(Tk, Sk, Nk)
            # Salted per-call args (identical executions can be cached).
            kargs = [
                (
                    jnp.asarray(rngk.integers(
                        -32768, 32768, (Rk, Tk, Nk)), jnp.float32),
                    jnp.asarray(rngk.random((Rk, Tk)) < 0.8),
                    jnp.asarray(rngk.integers(
                        0, Tk + 1, (Rk, Sk)), jnp.int32),
                )
                for _ in range(9)
            ]
            out = mixk(*kargs[0])
            jax.block_until_ready(out)
            t0 = time.perf_counter()
            trials = 8
            for i in range(trials):
                out = mixk(*kargs[1 + i])
            float(np.asarray(out)[0, 0, 0])
            RESULT["audio_mix_1kroom_tick_ms"] = round(
                (time.perf_counter() - t0) / trials * 1000.0, 3
            )
        except Exception as e:  # noqa: BLE001
            RESULT["audio_mix_1kroom_error"] = f"{type(e).__name__}"
        section_done("audio_mix_1kroom", t_sec)

    RESULT["bench_total_s"] = round(time.perf_counter() - _T0, 1)
    emit()
    # Compact scoreboard summary, printed LAST: the driver keeps the final
    # complete JSON line of stdout, and the full RESULT record grew past
    # the point where truncation mid-line was a real failure mode (rounds
    # 4-5 survived only as clipped text). Headline scalars only — the full
    # record is the emit() line right above this one.
    summary = {"summary": True, "device": RESULT["device"]}
    for key in ("metric", "value", "unit", "vs_baseline", "device_tick_ms",
                "host_egress_pps", "wire_host_egress_pps", "p50_wire_ms",
                "p99_wire_ms",
                "northstar_10240rooms_50subs_tick_ms",
                "wire_shape_device_tick_ms", "audio_mix_50p_tick_ms",
                "audio_mix_1kroom_tick_ms", "paged_kernel_tick_ms",
                "rooms_per_chip_realistic", "paged_vs_dense_rooms_ratio",
                "bench_total_s"):
        if key in RESULT:
            summary[key] = RESULT[key]
    if "egress_plane" in RESULT:
        summary["egress_plane"] = RESULT["egress_plane"]
    if "wire_ramp" in RESULT:
        summary["wire_ramp_max_rooms_ok"] = RESULT["wire_ramp"].get(
            "max_rooms_ok", 0
        )
    # Capacity/SLO curve from the fleet traffic twin: one row per
    # offered-load step with the headline robustness SLOs, plus the knee
    # (first load where admission dips below ~100%).
    if "fleet_twin" in RESULT:
        ft = RESULT["fleet_twin"]
        summary["fleet_twin"] = {
            "capacity_knee_load": ft.get("capacity_knee_load"),
            "steps": [
                {
                    "load": s.get("offered_load"),
                    "admission_rate": s.get("admission_rate"),
                    "audio_continuity": s.get("audio_continuity"),
                    "dup_wire_packets": s.get("dup_wire_packets"),
                    "wire_p99_ms": s.get("wire_p99_ms"),
                    "rung_residency": s.get("rung_residency"),
                    "recovery_ticks": s.get("recovery_ticks"),
                }
                for s in ft.get("steps", [])
            ],
        }
    # Sampled wire-latency stage decomposition (flight-recorder plane):
    # p50/p99 per stage from the wire section.
    st = (RESULT.get("wire") or {}).get("stages")
    if st:
        summary["wire_stages"] = {
            s: {"p50_ms": v.get("p50_ms"), "p99_ms": v.get("p99_ms")}
            for s, v in st.items()
        }
    # Recompile watchdog from the wire run: post-warmup XLA
    # compiles during the measurement window (0 = the steady-state tick
    # path never retraced) and the warmup window's total compile time.
    w = RESULT.get("wire") or {}
    if "xla_compiles_post_warmup" in w:
        summary["xla_compiles_post_warmup"] = w["xla_compiles_post_warmup"]
        summary["xla_warmup_compile_ms"] = w.get("xla_warmup_compile_ms")
    if "skipped" in RESULT:
        summary["skipped"] = sorted(RESULT["skipped"])
    sys.stdout.write(json.dumps(summary) + "\n")
    sys.stdout.flush()
    # A section that raised is in the record above and in the exit code:
    # the sections after it still ran, but the run did not succeed.
    failed = sorted(k for k in RESULT if k.endswith("_error"))
    if failed:
        print(f"bench.py: sections failed: {failed}", file=sys.stderr)
        sys.exit(1)


if __name__ == "__main__":
    main()
