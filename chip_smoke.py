#!/usr/bin/env python3
"""Chip smoke: the served path, once, on the TPU — through the entry
points a user calls.

    python chip_smoke.py              # one chip: default, cfg4, paged phases
    python chip_smoke.py --chips 4    # the room-sharded tick on 4 chips, alone
    python chip_smoke.py --rehearse   # toy sizes, any backend (tests, CPU)

One process: it imports JAX itself, refuses to start unless the first
device is a TPU, builds the real server (`create_server`, as `serve`
does) on loopback ports and plays the clients too — JWT → `/rtc`
WebSocket join → sealed UDP media — then checks what came out against a
plain reckoning of what was sent. `--rehearse` changes the sizes and
drops the platform assertion; the path is the same.

Every phase raises on failure; nothing is caught and carried on. No
time printed here is a result: this is a smoke, not a load test. The
last line of stdout is the JSON the driver reads.
"""

from __future__ import annotations

import argparse
import asyncio
import base64
import json
import selectors
import shutil
import socket
import struct
import sys
import threading
import time

API_KEY, API_SECRET = "smokekey", "smokesecret-smokesecret-smokesecret"
VP8_PT, OPUS_PT = 96, 111

# Three served phases, each the real server behind its loopback ports:
#   default  the `serve` defaults as a user gets them: 64 x 16 x 16 x 32, dense,
#            tick_ms 10, with the 8 rooms this process can also play the
#            clients for at that tick (below);
#   cfg4     BASELINE.json cfg4 width, 1024 x 10 x 8 x 10, dense, at the tick
#            this host loop needs for that width (below);
#   paged    the `serve` defaults with `plane.pager_enabled: true` (page 4x8,
#            pool 1024), ragged kernel on.
#
# Ticks and live rooms are what the chip's host held in my chip runs (PR 25,
# host clock; PERF.md has the numbers, ROADMAP queue A the item), and the smoke
# says so rather than hide it. At the default width an idle tick asks 4.6 ms
# of its 10 ms window, and with 8 live rooms 7 % of the ticks are late (a dozen
# after each 2 s checkpoint of the supervisor) and the governor stayed at 0 in
# 2 runs of 5; with 32 rooms, their 96 clients in this interpreter, it asks
# 8 ms and more, two thirds of the ticks are late and the governor sheds.
# At cfg4 width an idle tick costs 12-17 ms (every one of 1,024 room rows is
# staged and unpacked whether live or not) and the supervisor's 2 s checkpoint
# holds the event loop ~100 ms: the governor refuses every join at 10 ms, sheds
# at 20 ms, and reaches level 1 at 40 ms. The paged server's loaded tick is
# 19-34 ms at 32 live rooms.
DEFAULT_TICK_MS = 10
WIDE_TICK_MS = 80
MEDIA_MS = 40      # one packet per track per 40 ms: 25 pkt/s, video and audio
# 907 B RTP packets at 25 pkt/s: 181 kbit/s of video a subscriber, well above
# the 64 kbit/s floor of the server's delay-based estimator, so the estimator
# and the allocator have a choice to make in every run.
VIDEO_PAYLOAD = 888
# Linux: the kernel stamps each datagram on arrival (ns, else µs resolution)
ARRIVAL_STAMPS = {35: ("SO_TIMESTAMPNS", 1000), 29: ("SO_TIMESTAMP", 1)}
CFG4 = dict(rooms=1024, tracks_per_room=10, pkts_per_track=8, subs_per_room=10)
SERVE_DEFAULT = dict(rooms=64, tracks_per_room=16, pkts_per_track=16,
                     subs_per_room=32)
TOY = dict(rooms=8, tracks_per_room=4, pkts_per_track=16, subs_per_room=4)


def say(msg: str) -> None:
    print(msg, flush=True)


def free_port(kind=socket.SOCK_STREAM) -> int:
    s = socket.socket(socket.AF_INET, kind)
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


# -- the client side -----------------------------------------------------------

class SignalClient:
    """One participant's signal connection (JSON over the /rtc WebSocket)."""

    def __init__(self, session, port: int, room: str, identity: str):
        self.session, self.port = session, port
        self.room, self.identity = room, identity
        self.ws = None
        self.inbox: list[dict] = []
        self._reader = None
        self.crypto = None          # MediaCryptoClient after join
        self.subscribed: list[str] = []

    async def join(self) -> None:
        from livekit_server_tpu.auth import AccessToken, VideoGrant
        from livekit_server_tpu.runtime.crypto import MediaCryptoClient

        t = AccessToken(API_KEY, API_SECRET)
        t.identity = self.identity
        t.grant = VideoGrant(room_join=True, room=self.room)
        self.ws = await self.session.ws_connect(
            f"ws://127.0.0.1:{self.port}/rtc?access_token={t.to_jwt()}"
        )
        self._reader = asyncio.ensure_future(self._read())
        join = await self.take("join")
        mc = join["media_crypto"]
        self.crypto = MediaCryptoClient(mc["key_id"], base64.b64decode(mc["key"]))

    async def _read(self) -> None:
        import aiohttp

        async for msg in self.ws:
            if msg.type == aiohttp.WSMsgType.TEXT:
                self.inbox.append(json.loads(msg.data))

    async def take(self, kind: str, key: str | None = None, timeout: float = 20.0):
        """Pop the first `kind` message (holding `key`, if given)."""
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            for i, m in enumerate(self.inbox):
                if kind in m and (key is None or key in m[kind]):
                    return self.inbox.pop(i)[kind]
            await asyncio.sleep(0.005)
        raise TimeoutError(f"{self.identity}: no {kind!r}/{key!r} signal")

    async def send(self, kind: str, data: dict) -> None:
        await self.ws.send_str(json.dumps({kind: data}))

    async def publish(self, cid: str, video: bool) -> dict:
        await self.send("add_track", {
            "cid": cid, "type": 1 if video else 0, "name": cid,
            "transport": "udp",
        })
        return (await self.take("request_response", "udp_media"))["udp_media"]

    async def close(self) -> None:
        if self._reader is not None:
            self._reader.cancel()
        if self.ws is not None:
            await self.ws.close()


def rtp_packet(pt: int, sn: int, ts: int, ssrc: int, video: bool) -> bytes:
    hdr = bytearray(12)
    hdr[0] = 0x80
    hdr[1] = 0x80 | pt                       # one packet per frame: marker set
    hdr[2:4] = (sn & 0xFFFF).to_bytes(2, "big")
    hdr[4:8] = (ts & 0xFFFFFFFF).to_bytes(4, "big")
    hdr[8:12] = ssrc.to_bytes(4, "big")
    if video:
        # VP8 descriptor (X, I 15-bit pid, L, T), S bit set, and a first
        # payload byte with P=0: every packet is a whole key frame, so a
        # subscriber can lock on at any packet.
        pid = sn & 0x7FFF
        payload = bytes([0x90, 0xE0, 0x80 | (pid >> 8), pid & 0xFF,
                         sn & 0xFF, 0x20, 0x00]) + bytes(VIDEO_PAYLOAD)
    else:
        payload = bytes(80)                  # a 20 ms Opus frame's worth
    return bytes(hdr) + payload


class MediaDrive:
    """Publisher and subscriber sockets, each side on a thread of its own so
    the server's event loop is not the clients' clock. Subscribers share one
    socket per room, as separate clients would each have their own: a whole
    tick's egress on one socket is a burst of several hundred datagrams, and
    what the kernel's receive buffer cannot hold it drops (counted below as
    RcvbufErrors) — loss made by the smoke, not by the server."""

    def __init__(self, udp_port: int, n_rooms: int):
        self.dst = ("127.0.0.1", udp_port)
        self.pub = self._sock()
        self.subs = [self._sock() for _ in range(n_rooms)]
        self.stamp_opt = self._probe_arrival_stamps()
        for s in self.subs:
            if self.stamp_opt:
                s.setsockopt(socket.SOL_SOCKET, self.stamp_opt, 1)
        self.rcvbuf = self.subs[0].getsockopt(socket.SOL_SOCKET, socket.SO_RCVBUF)
        self.sel = selectors.DefaultSelector()
        for s in self.subs:
            s.setblocking(False)
            self.sel.register(s, selectors.EVENT_READ)
        self.frames: list[tuple] = []        # (key_id, sealed | None, opened | None)
        self.clients: dict[int, object] = {}     # key_id → MediaCryptoClient
        self.sock_of: dict[int, socket.socket] = {}   # key_id → its room's socket
        self.fb_ssrc: dict[int, int] = {}        # key_id → an egress SSRC
        self._pending: dict[int, list] = {}      # key_id → [(ctr, recv_us)]
        self._stop = threading.Event()
        self._rx = threading.Thread(target=self._recv_loop, daemon=True)
        self.sent = 0
        self.slipped_ms = 0.0

    @staticmethod
    def _sock() -> socket.socket:
        s = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
        s.bind(("127.0.0.1", 0))
        s.setsockopt(socket.SOL_SOCKET, socket.SO_RCVBUF, 8 << 20)
        s.setsockopt(socket.SOL_SOCKET, socket.SO_SNDBUF, 8 << 20)
        return s

    @classmethod
    def _probe_arrival_stamps(cls) -> int | None:
        """The socket option under which this kernel hands back an arrival
        time with a datagram; None where it offers neither."""
        for opt in ARRIVAL_STAMPS:
            s = cls._sock()
            try:
                s.setsockopt(socket.SOL_SOCKET, opt, 1)
                s.sendto(b"x", s.getsockname())
                s.settimeout(1.0)
                anc = s.recvmsg(16, 64)[1]
                if anc and anc[0][1] == opt:
                    return opt
            except OSError:
                pass
            finally:
                s.close()
        return None

    def start(self) -> None:
        self._rx.start()

    def ready(self, timeout: float):
        """(datagram, arrival in µs) for everything waiting on a subscriber
        socket. The arrival time is the kernel's where it gives one, not
        this thread's: the thread shares the GIL with the server it is
        driving and reads a tick's burst milliseconds after it landed, and
        feedback stamped with those times reads to the server's delay-based
        estimator as a queue building on the path — it then pauses the
        video (seen here on the CPU and on the chip; PERF.md, PR 25)."""
        per_unit = ARRIVAL_STAMPS[self.stamp_opt][1] if self.stamp_opt else 0
        for key, _ in self.sel.select(timeout):
            while True:
                try:
                    data, anc, _, _ = key.fileobj.recvmsg(4096, 64)
                except BlockingIOError:
                    break
                if anc:
                    sec, frac = struct.unpack("ll", anc[0][2])
                    yield data, sec * 1_000_000 + frac // per_unit
                else:
                    yield data, time.time_ns() // 1000

    def _recv_loop(self) -> None:
        """Drain egress; ack sealed-frame counters as transport-wide
        feedback every 100 ms, as a real client's congestion control does
        (without it the server's send-side BWE starves the video). Each
        subscriber acks on a phase of its own, as separate clients would:
        acked all at one instant, 96 feedback frames are ~10 ms of this
        thread holding the GIL and ~10 ms of the server's event loop in
        one piece, every 100 ms, which a 10 ms tick reads as late ticks
        (a quarter to two thirds of them; my chip calls 23-25, PR 25)."""
        from livekit_server_tpu.runtime.udp import build_twcc_feedback

        every = 0.1
        due: dict[int, float] = {}               # key_id → next ack time
        while not self._stop.is_set():
            for f, at_us in self.ready(0.005):
                if len(f) <= 14 or f[0] != 0x01:
                    continue
                kid = int.from_bytes(f[1:5], "big")
                self._pending.setdefault(kid, []).append(
                    (int.from_bytes(f[6:14], "big"), at_us)
                )
                # Frames are opened after the drive (`opened()`), off the
                # server's clock — all but each subscriber's first, whose
                # SSRC the feedback needs. Each frame is opened once: the
                # replay window refuses a second open.
                if kid in self.fb_ssrc:
                    self.frames.append((kid, f, None))
                    continue
                inner = self.clients[kid].open(f)
                self.frames.append((kid, None, inner))
                if (inner is not None and len(inner) >= 12
                        and not 192 <= inner[1] <= 223):
                    self.fb_ssrc[kid] = int.from_bytes(inner[8:12], "big")
                    due[kid] = time.monotonic() + every * (len(due) % 97) / 97
            now = time.monotonic()
            for kid, at in due.items():
                ents = self._pending[kid]
                if at <= now and ents:
                    due[kid] = max(at + every, now)
                    fb = build_twcc_feedback(0x42, self.fb_ssrc[kid], ents)
                    self.sock_of[kid].sendto(self.clients[kid].seal(fb), self.dst)
                    ents.clear()

    def send_schedule(self, schedule: list[list[bytes]], tick_s: float) -> None:
        """Send one list of sealed datagrams per media interval, paced on
        this thread's own clock (blocking — call via asyncio.to_thread).

        An interval's datagrams leave in one sendmmsg (the native library's
        `send_raw`), so they reach the server as one receive batch. Sent one
        by one they arrive as thousands of wake-ups a second, and the server's
        receive path costs about as much for one datagram as for a batch
        (1.7 ms a call here; my CPU profile, PR 25): the rx path alone then
        takes more than the whole tick."""
        import numpy as np

        from livekit_server_tpu import native

        staged = []
        for batch in schedule:
            lens = np.array([len(d) for d in batch], np.int32)
            offs = np.concatenate([[0], np.cumsum(lens[:-1], dtype=np.int64)])
            staged.append((
                np.frombuffer(b"".join(batch), np.uint8), offs, lens,
                np.full(len(batch), 0x7F000001, np.uint32),
                np.full(len(batch), self.dst[1], np.uint16),
            ))
        t0 = time.monotonic()
        for i, (batch, args) in enumerate(zip(schedule, staged)):
            due = t0 + i * tick_s
            delay = due - time.monotonic()
            if delay > 0:
                time.sleep(delay)
            else:
                self.slipped_ms = max(self.slipped_ms, -delay * 1e3)
            if native.egress is not None:
                n = native.egress.send_raw(self.pub.fileno(), *args)
                assert n == len(batch), f"sendmmsg sent {n} of {len(batch)}"
            else:
                for d in batch:
                    self.pub.sendto(d, self.dst)
            self.sent += len(batch)

    def stop(self) -> None:
        self._stop.set()
        self._rx.join(timeout=5)
        self.sel.close()
        for s in (self.pub, *self.subs):
            s.close()

    def opened(self):
        """(key_id, plaintext) of every frame received, in arrival order."""
        for kid, sealed, inner in self.frames:
            yield kid, (inner if sealed is None else self.clients[kid].open(sealed))


# -- one served phase ----------------------------------------------------------

def make_config(plane: dict, tick_ms: int):
    from livekit_server_tpu.config import load_config

    return load_config(yaml_text=json.dumps({
        "keys": {API_KEY: API_SECRET},
        "port": free_port(),
        "bind_addresses": ["127.0.0.1"],
        "plane": dict(plane, tick_ms=tick_ms),
        "rtc": {
            "udp_port": free_port(socket.SOCK_DGRAM),
            "tcp_port": 0,
            "require_encryption": True,      # AEAD on: the production wire
        },
    }))


def udp_counters() -> dict[str, int]:
    """This host's UDP counters (/proc/net/snmp): what the kernel dropped
    for want of socket buffer is loss the loopback made, not the server."""
    try:
        names, values = [ln.split()[1:] for ln in open("/proc/net/snmp")
                         if ln.startswith("Udp:")]
    except (OSError, ValueError):
        return {}
    return dict(zip(names, map(int, values)))


async def http_json(session, port: int, path: str) -> dict:
    async with session.get(f"http://127.0.0.1:{port}{path}") as r:
        assert r.status == 200, (path, r.status)
        return await r.json()


async def tick_report(session, port: int, name: str, when: str, tick_ms: int) -> int:
    """Print the loop's own per-stage split of recent ticks (host clock; a
    reading to diagnose with, not a result) and return the governor level."""
    ticks = (await http_json(session, port, "/debug/ticks"))["recent_ticks"][-40:]
    med = lambda k: sorted(t[k] for t in ticks)[len(ticks) // 2]  # noqa: E731
    level = (await http_json(session, port, "/debug/overload"))["governor"]["level"]
    say(f"[{name}] {when} tick (median of {len(ticks)}, host clock, no result): "
        f"stage {med('stage_ms')} + device call {med('device_ms')} + fan-out "
        f"{med('fanout_ms')} = {med('total_ms')} ms, of which "
        f"{med('work_ms')} not overlapped, in a window of {tick_ms}; "
        f"governor level {level}")
    return level


async def served_phase(name: str, plane: dict, *, tick_ms: int, live_rooms: int,
                       lead_ticks: int, ticks: int, may_shed: bool = False) -> None:
    """Start the server as `serve` does, join `live_rooms` rooms of three
    (a video publisher, an audio publisher, a listener; everyone
    subscribed to everyone else), drive media over sealed UDP, and check
    the egress against what was sent. `may_shed`: the overload governor
    may shed video during the drive; that is printed, not failed."""
    import aiohttp
    import jax

    from livekit_server_tpu.runtime.udp import PUNCH_ACK, PUNCH_REQ
    from livekit_server_tpu.service.server import create_server

    cfg = make_config(plane, tick_ms)
    say(f"[{name}] plane dims {cfg.plane.rooms}r x {cfg.plane.tracks_per_room}t x "
        f"{cfg.plane.pkts_per_track}k x {cfg.plane.subs_per_room}s, tick "
        f"{tick_ms} ms, pager {cfg.plane.pager_enabled}"
        + (f" (page {cfg.plane.pager_tpage}x{cfg.plane.pager_spage}, kernel "
           f"{cfg.plane.paged_kernel})" if cfg.plane.pager_enabled else ""))
    t0 = time.monotonic()
    server = create_server(cfg)
    await server.start()            # warm-compiles the tick, then mark_warm()
    runtime = server.room_manager.runtime
    warm_s = time.monotonic() - t0
    say(f"[{name}] warm-up {warm_s:.2f} s "
        f"({runtime.compile_ledger.snapshot()['xla_compiles_total']} XLA compiles, "
        f"{runtime.compile_ledger.warmup_ms / 1e3:.2f} s compiling)")
    if cfg.plane.pager_enabled:
        say(f"[{name}] runtime {type(runtime).__name__}, ragged kernel "
            f"{'on' if runtime._pk_enabled else 'off'}")

    drive = MediaDrive(cfg.rtc.udp_port, live_rooms)
    udp_before = udp_counters()
    async with aiohttp.ClientSession() as session:
        # -- the idle loop: what a tick costs before anyone has joined -----
        await asyncio.sleep(1.0)
        level = await tick_report(session, cfg.port, name, "idle", tick_ms)
        assert level == 0, "the governor is shedding before any client joined"

        # -- join: JWT → /rtc → tracks → subscriptions → UDP punch ---------
        rooms = []
        for r in range(live_rooms):
            people = [SignalClient(session, cfg.port, f"smoke-{r}", who)
                      for who in ("cam", "mic", "ear")]
            for p in people:
                await p.join()
                drive.clients[p.crypto.key_id] = p.crypto
                drive.sock_of[p.crypto.key_id] = drive.subs[r]
            cam = await people[0].publish("cam", video=True)
            mic = await people[1].publish("mic", video=False)
            for p, want in zip(people, ([mic], [cam], [cam, mic])):
                for _ in want:
                    p.subscribed.append(
                        (await p.take("track_subscribed"))["track_sid"])
                assert sorted(p.subscribed) == sorted(t["track_sid"] for t in want)
                await p.send("subscription", {
                    "track_sids": p.subscribed, "subscribe": True, "udp": True})
                punch = (await p.take("request_response", "udp_punch"))["udp_punch"]
                drive.subs[r].sendto(
                    p.crypto.seal(PUNCH_REQ + int(punch["punch_id"]).to_bytes(4, "big")),
                    drive.dst)
            rooms.append((people, cam["ssrc"], mic["ssrc"]))
        # every punch is acknowledged, sealed, on the subscriber socket
        acks, deadline = set(), time.monotonic() + 10
        while len(acks) < 3 * live_rooms and time.monotonic() < deadline:
            for f, _ in drive.ready(0):
                kid = int.from_bytes(f[1:5], "big")
                inner = drive.clients[kid].open(f)
                if inner is not None and inner[:8] == PUNCH_ACK:
                    acks.add(kid)
            await asyncio.sleep(0.01)
        assert len(acks) == 3 * live_rooms, f"{len(acks)} punch acks"
        say(f"[{name}] joined {live_rooms} rooms x 3 participants over /rtc; "
            f"{2 * live_rooms} tracks published, {len(acks)} UDP subscribers latched")

        # -- media: sealed ahead of time, sent on the publisher thread -----
        # video and audio each 1000/MEDIA_MS pkt/s per track: a rate one
        # Python process can send and receive beside the server it is
        # driving (sealed before the drive, opened after it).
        per_track_lead = lead_ticks * tick_ms // MEDIA_MS
        per_track = ticks * tick_ms // MEDIA_MS
        n_total = per_track_lead + per_track
        schedule: list[list[bytes]] = [[] for _ in range(n_total)]
        for r, (people, v_ssrc, a_ssrc) in enumerate(rooms):
            for who, ssrc, video in ((0, v_ssrc, True), (1, a_ssrc, False)):
                seal = people[who].crypto.seal
                for i in range(n_total):
                    sn = (1000 * r + 7 + i) & 0xFFFF
                    ts = (90 if video else 48) * MEDIA_MS * i
                    schedule[i].append(seal(rtp_packet(
                        VP8_PT if video else OPUS_PT, sn, ts, ssrc, video)))
        pps_in = 2 * live_rooms * 1000 // MEDIA_MS
        say(f"[{name}] offered {pps_in} pkt/s in (RTP packets of "
            f"{12 + 7 + VIDEO_PAYLOAD} B video, {12 + 80} B audio), "
            f"{2 * pps_in} pkt/s out expected; "
            f"lead-in {lead_ticks} ticks, checked window {ticks} ticks")

        before = await http_json(session, cfg.port, "/debug/rooms")
        drive.start()
        await asyncio.to_thread(drive.send_schedule, schedule, MEDIA_MS / 1e3)
        await tick_report(session, cfg.port, name, "loaded", tick_ms)
        # let the pipeline drain: every packet of the window out, or 5 s
        want_frames = 2 * 2 * live_rooms * per_track
        deadline = time.monotonic() + 5
        while time.monotonic() < deadline:
            n0 = len(drive.frames)
            await asyncio.sleep(0.25)
            if len(drive.frames) == n0 and n0 >= want_frames:
                break
        after = await http_json(session, cfg.port, "/debug/rooms")
        compiles = await http_json(session, cfg.port, "/debug/compiles")
        governor = (await http_json(session, cfg.port, "/debug/overload"))["governor"]
        async with session.get(f"http://127.0.0.1:{cfg.port}/metrics") as resp:
            metrics = await resp.text()
        for people, _, _ in rooms:
            for p in people:
                await p.close()
    drive.stop()
    udp_after = udp_counters()
    kernel_drops = {k: udp_after[k] - udp_before[k]
                    for k in ("RcvbufErrors", "SndbufErrors", "InErrors")
                    if k in udp_after}
    say(f"[{name}] kernel UDP drops on this host during the phase: "
        f"{kernel_drops or 'not readable'}; subscriber receive buffer "
        f"{drive.rcvbuf} bytes "
        f"on each of {len(drive.subs)} sockets; arrivals stamped by "
        + (f"the kernel ({ARRIVAL_STAMPS[drive.stamp_opt][0]})" if drive.stamp_opt
           else "the receiving thread (this kernel offers no arrival stamp)"))

    pb, pa = before["plane"], after["plane"]
    d_ticks = pa["ticks"] - pb["ticks"]
    say(f"[{name}] /debug/rooms: ticks {pb['ticks']} -> {pa['ticks']}, "
        f"fwd_packets {pa['fwd_packets']}, late_ticks {pa.get('late_ticks', 0)}, "
        f"ingest_dropped {after['ingest_dropped']}, governor level "
        f"{governor['level']} after {governor['transition_count']} transitions")

    # The governor sheds video only (layer caps, ingress policing, pauses);
    # audio and signalling ride through. Where a phase may be shed, what was
    # shed is printed with the governor's own reasons, and every check that
    # shedding cannot touch still holds.
    shed = max((t["to"] for t in governor["transitions"]), default=0)
    assert may_shed or not shed, (
        "the overload governor shed load during the drive (the tick is too "
        f"short for this host): {governor['transitions']}")
    if shed:
        say(f"[{name}] the overload governor shed video, to level {shed}: "
            f"{list(governor['transitions'])}. Ticks were late on this host at a "
            f"{tick_ms} ms tick (late_ticks above); the video counts below are "
            "what it let through, by design, and are not checked")

    # -- reckoning -------------------------------------------------------------
    # Group what arrived by (subscriber key, egress SSRC): one munged SN
    # space each. Padding probes share a stream's SN space and are not media.
    streams: dict[tuple[int, int], list[tuple[int, int, bool]]] = {}
    for kid, inner in drive.opened():
        assert inner is not None, "a received frame failed to open"
        if 192 <= inner[1] <= 223 or inner[:8] == PUNCH_ACK:
            continue                                           # RTCP / punch
        streams.setdefault((kid, int.from_bytes(inner[8:12], "big")), []).append(
            (int.from_bytes(inner[2:4], "big"), inner[1] & 0x7F,
             bool(inner[0] & 0x20)))
    n_audio = sum(v[0][1] == OPUS_PT for v in streams.values())
    assert n_audio == 2 * live_rooms and (shed or len(streams) == 4 * live_rooms), (
        f"{len(streams)} egress streams ({n_audio} audio), expected "
        f"{4 * live_rooms} (each track to its two subscribed peers)")
    # what shedding kept back: the video window less what came of it
    media_rx = pad_rx = short = 0
    shed_short = 2 * live_rooms * per_track if shed else 0
    for (kid, ssrc), pkts in streams.items():
        sns = [sn for sn, _, _ in pkts]
        # continuity of the subscriber's SN space: unwrap around the first,
        # then every SN from first to last exactly once
        base = sns[0]
        un = sorted(((sn - base + 0x8000) & 0xFFFF) - 0x8000 for sn in sns)
        media = [p for p in pkts if not p[2]]
        pad_rx += len(pkts) - len(media)
        media_rx += len(media)
        if shed and pkts[0][1] == VP8_PT:
            # policed ingress leaves the gaps of the packets it refused
            assert len(set(un)) == len(un), f"sub {kid:#x}: duplicate SNs"
            shed_short -= min(per_track, len(media))
            continue
        assert un == list(range(un[0], un[0] + len(un))), (
            f"sub {kid:#x} ssrc {ssrc:#x}: SN space has gaps or duplicates "
            f"({len(un)} packets over a span of {un[-1] - un[0] + 1}); "
            f"kernel UDP drops {kernel_drops}")
        # the window must be whole: lead-in packets may be missing at the
        # head (video forwards from the first key frame after allocation
        # has set the subscriber's target), nothing after it
        assert len(media) >= per_track, (
            f"sub {kid:#x} ssrc {ssrc:#x} ({'video' if pkts[0][1] == VP8_PT else 'audio'}): "
            f"{len(media)} media packets, the checked window alone sent "
            f"{per_track}; short streams: " + str(sorted(
                (len(v), v[0][1]) for v in streams.values()
                if len(v) < per_track)[:8]))
        short += per_track_lead + per_track - len(media)
    sent_total = drive.sent
    expected_window = 2 * 2 * live_rooms * per_track
    say(f"[{name}] packets in {sent_total} ({2 * live_rooms} tracks x "
        f"{per_track_lead + per_track}), out {media_rx} media + {pad_rx} padding; "
        f"checked window: {expected_window} expected "
        f"(= {2 * live_rooms} tracks x {per_track} packets x 2 peers), "
        + (f"{shed_short} of them video the governor shed, every audio "
           "packet received" if shed else "all received")
        + f"; lead-in shortfall {short} (video waits for its first key frame "
        "after the allocator sets a target; not loss)")
    assert sent_total == 2 * live_rooms * (per_track_lead + per_track)
    assert media_rx >= expected_window - shed_short
    say(f"[{name}] SN space continuous and gap-free on "
        + (f"the {n_audio} audio streams (video was shed)" if shed
           else f"all {len(streams)} (subscriber, track) streams")
        + f"; publisher clock slipped at most {drive.slipped_ms:.1f} ms")

    assert d_ticks >= ticks, f"only {d_ticks} ticks during the drive"
    assert pa["fwd_packets"] > 0
    assert len(after["rooms"]) == live_rooms
    post = compiles["xla_compiles_post_warmup"]
    line = next(ln for ln in metrics.splitlines()
                if ln.startswith("livekit_xla_compiles_post_warmup"))
    say(f"[{name}] compile ledger: {compiles['xla_compiles_total']} total, "
        f"{post} after warm-up ({line.strip()})")
    assert post == 0 and float(line.split()[-1]) == 0.0, (
        "XLA compiled after warm-up", compiles["recent"])
    if cfg.plane.pager_enabled:
        ks = pa.get("paged_kernel_ticks", 0)
        say(f"[{name}] paged kernel ticks {ks}, grid steps "
            f"{pa.get('paged_kernel_steps', 0)}")
        assert (ks > 0) == bool(runtime._pk_enabled)

    stats = jax.devices()[0].memory_stats() or {}
    say(f"[{name}] peak HBM "
        + (f"{stats['peak_bytes_in_use']} bytes" if "peak_bytes_in_use" in stats
           else "not reported by this backend"))
    t0 = time.monotonic()
    await asyncio.wait_for(server.stop(), 60)
    say(f"[{name}] server stopped cleanly in {time.monotonic() - t0:.2f} s")


# -- device comparisons --------------------------------------------------------

def _assert_trees_match(what: str, a, b) -> None:
    """Integer and boolean leaves exact; float leaves to 1e-5 relative
    (two different programs need not round alike on the device)."""
    import jax
    import numpy as np

    la, _ = jax.tree_util.tree_flatten_with_path(a)
    lb = jax.tree.leaves(b)
    assert len(la) == len(lb)
    n_int = n_flt = 0
    for (path, x), y in zip(la, lb):
        x, y = np.asarray(x), np.asarray(y)
        where = f"{what}{jax.tree_util.keystr(path)}"
        assert x.shape == y.shape and x.dtype == y.dtype, where
        assert np.all(np.isfinite(x)) if x.dtype.kind == "f" else True, where
        if x.dtype.kind == "f":
            n_flt += 1
            np.testing.assert_allclose(x, y, rtol=1e-5, atol=1e-5, err_msg=where)
        else:
            n_int += 1
            assert np.array_equal(x, y), f"{where}: integer field differs"
    say(f"[{what}] {n_int} integer/bool leaves exact, {n_flt} float leaves "
        f"within 1e-5")


def _random_inputs(rng, shape_rtk, shape_rs, live=None):
    """Seeded TickInputs; rows outside `live` stay empty."""
    import jax.numpy as jnp
    import numpy as np

    from livekit_server_tpu.models import plane

    R = shape_rtk[0]
    mask = np.ones(R, bool) if live is None else np.isin(np.arange(R), live)
    m3, m2 = mask[:, None, None], mask[:, None]
    ii = lambda lo, hi: rng.integers(lo, hi, shape_rtk).astype(np.int32) * m3  # noqa: E731
    bb = lambda p: (rng.random(shape_rtk) < p) & m3                     # noqa: E731
    fs = lambda lo, hi: (rng.uniform(lo, hi, shape_rs) * m2).astype(np.float32)  # noqa: E731
    bs = lambda p: (rng.random(shape_rs) < p) & m2                      # noqa: E731
    rt = shape_rtk[:2]
    kw = dict(
        sn=ii(0, 65536), ts=ii(0, 1 << 30), layer=ii(0, 3), temporal=ii(0, 4),
        keyframe=bb(0.2), layer_sync=bb(0.3), begin_pic=bb(0.4),
        end_frame=bb(0.4), pid=ii(0, 100), tl0=ii(0, 100), keyidx=ii(0, 30),
        size=ii(40, 1200), frame_ms=ii(0, 20), audio_level=ii(0, 127),
        arrival_rtp=ii(0, 1 << 28), ts_jump=np.zeros(shape_rtk, np.int32),
        valid=bb(0.8), estimate=fs(1e5, 5e6), estimate_valid=bs(0.5),
        nacks=fs(0, 3),
        pub_rtt_ms=(rng.uniform(0, 80, rt) * m2).astype(np.float32),
        fb_delay_ms=fs(0, 30), fb_recv_bps=fs(1e5, 4e6), fb_valid=bs(0.6),
        fb_enabled=bs(0.8), sub_reset=np.zeros(shape_rs, bool),
        pad_num=np.zeros(shape_rs, np.int32),
        pad_track=np.full(shape_rs, -1, np.int32),
        tick_ms=np.int32(10), roll_quality=np.int32(0),
    )
    return plane.TickInputs(**{k: jnp.asarray(v) for k, v in kw.items()})


def _random_ctrl(rng, state, live=None):
    """Seeded publications and subscriptions on the rows in `live`."""
    import jax.numpy as jnp
    import numpy as np

    R, T, S = state.ctrl.subscribed.shape
    mask = np.ones(R, bool) if live is None else np.isin(np.arange(R), live)
    vid = (rng.random((R, T)) < 0.6) & mask[:, None]
    return state._replace(
        meta=state.meta._replace(
            is_video=jnp.asarray(vid),
            is_svc=jnp.asarray((rng.random((R, T)) < 0.3) & vid),
            published=jnp.asarray((rng.random((R, T)) < 0.9) & mask[:, None])),
        ctrl=state.ctrl._replace(
            subscribed=jnp.asarray((rng.random((R, T, S)) < 0.7) & mask[:, None, None]),
            sub_muted=jnp.asarray((rng.random((R, T, S)) < 0.1) & mask[:, None, None])),
    )


def paged_kernel_comparison(seed: int, toy: bool) -> None:
    """`paged_plane_tick_fused` (the ragged Pallas kernel) against the stock
    `paged_plane_tick` on the same seeded pool, table and inputs."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from livekit_server_tpu.models import paged, plane
    from livekit_server_tpu.runtime.pager import RoomPager
    from livekit_server_tpu.runtime.slots import CapacityError

    if toy:
        pd = paged.PagedDims(rooms=4, tracks=4, pkts=4, subs=8,
                             tpage=2, spage=4, pool_pages=16)
    else:
        pd = paged.PagedDims(rooms=64, tracks=16, pkts=16, subs=32,
                             tpage=4, spage=8, pool_pages=1024)
    rng = np.random.default_rng(seed)
    pager = RoomPager(rooms=pd.rooms, tracks=pd.tracks, subs=pd.subs,
                      tpage=pd.tpage, spage=pd.spage, pool_pages=pd.pool_pages)
    for row in range(pd.rooms):       # mixed room sizes; about half the pool live
        n = int(rng.integers(1, max(2, pd.tracks // 2 + 1)))
        try:
            pager.alloc_room(row, tracks=n, subs=int(rng.integers(1, pd.subs // 2 + 1)))
        except CapacityError:
            break
    table = paged.PageTable(
        rooms_pages=jnp.asarray(pager.rooms_pages),
        tmembers=jnp.asarray(pager.tmembers), pg_room=jnp.asarray(pager.pg_room),
        pg_tp=jnp.asarray(pager.pg_tp), pg_sp=jnp.asarray(pager.pg_sp))
    live = np.nonzero(pager.pg_room >= 0)[0].astype(np.int32)
    nl = 1 << max(len(live) - 1, 1).bit_length()
    live_rows = np.concatenate([live, np.repeat(live[:1], nl - len(live))]).astype(np.int32)
    live_inv = np.zeros(pd.pool_pages, np.int32)
    live_inv[live] = np.arange(len(live), dtype=np.int32)

    state = _random_ctrl(rng, plane.init_state(pd.pooled()), live)
    stock = jax.jit(lambda s, i: paged.paged_plane_tick(s, i, table))
    fused = jax.jit(lambda s, i: paged.paged_plane_tick_fused(
        s, i, table, live_rows, live_inv))
    s_a = s_b = state
    P = pd.pool_pages
    for _ in range(3):
        inp = _random_inputs(rng, (P, pd.tpage, pd.pkts), (P, pd.spage), live)
        s_a, o_a = stock(s_a, inp)
        s_b, o_b = fused(s_b, inp)
    jax.block_until_ready((o_a, o_b))
    say(f"[paged-compare] pool {P} pages of {pd.tpage}x{pd.spage}, "
        f"{len(live)} live, {nl} grid steps, 3 ticks, seed {seed}")
    assert int(np.asarray(o_a.fwd_packets).sum()) > 0
    _assert_trees_match("paged-compare state", s_a, s_b)
    _assert_trees_match("paged-compare outputs", o_a, o_b)


def four_chip_phase(seed: int, toy: bool) -> None:
    """`mesh.make_sharded_tick` over four devices at 4 x cfg4 rooms against
    the single-device tick on the same seeded state and inputs."""
    import jax
    import numpy as np

    from livekit_server_tpu.models import plane
    from livekit_server_tpu.parallel.mesh import (
        make_mesh, make_sharded_tick, shard_tree,
    )

    devs = jax.devices()[:4]
    assert len(devs) == 4, f"--chips 4 needs four devices, JAX reports {len(jax.devices())}"
    dims = plane.PlaneDims(32, 4, 4, 4) if toy else plane.PlaneDims(4096, 10, 8, 10)
    rng = np.random.default_rng(seed)
    state = _random_ctrl(rng, plane.init_state(dims))
    inputs = [_random_inputs(rng, (dims.rooms, dims.tracks, dims.pkts),
                             (dims.rooms, dims.subs)) for _ in range(3)]
    say(f"[four-chip] dims {tuple(dims)}, 3 ticks, seed {seed}")

    single = jax.jit(plane.media_plane_tick)
    s1 = jax.device_put(state, devs[0])
    for inp in inputs:
        s1, o1 = single(s1, jax.device_put(inp, devs[0]))
    jax.block_until_ready(o1)

    mesh = make_mesh(devs)
    sharded = make_sharded_tick(mesh)
    s4 = shard_tree(state, mesh)
    for inp in inputs:
        s4, o4 = sharded(s4, shard_tree(inp, mesh))
    jax.block_until_ready(o4)

    # where the state lives: bytes of state shards on each device
    per_dev = {d.id: 0 for d in devs}
    for leaf in jax.tree.leaves(s4):
        for sh in leaf.addressable_shards:
            per_dev[sh.device.id] += sh.data.nbytes
    total = sum(per_dev.values())
    say(f"[four-chip] state bytes per device {per_dev} (total {total})")
    assert min(per_dev.values()) > 0.2 * total, "state is not spread over four devices"
    for d in devs:
        ms = d.memory_stats() or {}
        if "peak_bytes_in_use" in ms:
            say(f"[four-chip] device {d.id} peak HBM {ms['peak_bytes_in_use']} bytes")
    assert int(np.asarray(o1.fwd_packets).sum()) > 0
    _assert_trees_match("four-chip state", s1, s4)
    _assert_trees_match("four-chip outputs", o1, o4)


# -- main ------------------------------------------------------------------------

def native_report() -> None:
    """Which implementation carries parse / munge / egress: the C++
    libraries built from native/*.cpp, or their Python twins."""
    from livekit_server_tpu import native

    have = {
        "parse": bool(getattr(native.rtp, "native", False)),
        "munge": native.munge is not None,
        "egress": native.egress is not None,
    }
    gxx = shutil.which("g++")
    say("native libraries: " + ", ".join(
        f"{k}={'C++' if v else 'Python twin'}" for k, v in have.items())
        + f" (g++ {'at ' + gxx if gxx else 'absent'})")
    if gxx and not all(have.values()):
        raise RuntimeError(
            "a compiler exists but a Python twin carried the run: " + str(have))


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4))
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="toy sizes, no platform assertion (CPU rehearsal)")
    args = ap.parse_args(argv)

    import jax

    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    if not args.rehearse:
        if device["platform"] != "tpu":
            print(f"chip_smoke: no TPU (JAX reports {device}); nothing was run",
                  file=sys.stderr)
            return 2
        if device["count"] != args.chips:
            print(f"chip_smoke: --chips {args.chips} but JAX reports "
                  f"{device['count']} devices", file=sys.stderr)
            return 2

    from livekit_server_tpu.utils.compile_cache import setup_compile_cache

    say(f"jax {jax.__version__}, device {device['kind']} x {device['count']} "
        f"({device['platform']}), compile cache {setup_compile_cache()}")

    if args.chips == 4:
        four_chip_phase(args.seed, toy=args.rehearse)
    else:
        native_report()
        toy = args.rehearse
        paged_plane = dict(TOY, pager_tpage=2, pager_spage=2) if toy else SERVE_DEFAULT
        # (name, plane, tick_ms, live rooms, lead-in ticks, checked ticks):
        # every window is at least 300 ticks on the chip; the rehearsal's are
        # short, and its first tick is 40 ms, which XLA:CPU holds on a loaded
        # host. Only the phase at the default tick may be shed: the
        # supervisor's 2 s checkpoint alone makes a dozen 10 ms ticks late,
        # and on a slow stretch of the shared host that is the governor's 20
        # in a row (3 runs in 5 at 8 rooms; my chip calls 26-29, PR 25).
        for name, plane, tick_ms, rooms, lead, ticks in (
            ("default", TOY if toy else SERVE_DEFAULT,
             4 * DEFAULT_TICK_MS if toy else DEFAULT_TICK_MS,
             3 if toy else 8, 40 if toy else 200, 60 if toy else 1280),
            ("cfg4", TOY if toy else CFG4, WIDE_TICK_MS,
             3 if toy else 32, 20 if toy else 50, 30 if toy else 320),
            ("paged", dict(paged_plane, pager_enabled=True), WIDE_TICK_MS,
             3 if toy else 32, 20 if toy else 50, 30 if toy else 320),
        ):
            asyncio.run(served_phase(
                name, plane, tick_ms=tick_ms, live_rooms=rooms,
                lead_ticks=lead, ticks=ticks, may_shed=name == "default"))
        paged_kernel_comparison(args.seed, toy)

    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
