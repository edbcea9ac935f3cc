"""One client process: the participants of this process's share of the rooms.

    python -m benchmarks.client.worker <spec.json>

It joins every participant over `/rtc` with a JWT, publishes, subscribes and
punches the UDP path, prints `{"ready": ...}`, waits on stdin for the line
`go <t0_ns>` (the window's first instant, CLOCK_REALTIME), then sends the
plan's packets open loop — each when it is due, whatever the server does —
while a second thread takes every datagram off the subscribers' sockets
with the kernel's arrival stamp, opens it, holds it against the reference
and acks sealed-frame counters every 100 ms, each subscriber on a phase of
its own. It never imports JAX or the server's packages.
"""

from __future__ import annotations

import asyncio
import base64
import gc
import json
import selectors
import socket
import struct
import sys
import threading
import time

import aiohttp
import numpy as np

from benchmarks import reference, traffic
from benchmarks.client import wire

# Linux: the kernel stamps each datagram on arrival (ns, else µs resolution);
# gVisor offers SO_TIMESTAMP only.
ARRIVAL_STAMPS = {35: ("SO_TIMESTAMPNS", 1), 29: ("SO_TIMESTAMP", 1000)}
ACK_SENDER_SSRC = 0x42
# A client's pacer: after a stall (of this host, as a rule) the backlog leaves a
# slice of the schedule at a time, at 2.5 times the rate it built up at
# (libwebrtc's pacing factor), not in one burst that no client sends. Each
# packet keeps the instant it was due, so its latency counts the whole wait.
CATCH_UP_SLICE_NS, CATCH_UP_FACTOR = 50_000_000, 2.5
# what is kept of every media packet that arrived whole
RECORD = ("subscriber", "uid", "index", "ssrc", "sn", "ts", "due_ns", "arrival_ns")
DUE, ARRIVAL = RECORD.index("due_ns"), RECORD.index("arrival_ns")


class SignalClient:
    """One participant's signal connection (JSON over the /rtc WebSocket)."""

    def __init__(self, session, spec: dict, room: str, identity: str):
        self.session, self.spec = session, spec
        self.room, self.identity = room, identity
        self.ws = None
        self.inbox: list[dict] = []
        self._reader = None
        self.endpoint: wire.SealedEndpoint | None = None

    async def join(self) -> None:
        token = wire.join_token(self.spec["api_key"], self.spec["api_secret"],
                                self.identity, self.room)
        self.ws = await self.session.ws_connect(
            f"ws://127.0.0.1:{self.spec['port']}/rtc?access_token={token}")
        self._reader = asyncio.ensure_future(self._read())
        mc = (await self.take("join"))["media_crypto"]
        self.endpoint = wire.SealedEndpoint(mc["key_id"], base64.b64decode(mc["key"]))

    async def _read(self) -> None:
        async for msg in self.ws:
            if msg.type == aiohttp.WSMsgType.TEXT:
                self.inbox.append(json.loads(msg.data))

    async def take(self, kind: str, key: str | None = None, timeout: float = 60.0):
        """Pop the first `kind` message (holding `key`, if given)."""
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            for i, m in enumerate(self.inbox):
                if kind in m and (key is None or key in m[kind]):
                    return self.inbox.pop(i)[kind]
            await asyncio.sleep(0.005)
        raise TimeoutError(f"{self.room}/{self.identity}: no {kind!r}/{key!r} signal")

    async def send(self, kind: str, data: dict) -> None:
        await self.ws.send_str(json.dumps({kind: data}))

    async def publish(self, cid: str, video: bool) -> dict:
        await self.send("add_track", {"cid": cid, "type": 1 if video else 0,
                                      "name": cid, "transport": "udp"})
        return (await self.take("request_response", "udp_media"))["udp_media"]

    async def close(self) -> None:
        if self._reader is not None:
            self._reader.cancel()
        if self.ws is not None:
            await self.ws.close()


def udp_socket() -> socket.socket:
    s = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
    s.bind(("127.0.0.1", 0))
    s.setsockopt(socket.SOL_SOCKET, socket.SO_RCVBUF, 8 << 20)
    s.setsockopt(socket.SOL_SOCKET, socket.SO_SNDBUF, 8 << 20)
    return s


def probe_arrival_stamps() -> int | None:
    """The socket option under which this kernel hands back an arrival time
    with a datagram; None where it offers neither."""
    for opt in ARRIVAL_STAMPS:
        s = udp_socket()
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


class Subscriber:
    """One participant as a receiver: its key, its socket, what it is
    subscribed to, and the feedback it owes."""

    def __init__(self, number: int, room: int, participant: int,
                 endpoint: wire.SealedEndpoint, sock: socket.socket,
                 uids: set[int]):
        self.number, self.room, self.participant = number, room, participant
        self.endpoint, self.sock, self.uids = endpoint, sock, uids
        self.fb_ssrc: int | None = None
        self.pending: list[tuple[int, int]] = []
        self.ack_at = 0.0
        self.punched = False


class Drive:
    """The sockets and the two threads of a run."""

    def __init__(self, spec: dict, plan: traffic.Plan, rooms: list[int]):
        self.spec, self.plan, self.rooms = spec, plan, rooms
        self.dst = ("127.0.0.1", spec["udp_port"])
        self.lead_ns = int(spec["lead_in_s"] * traffic.NS)
        self.window_ns = int(spec["seconds"] * traffic.NS)
        self.ack_every = spec["ack_every_ms"] / 1e3
        self.pub = udp_socket()
        self.pub.connect(self.dst)
        # Subscriber sockets: gVisor's rmem_max is 212,992 B, and a tick's
        # egress to every subscriber of every room on one socket overflows it
        # (RcvbufErrors: loss the host made, not the server). The workload's
        # `subscribers_per_socket` says how many of a room's participants
        # share one; without it a room has one socket.
        self.per_sock = int(plan.workload.get("subscribers_per_socket") or plan.participants)
        self.sub_sock = {(r, g): udp_socket() for r in rooms
                         for g in range(-(-plan.participants // self.per_sock))}
        self.stamp_opt = probe_arrival_stamps()
        self.sel = selectors.DefaultSelector()
        for s in self.sub_sock.values():
            if self.stamp_opt:
                s.setsockopt(socket.SOL_SOCKET, self.stamp_opt, 1)
            s.setblocking(False)
            self.sel.register(s, selectors.EVENT_READ)
        self.rcvbuf = next(iter(self.sub_sock.values())).getsockopt(
            socket.SOL_SOCKET, socket.SO_RCVBUF)
        self.subs: list[Subscriber] = []
        self.by_key: dict[int, Subscriber] = {}
        self.publishers: dict[int, tuple[wire.SealedEndpoint, int]] = {}  # uid → (key, ssrc)
        self.t0_ns = 0
        self._stop = threading.Event()
        self._rx = threading.Thread(target=self._recv_loop, daemon=True)
        # what arrived whole, one entry a media packet, in arrival order
        # (rows of RECORD, one column a packet), with room for repeats
        sent = sum(t.first_index_at(self.lead_ns + self.window_ns)
                   for t in plan.tracks if t.room in rooms)
        self.n = 0
        self.rec = np.zeros((len(RECORD), int(sent * (plan.participants - 1) * 1.25) + 4096),
                            np.int64)
        self.pads: list[tuple[int, int, int]] = []       # (subscriber, ssrc, sn)
        self.counts = dict.fromkeys(("unsealed", "corrupt", "stray", "overflow"), 0)
        self.last_rx = 0.0
        self.sent = 0
        self.gen_late_ns = self.gen_send_ns = 0
        self.gen_stalls: list[tuple] = []

    def sock_of(self, room: int, participant: int) -> socket.socket:
        return self.sub_sock[room, participant // self.per_sock]

    def add_subscriber(self, room: int, participant: int,
                       endpoint: wire.SealedEndpoint) -> Subscriber:
        sub = Subscriber(len(self.subs), room, participant, endpoint,
                         self.sock_of(room, participant),
                         {t.uid for t in self.plan.subscribed(room, participant)})
        self.subs.append(sub)
        self.by_key[endpoint.key_id] = sub
        return sub

    # -- receiving -----------------------------------------------------------

    def _arrivals(self, timeout: float):
        """(datagram, arrival ns) for everything waiting on a subscriber
        socket: the kernel's stamp where it gives one, this thread's clock
        (later, and said so in the result) where it gives none."""
        scale = ARRIVAL_STAMPS[self.stamp_opt][1] if self.stamp_opt else 0
        for key, _ in self.sel.select(timeout):
            while True:
                try:
                    data, anc, _, _ = key.fileobj.recvmsg(2048, 64)
                except BlockingIOError:
                    break
                if anc:
                    sec, frac = struct.unpack("ll", anc[0][2])
                    yield data, sec * traffic.NS + frac * scale
                else:
                    yield data, time.time_ns()

    def _recv_loop(self) -> None:
        plan, counts = self.plan, self.counts
        next_ack_check = 0.0
        while not self._stop.is_set():
            for frame, at_ns in self._arrivals(0.002):
                sub = (self.by_key.get(wire.frame_key_id(frame))
                       if len(frame) > wire.HEADER_LEN and frame[0] == wire.MAGIC else None)
                inner = sub.endpoint.open(frame) if sub is not None else None
                if inner is None:
                    counts["unsealed"] += 1
                    continue
                self.last_rx = time.monotonic()
                if inner[:8] == wire.PUNCH_ACK:
                    sub.punched = True
                    continue
                sub.pending.append((wire.frame_counter(frame), at_ns // 1000))
                seen = reference.read_media(plan, sub.uids, inner, self.t0_ns
                                            - self.lead_ns)
                what = seen[0]
                if what == "media":
                    _, uid, index, ssrc, sn, ts, due_ns = seen
                    if sub.fb_ssrc is None:
                        sub.fb_ssrc = ssrc
                        sub.ack_at = time.monotonic() + self.ack_every * (
                            sub.number % 97) / 97
                    if self.n >= self.rec.shape[1]:
                        counts["overflow"] += 1
                        continue
                    self.rec[:, self.n] = (sub.number, uid, index, ssrc, sn, ts, due_ns, at_ns)
                    self.n += 1
                elif what == "padding":
                    self.pads.append((sub.number, seen[1], seen[2]))
                elif what != "rtcp":
                    counts[what] += 1
            now = time.monotonic()
            if now < next_ack_check:
                continue
            next_ack_check = now + 0.004
            for sub in self.subs:
                if sub.fb_ssrc is not None and sub.ack_at <= now and sub.pending:
                    sub.ack_at = max(sub.ack_at + self.ack_every, now)
                    fb = wire.twcc_feedback(ACK_SENDER_SSRC, sub.fb_ssrc, sub.pending)
                    sub.sock.sendto(sub.endpoint.seal(fb), self.dst)
                    sub.pending.clear()

    # -- sending -------------------------------------------------------------

    def send_all(self) -> None:
        """Every packet of this process's tracks, from the schedule's origin
        (lead-in first) to the window's end, each when it is due on the
        realtime clock, whatever the server does; packets due at one instant
        (one release of the batching grid) leave in one `sendmmsg`; a backlog leaves
        in slices (CATCH_UP_*). Blocking: call via a thread."""
        plan, end_ns = self.plan, self.lead_ns + self.window_ns
        events = sorted((t.due_offset_ns(k), t.uid, k)
                        for t in plan.tracks if t.uid in self.publishers
                        for k in range(t.first_index_at(end_ns)))
        origin = self.t0_ns - self.lead_ns
        sender = wire.BatchSender(self.pub)
        late_ns, send_ns, stalls = 0, 0, []
        i, n = 0, len(events)
        while i < n:
            due = origin + events[i][0]
            now = time.time_ns()
            if due > now:
                time.sleep((due - now) / 1e9)
                continue
            horizon = min(now, due + CATCH_UP_SLICE_NS)
            batch = []
            while i < n and origin + events[i][0] <= horizon:
                off, uid, k = events[i]
                t = plan.tracks[uid]
                endpoint, ssrc = self.publishers[uid]
                batch.append(endpoint.seal(wire.rtp_packet(
                    t.pt, t.sn0 + k, t.ts0 + k * t.ts_step, ssrc, t.video,
                    plan.body(t, k, origin + off))))
                i += 1
            sender.send(batch)
            if due >= self.t0_ns:
                late_ns = max(late_ns, now - due)
                took = time.time_ns() - now
                send_ns = max(send_ns, took)
                if took > 20_000_000 or now - due > 20_000_000:
                    stalls.append(((due - self.t0_ns) / 1e9, (now - due) / 1e6, took / 1e6))
            if horizon < now:
                time.sleep(CATCH_UP_SLICE_NS / CATCH_UP_FACTOR / 1e9)
        self.sent = n
        self.gen_late_ns = late_ns
        self.gen_send_ns = send_ns       # the longest seal + sendmmsg of one release
        self.gen_stalls = stalls[:20]    # (s into the window, ms late, ms in the send)

    def start(self) -> None:
        self._rx.start()

    def stop(self) -> None:
        self._stop.set()
        self._rx.join(timeout=10)
        self.sel.close()
        for s in (self.pub, *self.sub_sock.values()):
            s.close()

    # -- the reckoning -------------------------------------------------------

    def reckon(self) -> dict:
        """This process's numbers against the reference."""
        sub, uid, index, ssrc, sn, ts, _, _ = self.rec[:, :self.n]
        pads = np.array(self.pads, np.int64).reshape(-1, 3)
        sums = dict.fromkeys(reference.NUMBERS, 0) | {"expected": 0}
        for s in self.subs:
            mine, my_pads = sub == s.number, pads[pads[:, 0] == s.number]
            got = reference.judge_subscriber(
                self.plan.subscribed(s.room, s.participant), self.lead_ns, self.window_ns,
                uid[mine], index[mine], ssrc[mine], sn[mine], ts[mine],
                my_pads[:, 1], my_pads[:, 2])
            for k, v in got.items():
                sums[k] += v
        for k in ("unsealed", "corrupt", "stray"):
            sums[k] += self.counts[k]
        sums["missing"] += self.counts["overflow"]     # no room to record it: not held
        return sums | {"padding_probes": len(self.pads)}


async def run(spec: dict) -> int:
    workload = spec["workload"]       # the cell's file as the parent read it
    plan = traffic.make_plan(workload, spec["seed"])
    rooms = [r for r in range(plan.rooms) if r % spec["workers"] == spec["worker"]]
    drive = Drive(spec, plan, rooms)
    kinds = workload["tracks"]
    join_t0 = time.time()
    async with aiohttp.ClientSession() as session:
        everyone = []
        for r in rooms:
            people = [SignalClient(session, spec, *plan.identity(r, p))
                      for p in range(plan.participants)]
            for p, person in enumerate(people):
                await person.join()
                drive.add_subscriber(r, p, person.endpoint)
            tracks = plan.room_tracks(r)
            for t in tracks:
                media = await people[t.owner].publish(f"{t.kind}-{t.uid}",
                                                      kinds[t.kind]["kind"] == "video")
                drive.publishers[t.uid] = (people[t.owner].endpoint, media["ssrc"])
            everyone.append(people)
        drive.start()
        for r, people in zip(rooms, everyone):
            for p, person in enumerate(people):
                sids = [(await person.take("track_subscribed"))["track_sid"]
                        for _ in plan.subscribed(r, p)]
                await person.send("subscription", {"track_sids": sids,
                                                   "subscribe": True, "udp": True})
                punch = (await person.take("request_response", "udp_punch"))["udp_punch"]
                drive.sock_of(r, p).sendto(person.endpoint.seal(
                    wire.PUNCH_REQ + int(punch["punch_id"]).to_bytes(4, "big")), drive.dst)
        deadline = time.monotonic() + 30
        while not all(s.punched for s in drive.subs):
            if time.monotonic() > deadline:
                raise TimeoutError(f"{sum(not s.punched for s in drive.subs)} "
                                   "subscribers' punches were never acknowledged")
            await asyncio.sleep(0.01)
        join_t1 = time.time()
        print(json.dumps({"ready": True, "rooms": len(rooms),
                          "participants": len(drive.subs),
                          "tracks": len(drive.publishers)}), flush=True)

        loop = asyncio.get_running_loop()
        line = await loop.run_in_executor(None, sys.stdin.readline)
        if not line.startswith("go "):
            raise RuntimeError(f"expected 'go <t0_ns>', got {line!r}")
        drive.t0_ns = int(line.split()[1])
        # no collector pause inside the window: what is alive now stays, and
        # the run makes no cycles worth collecting
        gc.collect()
        gc.freeze()
        gc.disable()
        await asyncio.to_thread(drive.send_all)
        # An answer that comes late is late, not wrong: wait for every
        # delivery that is due, up to a minute past the close, and stop
        # sooner only once the sockets have been quiet for three seconds.
        expected = sum(
            len(reference.window_indices(t, drive.lead_ns, drive.window_ns))
            for s in drive.subs for t in plan.subscribed(s.room, s.participant))
        closed = time.monotonic()
        while time.monotonic() < closed + 60:
            await asyncio.sleep(0.1)
            whole = int((drive.rec[DUE, :drive.n] >= drive.t0_ns).sum())
            if whole >= expected and time.monotonic() > closed + 0.3:
                break
            if time.monotonic() - max(drive.last_rx, closed) > 3.0:
                break
        drained_s = time.monotonic() - closed
        drive.stop()
        for people in everyone:
            for person in people:
                await person.close()
    sums = drive.reckon()
    # every whole media packet's due time and arrival stamp, for the parent
    np.save(spec["out"] + ".times.npy", drive.rec[[DUE, ARRIVAL], :drive.n])
    result = sums | {
        "worker": spec["worker"], "sent": drive.sent, "received_whole": drive.n,
        "gen_late_ms": drive.gen_late_ns / 1e6, "gen_send_ms": drive.gen_send_ns / 1e6,
        "gen_stalls": drive.gen_stalls, "drained_s": drained_s,
        "join_t0": join_t0, "join_t1": join_t1, "rcvbuf": drive.rcvbuf,
        "arrival_stamp": ARRIVAL_STAMPS[drive.stamp_opt][0] if drive.stamp_opt
        else "receiving thread's clock (the kernel offers no arrival stamp)",
    }
    with open(spec["out"] + ".json", "w") as f:
        json.dump(result, f)
    print(json.dumps({"done": True}), flush=True)
    return 0


def main(argv: list[str]) -> int:
    with open(argv[1]) as f:
        spec = json.load(f)
    sys.setswitchinterval(0.0005)     # the sender must not wait 5 ms for the GIL
    return asyncio.run(run(spec))


if __name__ == "__main__":
    sys.exit(main(sys.argv))
