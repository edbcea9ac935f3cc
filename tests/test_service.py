"""Service-layer integration tests over real HTTP + WebSocket.

Reference parity: test/ integration tier (integration_helpers.go
createSingleNodeServer → real server + real WS clients;
singlenode_test.go scenarios: connect, duplicate identity, publisher +
subscriber media, permissions) and roomservice_test.go (admin API).
The in-process server binds a real port; clients are aiohttp WS sessions
speaking the JSON signal protocol + msgpack media frames.
"""

import asyncio
import json

import aiohttp
import msgpack
import pytest

from livekit_server_tpu.auth import AccessToken, VideoGrant
from livekit_server_tpu.config import load_config
from livekit_server_tpu.service.server import create_server

API_KEY, API_SECRET = "testkey", "testsecret"


def make_config(port: int, require_encryption: bool = False, **plane_overrides):
    plane = {"rooms": 4, "tracks_per_room": 4, "pkts_per_track": 4, "subs_per_room": 4,
             "tick_ms": 10} | plane_overrides
    return load_config(
        yaml_text=json.dumps(
            {
                "keys": {API_KEY: API_SECRET},
                "port": port,
                "bind_addresses": ["127.0.0.1"],
                "plane": plane,
                "room": {"empty_timeout_s": 2},
                # Ports offset to avoid cross-test collisions. Most tests
                # keep the legacy cleartext wire; the encrypted-path test
                # opts in to the (production-default) sealed wire.
                "rtc": {
                    "udp_port": port + 1,
                    "tcp_port": port + 2,
                    "require_encryption": require_encryption,
                },
            }
        )
    )


def token(identity: str, room: str, **grant_kw) -> str:
    t = AccessToken(API_KEY, API_SECRET)
    t.identity = identity
    t.grant = VideoGrant(room_join=True, room=room, **grant_kw)
    return t.to_jwt()


def admin_token(room: str = "") -> str:
    """roomAdmin is room-scoped (auth.go EnsureAdminPermission): per-room
    ops need a token whose room claim names the target room."""
    t = AccessToken(API_KEY, API_SECRET)
    t.identity = "admin"
    t.grant = VideoGrant(room_admin=True, room_create=True, room_list=True, room=room)
    return t.to_jwt()


class SignalClient:
    """Minimal test client (test/client/client.go RTCClient analog)."""

    def __init__(self, session: aiohttp.ClientSession, port: int):
        self.session = session
        self.port = port
        self.ws = None
        self.signals: list = []
        self.media: list = []
        self._reader: asyncio.Task | None = None

    async def connect(self, room: str, identity: str, query: str = "", **grant_kw):
        self.ws = await self.session.ws_connect(
            f"ws://127.0.0.1:{self.port}/rtc?access_token="
            f"{token(identity, room, **grant_kw)}{query}"
        )
        self._reader = asyncio.ensure_future(self._read())
        join = await self.wait_for("join")
        return join

    async def _read(self):
        async for msg in self.ws:
            if msg.type == aiohttp.WSMsgType.TEXT:
                self.signals.append(json.loads(msg.data))
            elif msg.type == aiohttp.WSMsgType.BINARY:
                self.media.append(msgpack.unpackb(msg.data, raw=False))

    async def wait_for(self, kind: str, timeout: float = 3.0):
        deadline = asyncio.get_event_loop().time() + timeout
        while asyncio.get_event_loop().time() < deadline:
            for m in self.signals:
                if kind in m:
                    return m[kind]
            await asyncio.sleep(0.01)
        raise TimeoutError(f"no {kind!r} in {self.signals}")

    async def wait_media(self, n: int = 1, timeout: float = 3.0):
        deadline = asyncio.get_event_loop().time() + timeout
        while asyncio.get_event_loop().time() < deadline:
            if len(self.media) >= n:
                return self.media
            await asyncio.sleep(0.01)
        raise TimeoutError(f"only {len(self.media)} media frames")

    async def send_signal(self, kind: str, data: dict):
        await self.ws.send_str(json.dumps({kind: data}))

    async def send_media(self, **frame):
        await self.ws.send_bytes(msgpack.packb(frame))

    async def close(self):
        if self._reader:
            self._reader.cancel()
        if self.ws is not None:
            await self.ws.close()


import contextlib
import socket


@contextlib.asynccontextmanager
async def running_server(configure=None, **plane_overrides):
    """In-process server on a free port (createSingleNodeServer analog).

    An async context manager rather than a pytest fixture: the conftest
    async shim runs coroutine *tests*, not async fixtures. `configure`
    (optional callable) mutates the Config before the server is built.
    """
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    cfg = make_config(port, **plane_overrides)
    if configure is not None:
        configure(cfg)
    srv = create_server(cfg)
    await srv.start()
    try:
        yield srv
    finally:
        await srv.stop(force=True)


@pytest.mark.parametrize("configured", [None, True, False])
def test_allow_pause_config_reaches_the_allocator(configured):
    """rtc.congestion_control.allow_pause (default false, as the reference)
    is the allocator's static flag in the served step: a low bandwidth
    estimate degrades video to its lowest layer and pauses it only where
    the operator said it may."""
    cfg = make_config(0)
    if configured is not None:
        cfg.rtc.congestion_control.allow_pause = configured
    rt = create_server(cfg).room_manager.runtime
    assert rt._bp.allow_pause is bool(configured)


async def test_health_and_validate():
    async with running_server() as server:
        async with aiohttp.ClientSession() as s:
            async with s.get(f"http://127.0.0.1:{server.port}/") as r:
                assert r.status == 200
            async with s.get(
                f"http://127.0.0.1:{server.port}/rtc/validate?access_token={token('a', 'r')}"
            ) as r:
                assert r.status == 200
            async with s.get(
                f"http://127.0.0.1:{server.port}/rtc/validate?access_token=garbage"
            ) as r:
                assert r.status == 401


async def test_rtc_rejects_bad_tokens():
    async with running_server() as server:
        async with aiohttp.ClientSession() as s:
            async with s.get(f"http://127.0.0.1:{server.port}/rtc") as r:
                assert r.status == 401
            t = AccessToken(API_KEY, API_SECRET)
            t.identity = "x"
            t.grant = VideoGrant(room_list=True)  # no roomJoin
            async with s.get(
                f"http://127.0.0.1:{server.port}/rtc?access_token={t.to_jwt()}"
            ) as r:
                assert r.status == 401


async def test_join_publish_subscribe_media():
    """The TestSinglePublisher flow end-to-end over the wire."""
    async with running_server() as server:
        async with aiohttp.ClientSession() as s:
            alice = SignalClient(s, server.port)
            bob = SignalClient(s, server.port)
            join_a = await alice.connect("lobby", "alice")
            assert join_a["participant"]["identity"] == "alice"
            join_b = await bob.connect("lobby", "bob")
            assert [p["identity"] for p in join_b["other_participants"]] == ["alice"]

            # alice announces + publishes an audio track
            await alice.send_signal("add_track", {"cid": "mic", "type": 0, "name": "mic"})
            tp = await alice.wait_for("track_published")
            track_sid = tp["track"]["sid"]

            # first media frame binds the pending track (the reference's
            # OnTrack moment); bob then auto-subscribes
            await alice.send_media(
                cid="mic", sn=99, ts=0, payload=b"bind", audio_level=20, frame_ms=20
            )
            await bob.wait_for("track_subscribed")

            # alice streams 5 packets; bob receives them munged+payload
            # intact. Flow-controlled (wait for each delivery before the
            # next send): under parallel-suite load the tick loop can stall
            # long enough that un-paced sends overflow one tick's K=4
            # packet slots and a frame drops — a harness artifact, not a
            # product property.
            for i in range(5):
                await alice.send_media(
                    cid="mic", sn=100 + i, ts=960 * i, payload=b"opus" + bytes([i]),
                    audio_level=20, frame_ms=20,
                )
                deadline = asyncio.get_event_loop().time() + 8.0
                while not any(m["sn"] == 100 + i for m in bob.media):
                    if asyncio.get_event_loop().time() > deadline:
                        raise TimeoutError(f"sn {100 + i} never delivered")
                    await asyncio.sleep(0.01)
            media = bob.media
            sns = [m["sn"] for m in media]
            assert [s for s in sns if s >= 100][:5] == [100, 101, 102, 103, 104]
            first = next(m for m in media if m["sn"] == 100)
            assert first["payload"] == b"opus\x00"
            assert first["track_sid"] == track_sid

            # speakers fire eventually (alice is loud)
            server.room_manager.sample_traffic()  # open a rate window
            for i in range(5, 40):
                await alice.send_media(
                    cid="mic", sn=100 + i, ts=960 * i, payload=b"x", audio_level=18,
                    frame_ms=20,
                )
                await asyncio.sleep(0.012)
            spk = await bob.wait_for("speakers_changed", timeout=5)
            assert spk["speakers"][0]["sid"] == join_a["participant"]["sid"]

            # Per-participant traffic accounting
            # (participant_traffic_load.go seat): alice published ~35
            # packets inside the sample window — her ingress rate is
            # nonzero and feeds the node packet rate.
            rm = server.room_manager
            rm.sample_traffic()
            traffic = rm.participant_traffic(rm.rooms["lobby"])
            assert traffic["alice"]["rx_pps"] > 0
            assert traffic["alice"]["rx_bps"] > 0
            assert rm.router.local_node.stats.packets_in_per_sec > 0

            await alice.close()
            await bob.close()


async def test_room_service_api():
    async with running_server() as server:
        async with aiohttp.ClientSession() as s:
            hdr = {"Authorization": f"Bearer {admin_token('api-room')}"}
            base = f"http://127.0.0.1:{server.port}/twirp/livekit.RoomService"

            async with s.post(f"{base}/CreateRoom", json={"name": "api-room"}, headers=hdr) as r:
                assert r.status == 200
                room = await r.json()
                assert room["name"] == "api-room"

            async with s.post(f"{base}/ListRooms", json={}, headers=hdr) as r:
                rooms = (await r.json())["rooms"]
                assert "api-room" in [x["name"] for x in rooms]

            # join someone, then admin ops on them
            alice = SignalClient(s, server.port)
            await alice.connect("api-room", "alice")
            async with s.post(
                f"{base}/ListParticipants", json={"room": "api-room"}, headers=hdr
            ) as r:
                parts = (await r.json())["participants"]
                assert [p["identity"] for p in parts] == ["alice"]

            async with s.post(
                f"{base}/UpdateRoomMetadata",
                json={"room": "api-room", "metadata": "hello"},
                headers=hdr,
            ) as r:
                assert (await r.json())["metadata"] == "hello"
            await alice.wait_for("room_update")

            async with s.post(
                f"{base}/RemoveParticipant",
                json={"room": "api-room", "identity": "alice"},
                headers=hdr,
            ) as r:
                assert r.status == 200
            await alice.wait_for("leave")

            async with s.post(f"{base}/DeleteRoom", json={"room": "api-room"}, headers=hdr) as r:
                assert r.status == 200
            await alice.close()

            # non-admin token refused
            async with s.post(
                f"{base}/DeleteRoom",
                json={"room": "x"},
                headers={"Authorization": f"Bearer {token('u', 'x')}"},
            ) as r:
                assert r.status == 403

            # admin of room A must NOT administrate room B
            # (auth.go:140 room-scoped EnsureAdminPermission)
            async with s.post(
                f"{base}/ListParticipants",
                json={"room": "other-room"},
                headers={"Authorization": f"Bearer {admin_token('api-room')}"},
            ) as r:
                assert r.status == 403

            # ...and a roomAdmin token with no room claim scopes to nothing
            async with s.post(
                f"{base}/SendData",
                json={"room": "api-room", "data": "x"},
                headers={"Authorization": f"Bearer {admin_token()}"},
            ) as r:
                assert r.status == 403


async def test_full_room_allows_same_identity_rejoin():
    """max_participants must not count the stale session a same-identity
    rejoin replaces (crash-reconnect without the reconnect flag)."""
    async with running_server() as server:
        async with aiohttp.ClientSession() as s:
            hdr = {"Authorization": f"Bearer {admin_token()}"}
            base = f"http://127.0.0.1:{server.port}/twirp/livekit.RoomService"
            async with s.post(
                f"{base}/CreateRoom",
                json={"name": "capped", "max_participants": 1},
                headers=hdr,
            ) as r:
                assert r.status == 200

            c1 = SignalClient(s, server.port)
            await c1.connect("capped", "alice")
            # a different identity is rejected (leave with JOIN_FAILURE)
            c2 = SignalClient(s, server.port)
            c2.ws = await s.ws_connect(
                f"ws://127.0.0.1:{server.port}/rtc?access_token={token('bob', 'capped')}"
            )
            c2._reader = asyncio.ensure_future(c2._read())
            leave = await c2.wait_for("leave")
            assert leave["reason"] == int(7)  # JOIN_FAILURE
            # same identity rejoins fine; the old session is kicked
            c3 = SignalClient(s, server.port)
            await c3.connect("capped", "alice")
            dup = await c1.wait_for("leave")
            assert dup["reason"] == 2  # DUPLICATE_IDENTITY
            await c1.close()
            await c2.close()
            await c3.close()


async def test_duplicate_identity_over_wire():
    async with running_server() as server:
        async with aiohttp.ClientSession() as s:
            c1 = SignalClient(s, server.port)
            await c1.connect("dup", "alice")
            c2 = SignalClient(s, server.port)
            await c2.connect("dup", "alice")
            leave = await c1.wait_for("leave")
            assert leave["reason"] == 2  # DUPLICATE_IDENTITY
            await c1.close()
            await c2.close()


async def test_metrics_and_debug():
    async with running_server() as server:
        async with aiohttp.ClientSession() as s:
            alice = SignalClient(s, server.port)
            await alice.connect("m", "alice")
            async with s.get(f"http://127.0.0.1:{server.port}/metrics") as r:
                text = await r.text()
                assert "livekit_events_total" in text
            async with s.get(f"http://127.0.0.1:{server.port}/debug/rooms") as r:
                dbg = await r.json()
                assert "m" in dbg["rooms"]
                assert dbg["rooms"]["m"]["participants"] == ["alice"]
            # Twirp request hooks (service/server.go Twirp options): a call
            # through /twirp shows up in the status counter.
            from livekit_server_tpu.auth import AccessToken, VideoGrant

            t = AccessToken(API_KEY, API_SECRET)
            t.grant = VideoGrant(room_list=True)
            hdr = {"Authorization": f"Bearer {t.to_jwt()}"}
            base = f"http://127.0.0.1:{server.port}/twirp/livekit.RoomService"
            async with s.post(f"{base}/ListRooms", json={}, headers=hdr) as r:
                pass
            async with s.get(f"http://127.0.0.1:{server.port}/metrics") as r:
                text = await r.text()
                assert 'livekit_twirp_requests_total{method="ListRooms"' in text
            # §5.1 profiling surfaces.
            async with s.get(f"http://127.0.0.1:{server.port}/debug/tasks") as r:
                assert (await r.json())["count"] > 0
            async with s.get(f"http://127.0.0.1:{server.port}/debug/ticks") as r:
                assert "stats" in await r.json()
            await alice.close()


async def test_spans_at_the_debug_endpoints():
    """Where the spans are served: /debug/rooms (totals, wire stages'
    sums), /debug/ticks (the record's keys), /debug/compiles (start-up
    by phase, which is no span: one record a number)."""
    from livekit_server_tpu.runtime import trace

    async with running_server() as server:
        async with aiohttp.ClientSession() as s:
            url = f"http://127.0.0.1:{server.port}"
            await asyncio.sleep(0.2)  # let some ticks complete
            async with s.get(f"{url}/debug/rooms") as r:
                rooms = await r.json()
            assert set(rooms["spans"]) == set(trace.SPANS)
            for name in ("loop/sleep", "stage/host", "device/call",
                         "device/dispatch", "device/fetch", "fanout/assemble"):
                assert rooms["spans"][name]["n"] >= 1, name
                assert rooms["spans"][name]["busy_s"] > 0.0, name
            assert set(rooms["wire_stages"]) == set(trace.STAGES)
            assert rooms["wire_stages"]["total"] == {"n": 0, "sum_ms": 0.0}
            async with s.get(f"{url}/debug/compiles") as r:
                startup = (await r.json())["startup"]
            phases = ("create_server", "warm_step", "warm_compile", "udp_start")
            assert set(startup) == set(phases) | {"warm_exec_s"}
            for ph in phases:
                assert 0.0 <= startup[ph]["compile_s"] <= startup[ph]["wall_s"]
            assert startup["warm_exec_s"] == pytest.approx(sum(
                startup[ph]["wall_s"] - startup[ph]["compile_s"]
                for ph in phases), abs=2e-3)
            async with s.get(f"{url}/debug/ticks") as r:
                ticks = await r.json()
            assert "recent_tick_s" not in ticks and ticks["recent_ticks"]
            assert {"sleep_ms", "dispatch_delay_ms", "lock_wait_ms", "upload_ms",
                    "device_dispatch_ms", "device_fetch_ms", "handoff_ms",
                    "egress_wait_ms", "send_ms"} <= set(ticks["recent_ticks"][-1])


async def test_trace_and_blackbox_endpoints():
    from livekit_server_tpu.telemetry import trace_export

    async with running_server() as server:
        async with aiohttp.ClientSession() as s:
            alice = SignalClient(s, server.port)
            await alice.connect("fr", "alice")
            await asyncio.sleep(0.15)  # let a few ticks record
            url = f"http://127.0.0.1:{server.port}"
            async with s.get(f"{url}/debug/trace?ticks=32") as r:
                doc = await r.json()
                events = doc["traceEvents"]
                assert events and trace_export.validate(events) == []
                assert {e["name"] for e in events} >= {
                    "stage_host", "device_step", "fan_out"
                }
            # room lane: the join emitted a lifecycle event
            async with s.get(f"{url}/debug/blackbox/fr") as r:
                bb = await r.json()
                assert any(e["event"] == "join" for e in bb["events"])
            async with s.get(f"{url}/debug/blackbox/node") as r:
                assert (await r.json())["room"] == "node"
            async with s.get(f"{url}/debug/blackbox/no-such-room") as r:
                assert r.status == 404
            await alice.close()


async def test_udp_media_through_full_server():
    """Publisher announces a UDP track via signal, streams plain RTP to the
    node's UDP port; subscriber proves address ownership via the punch
    handshake and receives rewritten RTP (the native-transport version of
    TestSinglePublisher)."""
    import socket

    from livekit_server_tpu.runtime.udp import PUNCH_ACK, PUNCH_REQ
    from tests.test_native import rtp_packet

    async with running_server() as server:
        udp_port = server.config.rtc.udp_port
        async with aiohttp.ClientSession() as s:
            alice = SignalClient(s, server.port)
            bob = SignalClient(s, server.port)
            await alice.connect("udp-room", "alice")
            await bob.connect("udp-room", "bob")

            await alice.send_signal(
                "add_track", {"cid": "mic", "type": 0, "name": "m", "transport": "udp"}
            )
            rr = await alice.wait_for("request_response")
            ssrc = rr["udp_media"]["ssrc"]
            track_sid = rr["udp_media"]["track_sid"]
            await bob.wait_for("track_subscribed")

            sub_sock = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
            sub_sock.bind(("127.0.0.1", 0))
            sub_sock.setblocking(False)
            # Request UDP egress: the server answers with a punch id, never
            # trusting a client-supplied address (reflection hardening).
            await bob.send_signal(
                "subscription",
                {"track_sids": [track_sid], "subscribe": True, "udp": True},
            )
            rr = await bob.wait_for("request_response")
            punch_id = rr["udp_punch"]["punch_id"]
            # Prove address ownership from the real receiving socket.
            sub_sock.sendto(
                PUNCH_REQ + int(punch_id).to_bytes(4, "big"), ("127.0.0.1", udp_port)
            )
            deadline = asyncio.get_event_loop().time() + 2
            ack = b""
            while asyncio.get_event_loop().time() < deadline:
                await asyncio.sleep(0.02)
                try:
                    ack, _ = sub_sock.recvfrom(2048)
                    break
                except BlockingIOError:
                    continue
            assert ack == PUNCH_ACK + int(punch_id).to_bytes(4, "big")

            pub_sock = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
            got = []
            for i in range(8):
                pub_sock.sendto(
                    rtp_packet(sn=900 + i, ts=960 * i, ssrc=ssrc, audio_level=25,
                               payload=b"udp-opus" + bytes([i])),
                    ("127.0.0.1", udp_port),
                )
                await asyncio.sleep(0.03)
                while True:
                    try:
                        data, _ = sub_sock.recvfrom(2048)
                        if not (192 <= data[1] <= 223):  # skip RTCP SRs
                            got.append(data)
                    except BlockingIOError:
                        break
            deadline = asyncio.get_event_loop().time() + 3
            while len(got) < 8 and asyncio.get_event_loop().time() < deadline:
                await asyncio.sleep(0.02)
                while True:
                    try:
                        data, _ = sub_sock.recvfrom(2048)
                        if not (192 <= data[1] <= 223):  # skip RTCP SRs
                            got.append(data)
                    except BlockingIOError:
                        break
            assert len(got) == 8, f"got {len(got)} packets"
            import numpy as np

            from livekit_server_tpu.native import rtp as parser

            sns = []
            for data in got:
                out = parser.parse_batch(
                    data, np.asarray([0], np.int32), np.asarray([len(data)], np.int32)
                )[0]
                sns.append(int(out["sn"]))
                off, ln = int(out["payload_off"]), int(out["payload_len"])
                assert data[off : off + ln].startswith(b"udp-opus")
            assert sns == list(range(900, 908))

            # Telemetry depth under load: quality histograms and per-track
            # analytics move once the ~1 s stats window rolls (VERDICT #9;
            # prometheus/packets.go + statsworker.go seats).
            deadline = asyncio.get_event_loop().time() + 4
            seen_hist = seen_stats = False
            while not (seen_hist and seen_stats):
                assert asyncio.get_event_loop().time() < deadline, (
                    "histograms/analytics never moved under load"
                )
                pub_sock.sendto(
                    rtp_packet(sn=950, ts=96000, ssrc=ssrc, audio_level=25,
                               payload=b"late"),
                    ("127.0.0.1", udp_port),
                )
                await asyncio.sleep(0.2)
                async with s.get(f"http://127.0.0.1:{server.port}/metrics") as r:
                    text = await r.text()
                    assert "livekit_forward_latency_ms_count" in text
                    assert "livekit_media_tx_total" in text
                    for line in text.splitlines():
                        if line.startswith("livekit_track_bitrate_kbps_count"):
                            seen_hist = float(line.split()[-1]) > 0
                async with s.get(
                    f"http://127.0.0.1:{server.port}/debug/analytics"
                ) as r:
                    stats = (await r.json())["track_stats"]
                    seen_stats = any(
                        rec["track"] == track_sid and rec["bps"] > 0
                        for rec in stats
                    )
            pub_sock.close()
            sub_sock.close()
            await alice.close()
            await bob.close()


async def test_encrypted_udp_media_through_full_server():
    """Production wire: join hands each participant an AEAD media key over
    the authenticated WS; all UDP media (punch, RTP, egress) is sealed,
    and cleartext datagrams are dropped (require_encryption default)."""
    import base64
    import socket

    import numpy as np

    from livekit_server_tpu.native import rtp as parser
    from livekit_server_tpu.runtime.crypto import MediaCryptoClient
    from livekit_server_tpu.runtime.udp import PUNCH_ACK, PUNCH_REQ
    from tests.test_native import rtp_packet

    async with running_server(require_encryption=True) as server:
        udp_port = server.config.rtc.udp_port
        async with aiohttp.ClientSession() as s:
            alice = SignalClient(s, server.port)
            bob = SignalClient(s, server.port)
            join_a = await alice.connect("sec-room", "alice")
            join_b = await bob.connect("sec-room", "bob")
            for j in (join_a, join_b):
                assert j["media_crypto"]["algo"] == "aes-128-gcm"
            a_crypt = MediaCryptoClient(
                join_a["media_crypto"]["key_id"],
                base64.b64decode(join_a["media_crypto"]["key"]),
            )
            b_crypt = MediaCryptoClient(
                join_b["media_crypto"]["key_id"],
                base64.b64decode(join_b["media_crypto"]["key"]),
            )

            await alice.send_signal(
                "add_track", {"cid": "mic", "type": 0, "name": "m", "transport": "udp"}
            )
            rr = await alice.wait_for("request_response")
            ssrc = rr["udp_media"]["ssrc"]
            track_sid = rr["udp_media"]["track_sid"]
            await bob.wait_for("track_subscribed")

            sub_sock = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
            sub_sock.bind(("127.0.0.1", 0))
            sub_sock.setblocking(False)
            await bob.send_signal(
                "subscription",
                {"track_sids": [track_sid], "subscribe": True, "udp": True},
            )
            rr = await bob.wait_for("request_response")
            punch_id = rr["udp_punch"]["punch_id"]
            # Sealed punch — a cleartext one would be dropped.
            sub_sock.sendto(
                b_crypt.seal(PUNCH_REQ + int(punch_id).to_bytes(4, "big")),
                ("127.0.0.1", udp_port),
            )
            deadline = asyncio.get_event_loop().time() + 2
            ack = None
            while asyncio.get_event_loop().time() < deadline:
                await asyncio.sleep(0.02)
                try:
                    ack = b_crypt.open(sub_sock.recvfrom(2048)[0])
                    break
                except BlockingIOError:
                    continue
            assert ack == PUNCH_ACK + int(punch_id).to_bytes(4, "big")

            pub_sock = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
            got = []
            for i in range(6):
                pub_sock.sendto(
                    a_crypt.seal(
                        rtp_packet(sn=910 + i, ts=960 * i, ssrc=ssrc,
                                   payload=b"sealed" + bytes([i]))
                    ),
                    ("127.0.0.1", udp_port),
                )
                await asyncio.sleep(0.04)
                while True:
                    try:
                        inner = b_crypt.open(sub_sock.recvfrom(4096)[0])
                        if inner is not None and not (192 <= inner[1] <= 223):
                            got.append(inner)
                    except BlockingIOError:
                        break
            deadline = asyncio.get_event_loop().time() + 3
            while len(got) < 6 and asyncio.get_event_loop().time() < deadline:
                await asyncio.sleep(0.02)
                while True:
                    try:
                        inner = b_crypt.open(sub_sock.recvfrom(4096)[0])
                        if inner is not None and not (192 <= inner[1] <= 223):
                            got.append(inner)
                    except BlockingIOError:
                        break
            assert len(got) == 6, f"got {len(got)} packets"
            for i, m in enumerate(got):
                out = parser.parse_batch(
                    m, np.asarray([0], np.int32), np.asarray([len(m)], np.int32)
                )[0]
                assert int(out["sn"]) == 910 + i
                off, ln = int(out["payload_off"]), int(out["payload_len"])
                assert m[off : off + ln] == b"sealed" + bytes([i])

            # Cleartext media is rejected on the secure wire.
            pub_sock.sendto(
                rtp_packet(sn=999, ssrc=ssrc, payload=b"plain"),
                ("127.0.0.1", udp_port),
            )
            await asyncio.sleep(0.05)
            assert server.room_manager.udp.stats["plaintext_drop"] >= 1
            pub_sock.close()
            sub_sock.close()
            await alice.close()
            await bob.close()
