"""The client's copy of the wire against the server's own codec."""

import pytest

from benchmarks.client import wire


def test_sealed_frames_open_on_the_server_and_the_reverse():
    from livekit_server_tpu.runtime.crypto import MediaCryptoSession

    key = bytes(range(16))
    client, server = wire.SealedEndpoint(0xABCDEF01, key), MediaCryptoSession(0xABCDEF01, key)
    for n in (0, 1, 80, 907):
        body = bytes(i & 0xFF for i in range(n))
        up = client.seal(body)
        assert server.open(up) == body
        assert server.open(up) is None                 # a replay
        down = server.seal(body)
        assert wire.frame_key_id(down) == 0xABCDEF01
        assert client.open(down) == body
        assert client.open(down) is None
        assert client.open(up) is None                 # its own direction, reflected
    tampered = bytearray(server.seal(b"payload"))
    tampered[-1] ^= 1
    assert client.open(bytes(tampered)) is None


def test_join_token_verifies_with_the_server():
    from livekit_server_tpu.auth.token import verify_token

    claims = verify_token(wire.join_token("k", "secret-secret", "p3", "bench-7"),
                          {"k": "secret-secret"})
    assert claims.identity == "p3" and claims.video.room == "bench-7"
    assert claims.video.room_join


def test_feedback_and_punch_are_the_servers():
    from livekit_server_tpu.runtime import udp

    entries = [(1000, 5_000_000), (1003, 5_000_400), (1001, 4_999_900)]
    assert wire.twcc_feedback(0x42, 0x1234, entries) == udp.build_twcc_feedback(
        0x42, 0x1234, entries)
    assert (wire.PUNCH_REQ, wire.PUNCH_ACK) == (udp.PUNCH_REQ, udp.PUNCH_ACK)


@pytest.mark.parametrize("video", [False, True])
def test_rtp_round_trip(video):
    body = wire.STAMP.pack(7, 9, 123456789) + bytes(range(40))
    pt, marker, sn, ts, ssrc, padding, payload = wire.parse_rtp(
        wire.rtp_packet(96 if video else 111, 70000, 1 << 33, 0xDEADBEEF, video, body))
    assert (pt, marker, sn, ts, ssrc, padding) == (
        96 if video else 111, True, 70000 & 0xFFFF, 0, 0xDEADBEEF, False)
    assert payload.endswith(body)
    assert len(payload) - len(body) == (7 if video else 0)


def test_batch_sender_sends_every_datagram():
    import socket

    rx = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
    rx.bind(("127.0.0.1", 0))
    rx.settimeout(2)
    tx = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
    tx.connect(rx.getsockname())
    sent = [bytes([i]) * (10 + i) for i in range(40)]
    wire.BatchSender(tx).send(sent)
    assert [rx.recv(2048) for _ in sent] == sent
    rx.close()
    tx.close()
