"""The client's side of the wire, copied so that it imports nothing of the
server: the HS256 join token (`auth/token.py`), the sealed-frame codec
(`runtime/crypto.py`), the punch magic and transport-wide feedback
(`runtime/udp.py`) and the RTP packets the load is made of.

    frame = 0x01 | key_id(4) | dir(1) | counter(8) | AES-128-GCM(ct + tag)
      nonce = dir(1) | counter(8) | zeros(3);  aad = frame[:14]
"""

from __future__ import annotations

import base64
import ctypes
import errno
import hashlib
import hmac
import json
import struct
import time

from cryptography.exceptions import InvalidTag
from cryptography.hazmat.primitives.ciphers.aead import AESGCM

MAGIC = 0x01
DIR_C2S, DIR_S2C = 0, 1
HEADER_LEN = 14
REPLAY_WINDOW = 1024
PUNCH_REQ = b"LKPUNCH0"
PUNCH_ACK = b"LKPUNCH1"
RTCP_RTPFB, TWCC_FMT = 205, 15
# The stamp every media packet carries in payload bytes the server forwards
# untouched: track uid, packet index, the time it was due (ns, CLOCK_REALTIME).
STAMP = struct.Struct(">IIQ")
# VP8 payload descriptor (X, S; I with a 15-bit picture id, L, T) and the
# payload header's first byte with P=0: every packet is a whole key frame, so
# a subscriber locks on at any packet. The server rewrites the descriptor per
# subscriber; what follows it is payload.
VP8_DESCRIPTOR_LEN = 6


def _b64url(data: bytes) -> str:
    return base64.urlsafe_b64encode(data).rstrip(b"=").decode()


def join_token(api_key: str, api_secret: str, identity: str, room: str,
               ttl_s: int = 6 * 3600) -> str:
    now = int(time.time())
    payload = {"iss": api_key, "nbf": now - 10, "exp": now + ttl_s,
               "video": {"roomJoin": True, "room": room},
               "sub": identity, "jti": identity}
    signing = ".".join(_b64url(json.dumps(part, separators=(",", ":")).encode())
                       for part in ({"alg": "HS256", "typ": "JWT"}, payload))
    sig = hmac.new(api_secret.encode(), signing.encode(), hashlib.sha256).digest()
    return signing + "." + _b64url(sig)


class SealedEndpoint:
    """One side of a participant's media session. `tx_dir` is the direction
    it seals in; it opens the other one, once per counter (RFC 4303 window)."""

    def __init__(self, key_id: int, key: bytes, tx_dir: int = DIR_C2S):
        self.key_id, self.tx_dir, self.rx_dir = key_id, tx_dir, 1 - tx_dir
        self.aead = AESGCM(key)
        self.tx_counter = 0
        self._hi, self._mask = -1, 0

    def seal(self, plaintext: bytes) -> bytes:
        ctr, self.tx_counter = self.tx_counter, self.tx_counter + 1
        tail = bytes([self.tx_dir]) + ctr.to_bytes(8, "big")
        header = bytes([MAGIC]) + self.key_id.to_bytes(4, "big") + tail
        return header + self.aead.encrypt(tail + b"\x00\x00\x00", plaintext, header)

    def open(self, frame: bytes) -> bytes | None:
        """frame → inner datagram; None where it is not a sealed frame of
        the other direction, fails authentication, or is a replay."""
        if len(frame) < HEADER_LEN + 16 or frame[0] != MAGIC or frame[5] != self.rx_dir:
            return None
        try:
            pt = self.aead.decrypt(frame[5:14] + b"\x00\x00\x00",
                                   frame[HEADER_LEN:], frame[:HEADER_LEN])
        except InvalidTag:
            return None
        return pt if self._fresh(int.from_bytes(frame[6:14], "big")) else None

    def _fresh(self, ctr: int) -> bool:
        if ctr > self._hi:
            shift = ctr - self._hi
            self._mask = 1 if shift >= REPLAY_WINDOW else (
                ((self._mask << shift) | 1) & ((1 << REPLAY_WINDOW) - 1))
            self._hi = ctr
            return True
        off = self._hi - ctr
        if off >= REPLAY_WINDOW or self._mask & (1 << off):
            return False
        self._mask |= 1 << off
        return True


def frame_key_id(frame: bytes) -> int:
    return int.from_bytes(frame[1:5], "big")


def frame_counter(frame: bytes) -> int:
    return int.from_bytes(frame[6:14], "big")


def twcc_feedback(sender_ssrc: int, media_ssrc: int,
                  entries: list[tuple[int, int]]) -> bytes:
    """Transport-wide feedback, the server's own FCI: base_ctr(8) |
    base_recv_us(8) | n(2) | pad(2) | n x (ctr_off u16 | recv_delta_us i32),
    for `entries` of (sealed-frame counter, arrival in µs)."""
    base_ctr = min(c for c, _ in entries)
    base_us = min(u for _, u in entries)
    fci = bytearray(base_ctr.to_bytes(8, "big") + base_us.to_bytes(8, "big")
                    + len(entries).to_bytes(2, "big") + b"\x00\x00")
    for c, u in entries:
        fci += (c - base_ctr).to_bytes(2, "big")
        fci += (u - base_us).to_bytes(4, "big", signed=True)
    fci += bytes(-len(fci) % 4)
    return (bytes([0x80 | TWCC_FMT, RTCP_RTPFB])
            + (2 + len(fci) // 4).to_bytes(2, "big")
            + sender_ssrc.to_bytes(4, "big") + media_ssrc.to_bytes(4, "big")
            + bytes(fci))


def rtp_packet(pt: int, sn: int, ts: int, ssrc: int, video: bool,
               body: bytes) -> bytes:
    """One frame in one packet (marker set). `body` starts with the stamp."""
    hdr = bytearray(12)
    hdr[0] = 0x80
    hdr[1] = 0x80 | pt
    hdr[2:4] = (sn & 0xFFFF).to_bytes(2, "big")
    hdr[4:8] = (ts & 0xFFFFFFFF).to_bytes(4, "big")
    hdr[8:12] = ssrc.to_bytes(4, "big")
    if not video:
        return bytes(hdr) + body
    pid = sn & 0x7FFF
    return bytes(hdr) + bytes([0x90, 0xE0, 0x80 | (pid >> 8), pid & 0xFF,
                               sn & 0xFF, 0x20, 0x00]) + body


def parse_rtp(inner: bytes):
    """(pt, marker, sn, ts, ssrc, padding, payload) of an RTP datagram, or
    None for RTCP, a punch ack or anything too short to be RTP."""
    if len(inner) < 12 or inner[0] >> 6 != 2 or 192 <= inner[1] <= 223:
        return None
    off = 12 + 4 * (inner[0] & 0x0F)
    if inner[0] & 0x10:                       # header extension
        if len(inner) < off + 4:
            return None
        off += 4 + 4 * int.from_bytes(inner[off + 2:off + 4], "big")
    padding = bool(inner[0] & 0x20)
    end = len(inner) - (inner[-1] if padding else 0)
    return (inner[1] & 0x7F, bool(inner[1] & 0x80),
            int.from_bytes(inner[2:4], "big"), int.from_bytes(inner[4:8], "big"),
            int.from_bytes(inner[8:12], "big"), padding, inner[off:max(off, end)])


class _Iovec(ctypes.Structure):
    _fields_ = [("iov_base", ctypes.c_void_p), ("iov_len", ctypes.c_size_t)]


class _Msghdr(ctypes.Structure):
    _fields_ = [("msg_name", ctypes.c_void_p), ("msg_namelen", ctypes.c_uint32),
                ("msg_iov", ctypes.POINTER(_Iovec)), ("msg_iovlen", ctypes.c_size_t),
                ("msg_control", ctypes.c_void_p), ("msg_controllen", ctypes.c_size_t),
                ("msg_flags", ctypes.c_int)]


class _Mmsghdr(ctypes.Structure):
    _fields_ = [("msg_hdr", _Msghdr), ("msg_len", ctypes.c_uint)]


class BatchSender:
    """Datagrams of one release of the generator in one `sendmmsg` on a
    connected UDP socket, so that they reach the server as one receive batch
    (a wake-up of its receive path costs: knee 16 rooms batched against 12
    sent alone, PERF.md section 4). One by one where libc has no `sendmmsg`."""

    def __init__(self, sock):
        self.sock, self.fd = sock, sock.fileno()
        try:
            self._sendmmsg = ctypes.CDLL(None, use_errno=True).sendmmsg
            self._sendmmsg.argtypes = [ctypes.c_int, ctypes.POINTER(_Mmsghdr),
                                       ctypes.c_uint, ctypes.c_int]
            self._sendmmsg.restype = ctypes.c_int
        except (OSError, AttributeError):
            self._sendmmsg = None

    def send(self, datagrams: list[bytes]) -> None:
        n = len(datagrams)
        if self._sendmmsg is None or n == 1:
            for d in datagrams:
                self.sock.send(d)
            return
        iov, msgs = (_Iovec * n)(), (_Mmsghdr * n)()
        for i, d in enumerate(datagrams):       # `datagrams` keeps the bytes alive
            iov[i].iov_base = ctypes.cast(ctypes.c_char_p(d), ctypes.c_void_p)
            iov[i].iov_len = len(d)
            msgs[i].msg_hdr.msg_iov = ctypes.pointer(iov[i])
            msgs[i].msg_hdr.msg_iovlen = 1
        done = 0
        while done < n:
            sent = self._sendmmsg(self.fd, ctypes.byref(msgs[done]), n - done, 0)
            if sent < 0:
                if ctypes.get_errno() == errno.EINTR:
                    continue
                raise OSError(ctypes.get_errno(), "sendmmsg")
            done += sent
