"""The plain reference of a selective forwarding unit, from the seed, the room
population and the subscriptions alone: what every subscriber must have
opened when the window has closed. It imports nothing of the server and
reads nothing the server made.

The guarantees a configuration's file states, and the number that holds
each to account (every one an exact comparison, limit 0):

  exactly once      `missing`, `duplicated`: every packet due in the window,
                    to every subscribed peer, once
  payload untouched `corrupt`: bytes after the RTP header (and the VP8
                    descriptor, which the server rewrites per subscriber),
                    payload type and marker equal to what was sent
  own SN space      `sn_breaks`: a subscriber's (SSRC) sequence numbers run
                    on without gap or repeat, padding probes included
  TS space          `ts_breaks`: out-timestamps keep the publisher's steps
  nothing else      `stray`: no media a peer is not subscribed to
  sealed            `unsealed`: every datagram on a subscriber's socket is a
                    sealed frame that opens under that subscriber's key
"""

from __future__ import annotations

import numpy as np

from benchmarks.client.wire import STAMP, parse_rtp
from benchmarks.traffic import Plan, Track

NUMBERS = ("missing", "duplicated", "corrupt", "sn_breaks", "ts_breaks",
           "stray", "unsealed")


def window_indices(track: Track, lead_ns: int, window_ns: int) -> range:
    """Indices of the packets due in [lead, lead + window) of the schedule."""
    return range(track.first_index_at(lead_ns),
                 track.first_index_at(lead_ns + window_ns))


def expected_deliveries(plan: Plan, lead_ns: int, window_ns: int) -> int:
    return sum(len(window_indices(t, lead_ns, window_ns)) * (plan.participants - 1)
               for t in plan.tracks)


def vp8_payload(payload: bytes) -> bytes:
    """VP8 payload after its descriptor (RFC 7741 §4.2), whatever its length."""
    if not payload:
        return payload
    off = 1
    if payload[0] & 0x80 and len(payload) > 1:       # X
        ext, off = payload[1], 2
        if ext & 0x80:                               # I
            off += 2 if len(payload) > off and payload[off] & 0x80 else 1
        off += bool(ext & 0x40)                      # L
        off += bool(ext & 0x30)                      # T or K
    return payload[off:]


def read_media(plan: Plan, subscribed_uids: set[int], inner: bytes, origin_ns: int):
    """What one opened datagram is to the reference; `origin_ns` is the
    instant the schedule's offsets count from (the lead-in's start).

    → ("rtcp",) | ("padding", ssrc, sn) | ("stray",) | ("corrupt",) |
      ("media", uid, index, ssrc, sn, ts, due_ns)
    """
    rtp = parse_rtp(inner)
    if rtp is None:
        return ("rtcp",)
    pt, marker, sn, ts, ssrc, padding, payload = rtp
    if padding and not payload:
        return ("padding", ssrc, sn)
    # which track: the video descriptor is the server's to rewrite, so try
    # the payload type's own framing
    for video in (False, True):
        body = vp8_payload(payload)[1:] if video else payload
        if len(body) < STAMP.size:
            continue
        uid, index, due_ns = STAMP.unpack_from(body)
        if uid >= len(plan.tracks):
            continue
        track = plan.tracks[uid]
        if track.video != video or track.pt != pt:
            continue
        if uid not in subscribed_uids:
            return ("stray",)
        if (not marker or due_ns != origin_ns + track.due_offset_ns(index)
                or body != plan.body(track, index, due_ns)):
            return ("corrupt",)
        return ("media", uid, index, ssrc, sn, ts, due_ns)
    return ("corrupt",)


def judge_subscriber(tracks: list[Track], lead_ns: int, window_ns: int,
                     uid: np.ndarray, index: np.ndarray, ssrc: np.ndarray,
                     sn: np.ndarray, ts: np.ndarray,
                     pad_ssrc: np.ndarray, pad_sn: np.ndarray) -> dict[str, int]:
    """One subscriber's media (arrays, one entry a whole packet received, in
    arrival order) and padding probes against what it was due."""
    out = dict.fromkeys(("missing", "duplicated", "sn_breaks", "ts_breaks",
                         "expected", "stray"), 0)
    for track in tracks:
        mine = uid == track.uid
        idx, s, t = index[mine], sn[mine], ts[mine]
        want = window_indices(track, lead_ns, window_ns)
        out["expected"] += len(want)
        counts = np.bincount(idx[(idx >= want.start) & (idx < want.stop)] - want.start,
                             minlength=len(want))
        out["missing"] += int((counts == 0).sum())
        out["duplicated"] += int((counts > 1).sum())
        if not len(idx):
            continue
        ssrcs = np.unique(ssrc[mine])
        # one SSRC a (subscriber, track), and it is nobody else's
        out["stray"] += len(ssrcs) - 1 + int(np.isin(ssrc[~mine], ssrcs).any())
        # the subscriber's SN space: media and padding of this SSRC together,
        # unwrapped round the first, every number from first to last once
        pads = pad_sn[pad_ssrc == ssrcs[0]]
        every = np.concatenate([s, pads]).astype(np.int64)
        un = np.sort(((every - every[0] + 0x8000) & 0xFFFF) - 0x8000)
        out["sn_breaks"] += int((np.diff(un) != 1).sum())
        # TS: out-timestamp minus what the publisher sent is one constant
        sent = (track.ts0 + idx.astype(np.int64) * track.ts_step) & 0xFFFFFFFF
        shift = (t.astype(np.int64) - sent) & 0xFFFFFFFF
        out["ts_breaks"] += int((shift != shift[0]).sum())
    return out
