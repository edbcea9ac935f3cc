"""The plain reference against a hand-made room: a perfect forwarder reads
clean, and each way of breaking a guarantee moves the number that holds it."""

import numpy as np
import pytest

from benchmarks import reference, traffic
from benchmarks.client import wire

WORKLOAD = {
    "live_rooms": 1,
    "room": {"participants": [{"count": 1, "publish": ["camera", "microphone"]},
                              {"count": 2, "publish": ["microphone"]}]},
    "tracks": {
        "camera": {"kind": "video", "pt": 96, "rtp_bytes": 907, "pps": 50, "clock_hz": 90000},
        "microphone": {"kind": "audio", "pt": 111, "rtp_bytes": 92, "pps": 50, "clock_hz": 48000}},
    "pace_ms": 5,
}
LEAD_NS, WINDOW_NS, ORIGIN = 200_000_000, 1_000_000_000, 1_790_000_000 * traffic.NS


def forward(plan, participant, fault=None):
    """What a perfect SFU hands one subscriber: every packet of every track
    not its own, once, on an SSRC and in an SN/TS space of the subscriber's
    own; `fault(track, k, inner)` may return a list to send in its place."""
    inners = []
    for j, t in enumerate(plan.subscribed(0, participant)):
        for k in range(t.first_index_at(LEAD_NS + WINDOW_NS)):
            inner = wire.rtp_packet(
                t.pt, 5000 * (j + 1) + k, t.ts0 + k * t.ts_step + 777 * (j + 1),
                0x1000 + j, t.video, plan.body(t, k, ORIGIN + t.due_offset_ns(k)))
            inners += [inner] if fault is None else fault(t, k, inner)
    return inners


def judge(plan, participant, inners):
    uids = {t.uid for t in plan.subscribed(0, participant)}
    rec, pads, counts = [], [], {"corrupt": 0, "stray": 0}
    for inner in inners:
        seen = reference.read_media(plan, uids, inner, ORIGIN)
        if seen[0] == "media":
            rec.append(seen[1:6])
        elif seen[0] == "padding":
            pads.append(seen[1:])
        elif seen[0] != "rtcp":
            counts[seen[0]] += 1
    rec = np.array(rec, np.int64).reshape(-1, 5)
    pads = np.array(pads, np.int64).reshape(-1, 2)
    got = reference.judge_subscriber(plan.subscribed(0, participant), LEAD_NS,
                                     WINDOW_NS, *rec.T, pads[:, 0], pads[:, 1])
    got["corrupt"] = counts["corrupt"]
    got["stray"] += counts["stray"]
    return got


@pytest.fixture(scope="module")
def plan():
    return traffic.make_plan(WORKLOAD, seed=2**31 + 12345)


def test_the_plan_is_the_room(plan):
    assert (plan.rooms, plan.participants, len(plan.tracks)) == (1, 3, 4)
    assert [t.uid for t in plan.subscribed(0, 0)] == [2, 3]
    assert [t.uid for t in plan.subscribed(0, 2)] == [0, 1, 2]
    # 4 tracks x 50 packets in the window's second, to the 2 others each
    assert reference.expected_deliveries(plan, LEAD_NS, WINDOW_NS) == 4 * 50 * 2
    assert all(t.due_offset_ns(k) % 5_000_000 == 0 for t in plan.tracks for k in range(3))


def test_seeds_deal_the_same_work(plan):
    other = traffic.make_plan(WORKLOAD, seed=7)
    assert sorted(t.phase_ns for t in other.tracks) == sorted(t.phase_ns for t in plan.tracks)
    assert [t.phase_ns for t in other.tracks] != [t.phase_ns for t in plan.tracks]


def test_a_perfect_forwarder_reads_clean(plan):
    for participant in range(3):
        got = judge(plan, participant, forward(plan, participant))
        assert got["expected"] == 50 * len(plan.subscribed(0, participant))
        assert all(got[k] == 0 for k in reference.NUMBERS if k in got), got


def in_window(t, k):
    return LEAD_NS <= t.due_offset_ns(k) < LEAD_NS + WINDOW_NS


def test_each_broken_guarantee_moves_its_number(plan):
    probe = wire.rtp_packet(96, 0, 0, 0x1000, False, b"")
    probe = bytes([probe[0] | 0x20]) + probe[1:] + bytes(254) + b"\xff"

    def dropped(t, k, inner):
        return [] if t.uid == 1 and k == 30 else [inner]

    def twice(t, k, inner):
        return [inner, inner] if t.uid == 1 and k == 30 else [inner]

    def altered(t, k, inner):
        return [inner[:-1] + bytes([inner[-1] ^ 0x55])] if t.uid == 0 and k == 30 else [inner]

    def no_marker(t, k, inner):
        return [inner[:1] + bytes([inner[1] & 0x7F]) + inner[2:]] if k == 30 else [inner]

    def renumbered(t, k, inner):
        if t.uid == 1 and k >= 30:
            sn = (int.from_bytes(inner[2:4], "big") + 1) & 0xFFFF
            return [inner[:2] + sn.to_bytes(2, "big") + inner[4:]]
        return [inner]

    def retimed(t, k, inner):
        if t.uid == 1 and k >= 30:
            ts = (int.from_bytes(inner[4:8], "big") + 960) & 0xFFFFFFFF
            return [inner[:4] + ts.to_bytes(4, "big") + inner[8:]]
        return [inner]

    def own_track_back(t, k, inner):
        mine = plan.tracks[3]                       # participant 2's own microphone
        extra = wire.rtp_packet(mine.pt, k, 0, 0x9999, False,
                                plan.body(mine, k, ORIGIN + mine.due_offset_ns(k)))
        return [inner, extra] if t.uid == 1 and k == 30 else [inner]

    assert in_window(plan.tracks[0], 30) and in_window(plan.tracks[1], 30)
    for fault, number, count in ((dropped, "missing", 1), (twice, "duplicated", 1),
                                 (altered, "corrupt", 1), (no_marker, "corrupt", 3),
                                 (renumbered, "sn_breaks", 1), (retimed, "ts_breaks", 30),
                                 (own_track_back, "stray", 1)):
        got = judge(plan, 2, forward(plan, 2, fault))
        assert got[number] == count, (fault.__name__, got)
    # a corrupt or dropped packet is also a delivery that did not arrive whole
    assert judge(plan, 2, forward(plan, 2, altered))["missing"] == 1
    # padding probes share a stream's SN space: one in the run of numbers is no gap
    def with_probe(t, k, inner):
        if t.uid == 0 and k == 30:
            sn = int.from_bytes(inner[2:4], "big")
            return [inner, probe[:2] + ((sn + 1) & 0xFFFF).to_bytes(2, "big") + probe[4:]]
        if t.uid == 0 and k > 30:
            sn = (int.from_bytes(inner[2:4], "big") + 1) & 0xFFFF
            return [inner[:2] + sn.to_bytes(2, "big") + inner[4:]]
        return [inner]
    assert judge(plan, 2, forward(plan, 2, with_probe))["sn_breaks"] == 0
