"""The one traffic generator: a workload file's parameters and a seed in, the
plan of a run out — rooms, participants, tracks, who subscribes to what, and
for every track the schedule and the bytes of each packet. Pure arithmetic
on the file and the seed: no clock, no socket, nothing of the server.

Every seed gives the same set of periods, sizes and phases: the tracks of
one kind stand evenly over that kind's period, and the seed only deals those
phases to other tracks of the kind and draws other SN/TS origins and payload
bytes, so two seeds ask the same work of the server.
"""

from __future__ import annotations

import dataclasses

import numpy as np

from benchmarks.client.wire import STAMP

NS = 1_000_000_000
PATTERN_LEN = 4096


@dataclasses.dataclass(frozen=True)
class Track:
    uid: int            # index over the whole run; carried in every packet
    room: int
    owner: int          # participant index within the room
    kind: str           # the key in the workload's "tracks"
    video: bool
    pt: int
    rtp_bytes: int      # RTP header + payload, as sent by the publisher
    period_ns: int
    phase_ns: int       # first packet's offset from the schedule's origin
    ts_step: int
    sn0: int
    ts0: int
    pace_ns: int = 0    # the generator's batching grid: packets leave on it (0: each alone)

    def due_offset_ns(self, index: int) -> int:
        """When packet `index` is due, from the schedule's origin: its own
        instant, or the grid's next release at or after it."""
        raw = self.phase_ns + index * self.period_ns
        return -(-raw // self.pace_ns) * self.pace_ns if self.pace_ns else raw

    def first_index_at(self, offset_ns: int) -> int:
        """The first packet index due at or after `offset_ns`."""
        if self.pace_ns:        # due >= offset <=> raw > the release before offset's
            offset_ns = (-(-offset_ns // self.pace_ns) - 1) * self.pace_ns + 1
        return max(0, -((self.phase_ns - offset_ns) // self.period_ns))


@dataclasses.dataclass(frozen=True)
class Plan:
    workload: dict
    seed: int
    rooms: int
    participants: int                 # per room
    tracks: tuple[Track, ...]
    pattern: bytes

    def room_tracks(self, room: int) -> list[Track]:
        return [t for t in self.tracks if t.room == room]

    def subscribed(self, room: int, participant: int) -> list[Track]:
        """Everyone is subscribed to every track of the room not their own."""
        return [t for t in self.tracks if t.room == room and t.owner != participant]

    def body(self, track: Track, index: int, due_ns: int) -> bytes:
        """Payload bytes after the RTP header (audio) or after the VP8
        descriptor and payload-header byte (video): stamp, then pattern."""
        n = track.rtp_bytes - 12 - (7 if track.video else 0) - STAMP.size
        start = (track.uid * 131 + index * 7) % (PATTERN_LEN // 2)
        return STAMP.pack(track.uid, index, due_ns) + self.pattern[start:start + n]

    def identity(self, room: int, participant: int) -> tuple[str, str]:
        return f"bench-{room}", f"p{participant}"


def make_plan(workload: dict, seed: int, rooms: int | None = None) -> Plan:
    rooms = workload["live_rooms"] if rooms is None else rooms
    kinds = workload["tracks"]
    people: list[list[str]] = []
    for group in workload["room"]["participants"]:
        people += [list(group["publish"])] * group["count"]
    rng = np.random.default_rng(seed)
    n_tracks = rooms * sum(len(p) for p in people)
    # a grid of phases over [0, 1) for each kind, dealt to its tracks by the seed
    of_kind = {kind: rooms * sum(p.count(kind) for p in people) for kind in kinds}
    deal = {kind: iter(rng.permutation(n)) for kind, n in of_kind.items()}
    sn0 = rng.integers(0, 1 << 16, n_tracks)
    ts0 = rng.integers(0, 1 << 31, n_tracks)
    pattern = rng.integers(0, 256, PATTERN_LEN, dtype=np.uint8).tobytes()
    tracks = []
    for room in range(rooms):
        for owner, publishes in enumerate(people):
            for kind in publishes:
                k, uid = kinds[kind], len(tracks)
                period = NS // k["pps"]
                tracks.append(Track(
                    uid=uid, room=room, owner=owner, kind=kind,
                    video=k["kind"] == "video", pt=k["pt"],
                    rtp_bytes=k["rtp_bytes"], period_ns=period,
                    phase_ns=int(next(deal[kind])) * period // of_kind[kind],
                    ts_step=k["clock_hz"] // k["pps"],
                    sn0=int(sn0[uid]), ts0=int(ts0[uid]),
                    pace_ns=int(workload.get("pace_ms", 0) * 1_000_000)))
    return Plan(workload, seed, rooms, len(people), tuple(tracks), pattern)


def offered_pps(plan: Plan) -> tuple[float, float]:
    """(packets a second in, packets a second out) that the plan asks for."""
    pin = sum(NS / t.period_ns for t in plan.tracks)
    pout = sum(NS / t.period_ns * (plan.participants - 1) for t in plan.tracks)
    return pin, pout
