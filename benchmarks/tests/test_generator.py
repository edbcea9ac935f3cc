"""The load generator's sender against a clock that stalls: a backlog leaves
in slices of the schedule, every packet once, each still due when it was."""

import json

from benchmarks import run, traffic
from benchmarks.client import wire, worker


class StallingClock:
    """`time` for `worker`: sleeps cost what they ask, and the call that
    crosses `at_ns` finds the process stopped for `stall_ns` more."""

    def __init__(self, start_ns: int, at_ns: int, stall_ns: int):
        self.now, self.at, self.stall = start_ns, at_ns, stall_ns

    def time_ns(self) -> int:
        return self.now

    def sleep(self, seconds: float) -> None:
        before, self.now = self.now, self.now + int(seconds * 1e9) + 1
        if before < self.at <= self.now:
            self.now += self.stall


def test_a_backlog_leaves_in_slices_and_nothing_is_sent_twice(monkeypatch):
    workload = json.loads((run.ROOT / "benchmarks/workloads/meet-tick20.steady.json").read_text())
    plan = traffic.make_plan(workload, seed=9, rooms=1)
    spec = {"udp_port": 9, "lead_in_s": 0.5, "seconds": 4.0, "ack_every_ms": 100}
    drive = worker.Drive(spec, plan, [0])
    key = wire.SealedEndpoint(1, bytes(16))
    drive.publishers = {t.uid: (key, 1000 + t.uid) for t in plan.tracks}
    drive.t0_ns = 10 * traffic.NS + drive.lead_ns
    clock = StallingClock(10 * traffic.NS, 12 * traffic.NS, int(1.5 * traffic.NS))
    releases = []          # (when, how many datagrams)

    class Recorder:
        def __init__(self, sock):
            pass

        def send(self, batch):
            releases.append((clock.now, len(batch)))

    monkeypatch.setattr(worker, "time", clock)
    monkeypatch.setattr(wire, "BatchSender", Recorder)
    try:
        drive.send_all()
    finally:
        drive.sel.close()
        for s in (drive.pub, *drive.sub_sock.values()):
            s.close()
    sent = sum(n for _, n in releases)
    assert sent == drive.sent == sum(t.first_index_at(drive.lead_ns + drive.window_ns)
                                     for t in plan.tracks)
    # 8 tracks at 60 and 50 a second: 50 ms of the schedule is at most 3 + 1 of each
    per_slice = 8 * 4
    assert max(n for _, n in releases) <= per_slice
    behind = [(at, n) for at, n in releases if n > 2]
    assert len(behind) >= 1.5 / 0.05 * 0.8          # the stall's backlog, slice by slice
    gaps = [b[0] - a[0] for a, b in zip(behind, behind[1:])]
    assert min(gaps) >= worker.CATCH_UP_SLICE_NS / worker.CATCH_UP_FACTOR
    assert drive.gen_late_ns >= 1.4 * traffic.NS    # the stall is reported as it was
