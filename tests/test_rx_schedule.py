"""When the receive path reads its socket (runtime/udp.py RxSchedule): the
three rules on a fake clock and a fake readable socket, one parametrised
test a rule; the order of `_run`'s signals on a real loop; and the kernel's
arrival stamps on a real loopback socket.
"""

import asyncio
import socket
import time
from types import SimpleNamespace

import numpy as np
import pytest

from livekit_server_tpu.models import plane
from livekit_server_tpu.runtime import PlaneRuntime
from livekit_server_tpu.runtime.udp import RX_PACE, SO_TIMESTAMP, RxSchedule, start_udp_transport
from tests.test_native import rtp_packet

DIMS = plane.PlaneDims(rooms=2, tracks=4, pkts=8, subs=4)
FD = 7


class FakeLoop:
    """A selector with one level-triggered reader and a timer wheel, on a
    clock the test moves."""

    def __init__(self):
        self.now = 100.0
        self.reader = None
        self.timers = []            # [when, callback, cancelled]

    def clock(self):
        return self.now

    def add_reader(self, fd, cb):
        assert fd == FD and self.reader is None
        self.reader = cb

    def remove_reader(self, fd):
        assert fd == FD and self.reader is not None
        self.reader = None

    def call_later(self, delay, cb):
        timer = [self.now + delay, cb, False]
        self.timers.append(timer)
        return SimpleNamespace(cancel=lambda: timer.__setitem__(2, True))

    def pending(self):
        return [t for t in self.timers if not t[2]]

    def advance(self, dt):
        """Move the clock, firing the timers that come due on the way."""
        end = self.now + dt
        while True:
            due = [t for t in self.pending() if t[0] <= end]
            if not due:
                break
            timer = min(due, key=lambda t: t[0])
            self.timers.remove(timer)
            self.now = max(self.now, timer[0])
            timer[1]()
        self.now = max(self.now, end)


class FakeSocket:
    """What the kernel holds, and the read that drains it whole at a cost."""

    def __init__(self, loop, fixed_s=0.00075, each_s=0.00001):
        self.loop, self.fixed_s, self.each_s = loop, fixed_s, each_s
        self.held = []
        self.fed = []               # one list a feed_batch, in the socket's order
        self.calls = 0              # rx_batch calls, empty ones included

    def send(self, *dgrams):
        self.held += dgrams

    def read(self):
        self.calls += 1
        got, self.held = self.held, []
        if got:
            self.fed.append(got)
            self.loop.now += self.fixed_s + self.each_s * len(got)
        return len(got)

    def wake(self):
        """One pass of the selector: a readable socket calls its reader, if
        it has one."""
        if self.held and self.loop.reader is not None:
            self.loop.reader()


def served(express_holds=False, serving=True):
    lane = SimpleNamespace(holds_rooms=express_holds)
    return SimpleNamespace(serving=serving, express=lane)


def make(plane_=None, **sock_kw):
    loop = FakeLoop()
    sock = FakeSocket(loop, **sock_kw)
    stats = {}
    sched = RxSchedule(loop, FD, sock.read, stats, clock=loop.clock)
    sched.plane = plane_
    return loop, sock, sched, stats


@pytest.mark.parametrize("wakes", [1, 7, 200])
def test_rule1_nothing_reads_between_chain_begin_and_chain_end(wakes):
    loop, sock, sched, stats = make(served())
    sched.chain_begin()
    assert loop.reader is None          # off the selector: it cannot spin
    for i in range(wakes):
        sock.send(f"d{i}")
        sock.wake()
        loop.advance(0.013 / wakes)
    assert sock.calls == 0 and sock.fed == [] and not loop.pending()
    assert stats["rx_reads"] == 0


@pytest.mark.parametrize("n", [1, 52, 300])
def test_rule1_the_read_after_a_chain_brings_all_of_it_in_order(n):
    loop, sock, sched, stats = make(served())
    sched.chain_begin()
    sent = [f"d{i}" for i in range(n)]
    for d in sent:
        sock.send(d)
        sock.wake()
    sched.chain_end()                   # at once, no wake needed
    assert sock.fed == [sent]
    assert (stats["rx_reads"], stats["rx_held_chain"]) == (1, 1)
    assert loop.reader is None and len(loop.pending()) == 1     # rule 2 follows


@pytest.mark.parametrize("fixed_s,n", [(0.00075, 11), (0.0005, 1), (0.0016, 86)])
def test_rule2_the_gap_to_the_next_read_is_the_constant_times_the_last_cost(fixed_s, n):
    loop, sock, sched, stats = make(served(), fixed_s=fixed_s)
    cost = fixed_s + 0.00001 * n
    sock.send(*range(n))
    sock.wake()                         # a read from the selector
    read_end = loop.now
    assert sock.calls == 1 and loop.reader is None
    [(when, _, _)] = loop.pending()
    assert when == pytest.approx(read_end + RX_PACE * cost, abs=1e-9)
    # what arrives in the gap waits, however often the socket wakes...
    sock.send("late")
    for _ in range(5):
        sock.wake()
    loop.advance(RX_PACE * cost - 1e-6)
    assert sock.calls == 1
    # ...and is read when the gap has passed, without a wake of its own
    loop.advance(2e-6)
    assert sock.fed[-1] == ["late"] and stats["rx_held_pause"] == 1
    # a gap that ends on an empty socket puts the reader back on the selector
    loop.advance(1.0)
    assert loop.reader is not None and not loop.pending()
    assert stats["rx_reads"] == 2 and sock.calls == 3


@pytest.mark.parametrize("pause_pending,readable,feeds,edge_reads", [
    (True, True, 1, 1),      # the pause would have covered the edge: read first
    (True, False, 0, 0),     # nothing readable: an rx_batch, no feed_batch
    (False, True, 0, 0),     # no pause pending: the reader was on until now
])
def test_rule3_an_edge_with_a_pause_pending_reads_before_the_stage(
        pause_pending, readable, feeds, edge_reads):
    loop, sock, sched, stats = make(served())
    if pause_pending:
        sock.send("early")
        sock.wake()
        assert loop.pending()
    fed_before = len(sock.fed)
    if readable:
        sock.send("a", "b")
    sched.chain_begin()                 # returns before `_run` stages
    assert len(sock.fed) - fed_before == feeds
    if feeds:
        assert sock.fed[-1] == ["a", "b"]
    assert stats["rx_edge_reads"] == edge_reads
    assert loop.reader is None and not loop.pending()   # rule 1 from here
    sched.chain_end()
    assert sock.held == []


@pytest.mark.parametrize("plane_", [
    None, served(serving=False), served(express_holds=True),
], ids=["no_plane", "no_serving_loop", "express_room"])
def test_without_a_serving_loop_or_with_an_express_room_every_wake_reads(plane_):
    loop, sock, sched, stats = make(plane_)
    for i in range(5):
        sched.chain_begin()             # (a step_once-driven test has no such call)
        sock.send(i)
        sock.wake()
        assert sock.fed[-1] == [i]
        assert loop.reader is not None and not loop.pending()
        sched.chain_end()
    assert stats["rx_reads"] == 5 and sock.calls == 5
    assert stats["rx_held_chain"] == stats["rx_held_pause"] == stats["rx_edge_reads"] == 0


def test_a_loop_that_ends_in_its_chain_leaves_the_reader_on():
    plane_ = served()
    loop, sock, sched, stats = make(plane_)
    sched.chain_begin()
    sock.send("x")
    plane_.serving = False              # `_run` cancelled: its `finally` signals the end
    sched.chain_end()
    assert sock.fed == [["x"]] and loop.reader is not None and not loop.pending()
    sched.close()
    sched.chain_begin()
    sched.chain_end()
    assert loop.reader is None and sock.calls == 1      # closed: it reads no more


def test_a_read_that_raises_is_reported_and_the_schedule_goes_on():
    loop, sock, sched, stats = make(served())
    reported = []
    loop.call_exception_handler = reported.append
    good, sched.read = sched.read, lambda: 1 / 0
    sock.send("x")
    sched.chain_begin()
    sched.chain_end()                   # raises inside: `_run` must not see it
    assert [type(r["exception"]) for r in reported] == [ZeroDivisionError]
    assert loop.reader is not None and stats["rx_reads"] == 0
    sched.read = good
    sock.wake()
    assert sock.fed == [["x"]]


@pytest.mark.parametrize("depth", [0, 1])
async def test_run_signals_the_chain_round_every_stage_and_step(depth):
    """`_run` on a real loop: chain_begin at the wake, before the tick is
    staged; chain_end as the loop goes to sleep; and once more as it ends."""
    rt = PlaneRuntime(DIMS, tick_ms=10)
    rt.choose_depth = lambda *a: (depth, 0)
    events = []
    rt.rx = SimpleNamespace(chain_begin=lambda: events.append("begin"),
                            chain_end=lambda: events.append("end"))
    stage, step = rt._stage_host, rt._device_step
    rt._stage_host = lambda: (events.append("stage"), stage())[1]
    rt._device_step = lambda st: (events.append("step"), step(st))[1]
    assert not rt.serving
    rt.start()
    try:
        assert rt.serving
        while rt.stats["ticks"] < 4:
            await asyncio.sleep(0.01)
    finally:
        await rt.stop()
    assert not rt.serving
    assert events[0] == "end" and events[-1] == "end"   # first sleep; the finally
    chains = [c.split() for c in " ".join(events).split("begin")]
    assert len(chains) >= 5
    assert chains[0] == ["end"]                         # nothing staged outside a chain
    for ev in chains[1:-1]:                             # (the last one the stop cut short)
        assert ev[-1] == "end" and ev.count("end") == 1 and ev.count("step") == 1
        assert "stage" in ev
        assert ev[0] == "stage" or depth                # at depth 0 the stage is the edge's


async def _loopback(stamped: bool):
    rt = PlaneRuntime(DIMS, tick_ms=10)
    s = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    transport = await start_udp_transport(rt.ingest, "127.0.0.1", port)
    pub = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
    try:
        if not stamped:
            transport.transport.get_extra_info("socket").setsockopt(
                socket.SOL_SOCKET, SO_TIMESTAMP, 0)
        rt.set_track(0, 0, published=True, is_video=False)
        ssrc = transport.assign_ssrc(room=0, track=0, is_video=False)
        t0 = time.perf_counter()
        pub.sendto(rtp_packet(sn=1, ts=0, ssrc=ssrc, payload=b"a" * 40), ("127.0.0.1", port))
        time.sleep(0.005)               # the loop is held: no read between the two
        pub.sendto(rtp_packet(sn=2, ts=960, ssrc=ssrc, payload=b"b" * 40), ("127.0.0.1", port))
        t1 = time.perf_counter()
        while transport.stats["rx"] < 2 and time.perf_counter() < t1 + 2.0:
            await asyncio.sleep(0.002)
        t2 = time.perf_counter()
        assert transport.stats["rx"] == 2 and transport.stats["rx_reads"] == 1
        assert rt.ingest._count[0, 0] == 2
        return transport.stats, rt.ingest.t_arr[0, 0, :2].copy(), (t0, t1, t2)
    finally:
        pub.close()
        transport.transport.close()
        await rt.stop()


async def test_one_drain_gives_each_datagram_its_own_arrival():
    # (a loaded host's loopback may deliver, and so stamp, the first datagram
    # late: up to three tries, one has to read as sent)
    for attempt in range(3):
        stats, t_arr, (t0, t1, t2) = await _loopback(stamped=True)
        if stats["rx_stamp_fallback"]:
            pytest.skip("this kernel hands no SCM_TIMESTAMP back with SO_TIMESTAMP set")
        gap = t_arr[1] - t_arr[0]
        if gap >= 0.004:
            break
    assert 0.004 <= gap <= t1 - t0 + 0.001      # ~5 ms apart, as they were sent
    # on perf_counter's scale, at the sends and not at the read (1 ms for the
    # two clocks' offset, taken once a read)
    assert t0 - 0.001 <= t_arr[0] and t_arr[1] <= t1 + 0.001


async def test_a_datagram_without_a_stamp_takes_the_reads_time_and_is_counted():
    stats, t_arr, (t0, t1, t2) = await _loopback(stamped=False)
    assert stats["rx_stamp_fallback"] == 2
    assert t_arr[0] == t_arr[1] and t1 - 0.001 <= t_arr[0] <= t2


def test_arrival_times_never_lie_in_the_future():
    from livekit_server_tpu.runtime.udp import UDPMediaTransport

    tr = UDPMediaTransport(PlaneRuntime(DIMS, tick_ms=10).ingest)
    now_us = time.time_ns() // 1000
    t = tr.arrival_times(np.array([now_us - 13_000, 0, now_us + 5_000_000], np.int64))
    now = time.perf_counter()
    assert 0.0125 < now - t[0] < 0.02
    assert now - 0.005 < t[1] <= now and t[2] == t[1]   # unstamped, and a stepped clock
    assert tr.stats["rx_stamp_fallback"] == 1
