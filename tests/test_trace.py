"""Flight-recorder plane: trace-ring bounds, attribution sampling math,
black-box rings, and the Chrome trace-event export schema."""

from __future__ import annotations

import asyncio
import json

import numpy as np
import pytest

from livekit_server_tpu.runtime.trace import (
    EV_GOV_LEVEL,
    EV_NACK_STORM,
    EV_QUARANTINE,
    MAX_SHARDS,
    STAGES,
    BlackBox,
    LatencyAttribution,
    TickTraceRing,
)
from livekit_server_tpu.telemetry import trace_export


def _record(ring: TickTraceRing, idx: int, base: float = 100.0) -> int:
    """One well-formed tick record at a synthetic perf_counter base."""
    t = base + idx * 0.005
    return ring.record_tick(
        idx=idx, edge=t, stage_t0=t + 0.0001, stage_s=0.001,
        retier_s=0.0002, upload_t0=t + 0.0012, upload_s=0.0003,
        device_t0=t + 0.0016, device_s=0.002, fanout_t0=t + 0.0037,
        fanout_s=0.0008, send_s=0.0004, wake_over_us=42.0, depth=1,
        late=(idx % 7 == 0),
    )


# -- TickTraceRing ----------------------------------------------------------

def test_ring_bounds_and_wraparound():
    ring = TickTraceRing(cap=16)
    for i in range(40):
        _record(ring, i)
    assert ring.recorded == 40
    snap = ring.snapshot()
    # only the newest cap records survive, oldest first
    assert len(snap) == 16
    assert [r["tick"] for r in snap] == list(range(24, 40))


def test_ring_snapshot_newest_n():
    ring = TickTraceRing(cap=32)
    for i in range(10):
        _record(ring, i)
    snap = ring.snapshot(4)
    assert [r["tick"] for r in snap] == [6, 7, 8, 9]
    assert ring.snapshot(0) == []
    # n beyond what's recorded clamps
    assert len(ring.snapshot(99)) == 10


def test_ring_minimum_capacity():
    assert TickTraceRing(cap=1).cap >= 8


def test_ring_record_fields_round_trip():
    ring = TickTraceRing(cap=8)
    _record(ring, 3)
    r = ring.snapshot()[-1]
    assert r["tick"] == 3 and r["depth"] == 1
    assert r["stage_s"] == pytest.approx(0.001)
    assert r["retier_s"] == pytest.approx(0.0002)
    assert r["device_s"] == pytest.approx(0.002)
    assert r["wake_over_us"] == pytest.approx(42.0)


def test_ring_shard_lanes_bounded():
    ring = TickTraceRing(cap=8)
    slot = _record(ring, 0)
    ring.set_shard(slot, 0, 0.5, 0.25)
    ring.set_shard(slot, 2, 0.125, 0.0625)
    ring.set_shard(slot, MAX_SHARDS + 3, 9.0, 9.0)  # out of range: dropped
    r = ring.snapshot()[-1]
    assert len(r["shard_munge_ms"]) == 3  # lanes 0..2, lane 1 zero-filled
    assert r["shard_munge_ms"][0] == pytest.approx(0.5)
    assert r["shard_send_ms"][2] == pytest.approx(0.0625)


def test_ring_shard_reset_on_slot_reuse():
    ring = TickTraceRing(cap=8)
    slot = _record(ring, 0)
    ring.set_shard(slot, 5, 1.0, 1.0)
    for i in range(1, 9):  # wrap back onto slot 0
        _record(ring, i)
    r = ring.snapshot()[-1]
    assert r["tick"] == 8 and r["shard_munge_ms"] == []


# -- LatencyAttribution -----------------------------------------------------

def test_attribution_deterministic_sampling():
    la = LatencyAttribution(sample_every=8)
    sn = np.arange(32)
    ta = np.full(32, 99.0)
    la.observe_batch(sn, ta, t_dispatch=99.004, t_device_end=99.006,
                     now=99.010)
    # exactly sn % 8 == 0 sampled: 4 of 32
    assert int(la.total[STAGES.index("staging")]) == 4
    assert int(la.total[STAGES.index("total")]) == 4


def test_attribution_unstamped_and_predecomposition_batches_skipped():
    la = LatencyAttribution(sample_every=1)
    sn = np.arange(4)
    la.observe_batch(sn, np.zeros(4), 1.0, 2.0, 3.0)   # t_arr == 0
    la.observe_batch(sn, np.full(4, 99.0), 0.0, 0.0, 99.1)  # no stamps
    assert not la.summary()


def test_attribution_stage_split_sums_to_total():
    la = LatencyAttribution(sample_every=1)
    now = 200.0
    sn = np.array([0, 1, 2])
    ta = np.array([now - 0.010, now - 0.012, now - 0.008])
    la.observe_batch(sn, ta, t_dispatch=now - 0.006,
                     t_device_end=now - 0.004, now=now)
    d = la.drain()
    summed = d["staging"] + d["device"] + d["egress"]
    assert np.allclose(summed, d["total"], atol=1e-3)
    # late straggler (arrival after dispatch) clips staging at 0
    la.observe_batch(np.array([3]), np.array([now - 0.001]),
                     t_dispatch=now - 0.006, t_device_end=now - 0.004,
                     now=now)
    assert float(la.drain()["staging"][0]) == 0.0


def test_attribution_express_feeds_total_too():
    la = LatencyAttribution(sample_every=1)
    la.observe_express(np.array([0, 1]), np.array([9.998, 9.997]), 10.0)
    d = la.drain()
    assert len(d["express"]) == 2 and len(d["total"]) == 2
    assert "staging" not in d


def test_attribution_drain_is_incremental():
    la = LatencyAttribution(sample_every=1)
    la.observe_express(np.array([0]), np.array([0.9]), 1.0)
    assert len(la.drain()["express"]) == 1
    assert la.drain() == {}  # nothing new
    la.observe_express(np.array([1]), np.array([1.9]), 2.0)
    assert len(la.drain()["express"]) == 1


def test_attribution_ring_wrap_keeps_newest():
    la = LatencyAttribution(sample_every=1)
    n = la.CAP + 100
    la.observe_express(np.arange(n), np.full(n, 4.0), 5.0)
    d = la.drain()
    assert len(d["express"]) == la.CAP
    s = la.summary()
    # an over-CAP burst is truncated to the newest CAP before the push,
    # so the lifetime count reflects what was retained
    assert s["express"]["n"] == la.CAP
    assert s["express"]["p50_ms"] == pytest.approx(1000.0, rel=0.01)


def test_attribution_summary_percentiles():
    la = LatencyAttribution(sample_every=1)
    lat_s = np.arange(1, 101) / 1e3  # 1..100 ms
    la.observe_express(np.arange(100), 50.0 - lat_s, 50.0)
    s = la.summary()["express"]
    assert s["n"] == 100
    assert 49.0 <= s["p50_ms"] <= 52.0
    assert 98.0 <= s["p99_ms"] <= 100.0


# -- BlackBox ---------------------------------------------------------------

def test_blackbox_round_trip_and_bounds():
    bb = BlackBox(rooms=2, events=4)
    for k in range(7):
        bb.emit(1, EV_QUARANTINE, float(k))
    ev = bb.dump(1)
    assert len(ev) == 4  # ring keeps the last M
    assert [e["a"] for e in ev] == [3.0, 4.0, 5.0, 6.0]
    assert all(e["event"] == "quarantine" for e in ev)
    assert bb.dump(0) == []  # other lanes untouched


def test_blackbox_node_lane_and_out_of_range():
    bb = BlackBox(rooms=2, events=4)
    bb.emit(bb.NODE, EV_GOV_LEVEL, 0.0, 2.0)
    bb.emit(99, EV_GOV_LEVEL, 2.0, 3.0)  # out of range → node lane
    ev = bb.dump(bb.NODE)
    assert len(ev) == 2 and ev[0]["b"] == 2.0


def test_blackbox_dump_to_retains_and_logs():
    class Log:
        def __init__(self):
            self.calls = []

        def warn(self, msg, **kw):
            self.calls.append((msg, kw))

    log = Log()
    bb = BlackBox(rooms=1, events=4, log=log)
    bb.emit(0, EV_NACK_STORM, 1.0, 25.0)
    dumped = bb.dump_to(0, "nack_storm")
    assert dumped[-1]["event"] == "nack_storm"
    assert bb.dumps == 1
    assert bb.last_dumps[-1]["reason"] == "nack_storm"
    assert log.calls and log.calls[0][1]["room"] == 0
    # no log attached is fine (detached runtimes)
    bb.log = None
    bb.dump_to(0, "again")
    assert bb.dumps == 2


# -- export schema ----------------------------------------------------------

def _synthetic_events(n_ticks: int = 5):
    ring = TickTraceRing(cap=64)
    for i in range(n_ticks):
        slot = _record(ring, i)
        ring.set_shard(slot, 0, 0.2, 0.1)
        ring.set_shard(slot, 1, 0.15, 0.05)
    return trace_export.to_chrome(ring.snapshot(), tick_ms=5)


def test_export_schema_valid_and_json_clean():
    events = _synthetic_events()
    assert trace_export.validate(events) == []
    doc = json.loads(trace_export.export_json([], 5))
    assert doc["traceEvents"] == []


def test_export_span_inventory():
    events = _synthetic_events()
    names = {e["name"] for e in events}
    for want in ("tick_edge", "stage_host", "express_retier", "ctrl_upload",
                 "device_step", "fan_out", "egress_send", "munge", "send",
                 "thread_name"):
        assert want in names, want
    # every X event carries µs ts/dur and the shared pid
    for e in events:
        if e["ph"] == "X":
            assert e["pid"] == 1 and e["ts"] >= 0 and e["dur"] >= 0


def test_export_lane_assignment():
    events = _synthetic_events()
    by_name = {}
    for e in events:
        by_name.setdefault(e["name"], set()).add(e["tid"])
    assert by_name["stage_host"] == {trace_export.TID_LOOP}
    assert by_name["device_step"] == {trace_export.TID_DEVICE}
    assert by_name["fan_out"] == {trace_export.TID_FANOUT}
    assert by_name["munge"] == {trace_export.TID_SHARD0,
                                trace_export.TID_SHARD0 + 1}


def test_validate_rejects_broken_traces():
    assert trace_export.validate([{"ph": "X", "pid": 1, "tid": 1}])
    assert trace_export.validate(
        [{"name": "a", "ph": "X", "pid": 1, "tid": 1, "ts": 0.0,
          "dur": -1.0}]
    )
    # partial overlap on one lane is a nesting violation
    bad = [
        {"name": "a", "ph": "X", "pid": 1, "tid": 1, "ts": 0.0, "dur": 10.0},
        {"name": "b", "ph": "X", "pid": 1, "tid": 1, "ts": 5.0, "dur": 10.0},
    ]
    assert any("overlaps" in p for p in trace_export.validate(bad))
    # containment is fine
    ok = [
        {"name": "a", "ph": "X", "pid": 1, "tid": 1, "ts": 0.0, "dur": 10.0},
        {"name": "b", "ph": "X", "pid": 1, "tid": 1, "ts": 2.0, "dur": 3.0},
    ]
    assert trace_export.validate(ok) == []


def test_selftest_end_to_end():
    assert trace_export.selftest(ticks=4) == []


# -- spans inside the serving loop ------------------------------------------

NEW_TICK_KEYS = ("sleep_ms", "dispatch_delay_ms", "lock_wait_ms", "upload_ms",
                 "device_dispatch_ms", "device_fetch_ms", "handoff_ms",
                 "egress_wait_ms", "send_ms")
OLD_TICK_KEYS = ("idx", "depth", "stage_ms", "device_ms", "fanout_ms",
                 "total_ms", "work_ms", "late", "edge_overshoot_us")


async def _drive(rt, step_ticks: int = 3, run_s: float = 0.12) -> None:
    """A few sequential ticks, then the serving loop for a moment."""
    from livekit_server_tpu.runtime.ingest import PacketIn

    rt.set_track(0, 0, published=True, is_video=False)
    rt.set_subscription(0, 0, 1, subscribed=True)
    rt.on_tick(lambda result: None)
    for k in range(step_ticks):
        rt.ingest.push(PacketIn(room=0, track=0, sn=100 + k, ts=960 * k,
                                size=8, payload=b"p" * 8))
        await rt.step_once()
    if run_s:
        rt.start()
        await asyncio.sleep(run_s)
        await rt.stop()


def _runtime(**kw):
    from livekit_server_tpu.models import plane
    from livekit_server_tpu.runtime.plane_runtime import PlaneRuntime

    dims = plane.PlaneDims(rooms=2, tracks=2, pkts=2, subs=2)
    rt = PlaneRuntime(dims, tick_ms=5, **kw)
    # the serving loop's ticks pinned at depth 1: the tests below tell them
    # from step_once's (depth 0) by it, and read the pipelined waits
    rt.choose_depth = lambda *a: (1, 0)
    return rt


@pytest.fixture(scope="module")
def driven():
    rt = _runtime(trace_ring_ticks=64)
    asyncio.run(_drive(rt))
    return rt


@pytest.mark.parametrize("key", NEW_TICK_KEYS)
def test_tick_record_has_each_new_key_with_a_value(driven, key):
    recs = list(driven.recent_ticks)
    assert len(recs) > 6 and all(key in r and r[key] >= 0.0 for r in recs)
    looped = [r for r in recs if r["depth"] == 1]          # the ticks of _run
    assert looped, "the serving loop completed no tick"
    if key == "send_ms":        # a callback that does nothing: 0.000 ms is a reading
        return
    assert any(r[key] > 0.0 for r in looped), key
    # step_once neither sleeps nor has an edge to be late against
    if key in ("sleep_ms", "dispatch_delay_ms"):
        assert all(r[key] == 0.0 for r in recs if r["depth"] == 0)


def test_tick_record_keeps_its_depth_and_its_keys(driven):
    assert driven.recent_ticks.maxlen == 120 and driven.trace.cap == 64
    assert not hasattr(driven, "recent_tick_s")
    for r in driven.recent_ticks:
        assert all(k in r for k in OLD_TICK_KEYS)


def test_children_lie_inside_their_parents(driven):
    for r in driven.recent_ticks:
        assert r["device_dispatch_ms"] + r["device_fetch_ms"] <= r["device_ms"] + 0.002
        if r["depth"] == 1:
            assert r["lock_wait_ms"] + r["upload_ms"] <= r["dispatch_delay_ms"] + 0.002
        assert r["handoff_ms"] <= r["egress_wait_ms"] + 0.002
    for r in driven.trace.snapshot():
        parts = r["dispatch_s"] + r["fetch_s"] + r["mirror_s"] + r["audit_s"]
        assert 0.0 < parts <= r["device_s"]
        # read off their boundaries: they add up to the call, but for its
        # last statements
        assert parts >= 0.9 * r["device_s"]
        if r["sleep_t0"]:
            assert r["sleep_t0"] + r["sleep_s"] <= r["lock_t0"] <= r["upload_t0"]


def test_totals_equal_the_sum_of_the_rings_records(driven):
    from livekit_server_tpu.runtime import trace

    recs = driven.trace.snapshot()
    assert len(recs) == driven.stats["ticks"] < driven.trace.cap
    totals = driven.spans.snapshot()
    assert set(totals) == set(trace.SPANS)
    sums = {
        "stage/host": [r["stage_s"] for r in recs],
        "ctrl/upload": [r["upload_s"] for r in recs],
        "device/call": [r["device_s"] for r in recs],
        "device/dispatch": [r["dispatch_s"] for r in recs],
        "device/fetch": [r["fetch_s"] for r in recs],
        "loop/handoff": [r["handoff_s"] for r in recs],
        "fanout/assemble": [r["fanout_s"] for r in recs],
        "egress/send": [r["send_s"] for r in recs],
        "loop/lock_wait": [r["lock_s"] for r in recs],
        "loop/sleep": [r["sleep_s"] for r in recs if r["sleep_s"] > 0.0],
        "loop/dispatch_delay": [trace.between(r["edge"], r["device_t0"])
                                for r in recs if r["edge"] > 0.0],
        "egress/wait": [trace.between(r["device_t0"] + r["device_s"],
                                      r["fanout_t0"]) for r in recs],
    }
    for name, values in sums.items():
        assert totals[name]["n"] == len(values) > 0, name
        assert totals[name]["busy_s"] == pytest.approx(sum(values), abs=2e-6), name
        assert totals[name]["max_ms"] == pytest.approx(1e3 * max(values), abs=2e-3), name
    # what this plane does not run stays at nothing, and is still listed
    for name in ("stage/retier", "device/mirror", "device/audit", "rx"):
        assert totals[name] == {"n": 0, "items": 0, "busy_s": 0.0, "max_ms": 0.0}


def test_export_of_a_driven_ring_nests_and_names_the_new_events(driven):
    events = trace_export.to_chrome(driven.trace.snapshot(), driven.tick_ms)
    assert trace_export.validate(events) == []
    by_name = {}
    for e in events:
        by_name.setdefault(e["name"], set()).add(e["tid"])
    assert by_name["loop_sleep"] == by_name["lock_wait"] == {trace_export.TID_LOOP}
    assert by_name["device_dispatch"] == by_name["device_fetch"] == {trace_export.TID_DEVICE}
    assert by_name["dispatch_delay"] == {trace_export.TID_DISPATCH_WAIT}
    assert by_name["egress_wait"] == by_name["loop_handoff"] == {trace_export.TID_EGRESS_WAIT}


def test_export_of_late_ticks_still_nests():
    """A tick dispatched more than a period late (a checkpoint held
    state_lock) and a fan-out deferred past the next device step: the
    waits of neighbouring ticks overlap in time, and each lane has to
    nest all the same."""
    ring = TickTraceRing(cap=16)
    now = 100.0
    for i in range(4):
        edge = 100.0 + i * 0.005
        sleep_t0, now = now, max(now, edge) + 1e-5      # behind: no sleep to speak of
        lock_t0, lock_s = now, (0.012 if i == 1 else 1e-5)   # tick 1 waits 12 ms
        upload_t0 = lock_t0 + lock_s
        device_t0 = upload_t0 + 0.0002
        ring.record_tick(
            idx=i, edge=edge, stage_t0=device_t0 + 0.0001, stage_s=0.001,
            retier_s=0.0, upload_t0=upload_t0, upload_s=0.0001,
            device_t0=device_t0, device_s=0.002,
            fanout_t0=device_t0 + 0.006, fanout_s=0.0008, send_s=0.0004,
            wake_over_us=10.0, depth=1, late=i > 0,
            sleep_t0=sleep_t0, sleep_s=lock_t0 - sleep_t0, lock_t0=lock_t0,
            lock_s=lock_s, dispatch_s=0.0005,
            fetch_s=0.0012, mirror_s=0.0001, audit_s=0.0001,
            handoff_s=0.0003,
        )
        now = device_t0 + 0.0023                        # resumed after the hand-off
    events = trace_export.to_chrome(ring.snapshot(), tick_ms=5)
    assert trace_export.validate(events) == []
    names = {e["name"] for e in events}
    assert {"device_dispatch", "device_fetch", "device_mirror", "device_audit",
            "loop_handoff"} <= names
    assert "paged_kernel" not in names      # the host-clock kernel lane is gone
    held = next(e for e in events if e["name"] == "dispatch_delay"
                and e["args"]["tick"] == 2)
    # tick 2's edge lay inside tick 1's wait: drawn from where that ended,
    # the whole wait kept beside it
    assert held["dur"] < held["args"]["wait_us"]


async def test_trace_disabled_gives_no_ring_and_no_totals_but_keeps_the_record():
    rt = _runtime(trace_enabled=False)
    await _drive(rt, step_ticks=2, run_s=0.05)
    assert rt.trace is None and rt.wire_stages is None
    assert rt.spans.snapshot() == {} and not any(rt.spans.n)
    recs = list(rt.recent_ticks)
    assert len(recs) >= 3
    for r in recs:
        assert all(k in r for k in OLD_TICK_KEYS + NEW_TICK_KEYS)
        assert r["device_dispatch_ms"] > 0.0 and r["device_fetch_ms"] > 0.0


def test_a_profiler_trace_holds_the_spans_on_their_threads(tmp_path):
    """`Spans.span` is a `TraceAnnotation`: with a profiler session on,
    the spans lie in the `.xplane.pb`, on the profiler's clock, each on
    the line of the thread that ran it."""
    import jax
    from jax.profiler import ProfileData

    rt = _runtime()

    async def traced():
        await _drive(rt, step_ticks=1, run_s=0)            # compile outside it
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0                    # no Python frames
        jax.profiler.start_trace(str(tmp_path), profiler_options=options)
        try:
            await _drive(rt, step_ticks=3, run_s=0)
        finally:
            jax.profiler.stop_trace()

    asyncio.run(traced())
    found = sorted(tmp_path.rglob("*.xplane.pb"))
    assert found, "the profiler wrote no trace"
    lines: dict[int, list] = {}         # a line a thread; threads share the name
    for pl in ProfileData.from_file(str(found[-1])).planes:
        if not pl.name.startswith("/host:"):
            continue
        for line in pl.lines:
            spans = [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                     for e in line.events if e.name.startswith("sfu/")]
            if spans:
                lines[len(lines)] = spans
    where = {name: line for line, spans in lines.items() for name, _, _ in spans}
    assert where["sfu/device/call"] == where["sfu/device/fetch"] \
        == where["sfu/device/dispatch"]
    assert where["sfu/stage/host"] == where["sfu/fanout/assemble"] \
        == where["sfu/ctrl/upload"]
    assert where["sfu/device/call"] != where["sfu/stage/host"]     # worker / loop
    worker = lines[where["sfu/device/call"]]
    calls = [(a, b) for n, a, b in worker if n == "sfu/device/call"]
    fetches = [(a, b) for n, a, b in worker if n == "sfu/device/fetch"]
    assert len(calls) == len(fetches) == 3
    for (a, b), (fa, fb) in zip(sorted(calls), sorted(fetches)):
        assert a <= fa and fb <= b


@pytest.mark.parametrize("stage", ["staging", "device", "egress", "total", "express"])
def test_attribution_sum_survives_drain_and_reset(stage):
    la = LatencyAttribution(sample_every=1)
    now = 50.0
    ta = now - np.array([0.010, 0.012, 0.008])
    if stage == "express":
        la.observe_express(np.arange(3), ta, now)
    else:
        la.observe_batch(np.arange(3), ta, t_dispatch=now - 0.006,
                         t_device_end=now - 0.004, now=now)
    pushed = la.drain()[stage]
    first = la.cumulative()[stage]
    assert first["n"] == 3
    assert first["sum_ms"] == pytest.approx(float(pushed.sum()), abs=2e-3)
    la.reset()                                   # zeroes `total`, not the pair
    assert la.cumulative()[stage] == first and not la.summary()
    if stage == "express":
        la.observe_express(np.arange(3), ta, now)
    else:
        la.observe_batch(np.arange(3), ta, now - 0.006, now - 0.004, now)
    second = la.cumulative()[stage]
    assert second["n"] == 6
    assert second["sum_ms"] == pytest.approx(2 * first["sum_ms"], abs=2e-3)
    # every stage is listed, fed or not: a reader by difference finds its key
    assert set(la.cumulative()) == set(STAGES)


def test_spans_off_record_nothing_and_a_rare_span_totals_itself():
    from livekit_server_tpu.runtime import trace

    on, off = trace.Spans(True), trace.Spans(False)
    for spans in (on, off):
        with spans.span(trace.SP_CKPT_ENCODE) as sp:
            pass
        assert sp.t0 > 0.0 and sp.dt >= 0.0          # stamped either way
        with spans.span(trace.SP_STAGE_HOST):        # a tick's span: the ring's to total
            pass
        spans.add(trace.SP_RX, 0.002, items=7)
    snap = on.snapshot()
    assert snap["supervisor/checkpoint/encode"]["n"] == 1
    assert snap["stage/host"]["n"] == 0
    assert snap["rx"] == {"n": 1, "items": 7, "busy_s": 0.002, "max_ms": 2.0}
    assert off.snapshot() == {} and not any(off.n)
