"""Node-death failover: lease expiry → takeover → restore from checkpoint.

Reference parity: redisrouter's RemoveDeadNodes plus the migration seeding
of participant.go:823, composed into an unattended path — no client join
is needed to re-home a dead node's rooms. The survivor's failover worker
(service/roommanager.py) notices the expired liveness lease, wins the
takeover lock, and restores the room row from the periodic checkpoint the
dead node published to the KV bus (runtime/supervisor.py cadence).

The node kill here is the fault-injection harness's non-graceful variant
(runtime/faultinject.py kill_node): heartbeats and the lease stop, the
bus socket drops, and NOTHING is cleaned up — exactly what a crashed host
looks like to the survivors.
"""

import asyncio

import aiohttp
import pytest

from livekit_server_tpu.routing.tcpbus import TCPBusClient
from livekit_server_tpu.runtime.faultinject import FaultInjector
from livekit_server_tpu.runtime.ingest import PacketIn
from livekit_server_tpu.service.server import create_server
from tests.conftest import free_port
from tests.test_multinode import start_bus
from tests.test_service import SignalClient, make_config


async def start_chaos_node(bus_port: int, *, lease_ttl: float = 1.0):
    """A node with failure-detection cadences tightened for test time:
    sub-second lease, fast failover scan, fast checkpoint cadence. The
    heartbeat interval must stay well inside the lease TTL or live nodes
    would flap dead between refreshes."""
    client = await TCPBusClient.connect("127.0.0.1", bus_port)
    cfg = make_config(free_port())
    cfg.kv.lease_ttl_s = lease_ttl
    cfg.kv.failover_interval_s = 0.15
    cfg.supervisor.checkpoint_interval_s = 0.25
    srv = create_server(cfg, bus=client)
    srv.router.stats_interval = 0.3  # heartbeat + lease refresh cadence
    await srv.start()
    return srv, client


async def _stop_quiet(srv) -> None:
    try:
        await srv.stop(force=True)
    except (ConnectionError, OSError):
        pass  # a killed node's bus is gone; cleanup calls fail fast


async def test_node_death_failover_restores_room_on_survivor():
    """Kill node A (non-graceful) with a room pinned to it and media
    state checkpointed: node B's failover worker adopts the room without
    any client action, the munger lane resumes from the checkpoint (the
    continued stream emits contiguous SNs, no reset), and the failover
    counter increments."""
    bus = await start_bus()
    srv_a = srv_b = None
    try:
        srv_a, _ = await start_chaos_node(bus.port)
        srv_b, _ = await start_chaos_node(bus.port)
        async with aiohttp.ClientSession() as s:
            alice = SignalClient(s, srv_a.port)
            await alice.connect("chaos", "alice")
            row_a = srv_a.room_manager.rooms["chaos"].slots.row
            rt_a = srv_a.room_manager.runtime
            rt_a.set_track(row_a, 0, published=True, is_video=False)
            rt_a.set_subscription(row_a, 0, 1, subscribed=True)
            # A's serving loop carries the traffic (mixing step_once into
            # a served runtime reorders the pipelined fan-outs, which can
            # transiently run munger state BACKWARDS); munger state —
            # polled, not sampled — is the ground truth for what went out.
            for i in range(5):
                rt_a.ingest.push(PacketIn(room=row_a, track=0, sn=7000 + i,
                                          ts=960 * i, size=50, payload=b"a"))
                await asyncio.sleep(0.02)
            deadline = asyncio.get_event_loop().time() + 10
            while (int(rt_a.munger.last_sn[row_a, 0, 1]) < 7004
                   and asyncio.get_event_loop().time() < deadline):
                await asyncio.sleep(0.02)
            assert int(rt_a.munger.last_sn[row_a, 0, 1]) == 7004
            await alice.close()

            # Make sure the bus checkpoint reflects the final munger state
            # (the periodic cadence would get there too; this pins timing).
            await srv_a.room_manager.checkpoint_rooms()
            a_id = srv_a.router.local_node.node_id

            await FaultInjector().kill_node(srv_a)
            # The stale pin still names the dead node on the bus…
            assert await srv_b.router.get_node_for_room("chaos") == a_id

            # …until B's failover worker sees the lease expire and adopts.
            deadline = asyncio.get_event_loop().time() + 15
            while ("chaos" not in srv_b.room_manager.rooms
                   and asyncio.get_event_loop().time() < deadline):
                await asyncio.sleep(0.05)
            assert "chaos" in srv_b.room_manager.rooms, "failover never happened"
            # The restore creates the room and then moves the pin: wait for
            # the pin inside the same deadline rather than racing it.
            b_id = srv_b.router.local_node.node_id
            while (await srv_b.router.get_node_for_room("chaos") != b_id
                   and asyncio.get_event_loop().time() < deadline):
                await asyncio.sleep(0.05)
            assert await srv_b.router.get_node_for_room("chaos") == b_id

            rt_b = srv_b.room_manager.runtime
            row_b = srv_b.room_manager.rooms["chaos"].slots.row
            # Munger lane restored from the checkpoint, not reset.
            assert int(rt_b.munger.last_sn[row_b, 0, 1]) == 7004
            # The continued stream emits contiguous, monotonic SNs across
            # the node death (subscribers re-subscribe after failover, as
            # after migration — masks deliberately don't travel). B's
            # serving loop carries the traffic — stepping manually here
            # would race its pipelined fan-out and scramble arrival order.
            rt_b.set_subscription(row_b, 0, 1, subscribed=True)
            got_b = []
            rt_b.on_tick(lambda res: got_b.extend(
                p.sn for p in res.egress if p.sub == 1 and p.room == row_b))
            for i in range(5, 10):
                rt_b.ingest.push(PacketIn(room=row_b, track=0, sn=7000 + i,
                                          ts=960 * i, size=50, payload=b"b"))
                await asyncio.sleep(0.02)
            deadline = asyncio.get_event_loop().time() + 5
            while (len(got_b) < 5
                   and asyncio.get_event_loop().time() < deadline):
                await asyncio.sleep(0.05)
            assert got_b == list(range(7005, 7010))
            assert int(rt_b.munger.last_sn[row_b, 0, 1]) == 7009
            assert srv_b.telemetry.counters["livekit_room_failovers_total"] >= 1
    finally:
        for srv in (srv_a, srv_b):
            if srv is not None:
                await _stop_quiet(srv)
        bus.close()


@pytest.mark.slow
async def test_soak_lossy_ingest_stays_healthy():
    """Soak: hundreds of ticks of seeded drop+duplicate chaos at the
    ingest boundary — the plane keeps forwarding, per-sub egress SNs stay
    strictly increasing (drops gap, dups dedup), and accounting matches
    the injector's tally."""
    from livekit_server_tpu.models import plane
    from livekit_server_tpu.runtime import PlaneRuntime
    from livekit_server_tpu.runtime.faultinject import FaultSpec

    dims = plane.PlaneDims(rooms=2, tracks=4, pkts=4, subs=4)
    rt = PlaneRuntime(dims, tick_ms=10)
    inj = FaultInjector(FaultSpec(seed=42, drop_pct=0.1, dup_pct=0.1))
    rt.fault = inj
    rt.ingest.fault = inj
    rt.set_track(0, 0, published=True, is_video=False)
    rt.set_subscription(0, 0, 1, subscribed=True)

    egressed = []
    for i in range(400):
        rt.ingest.push(PacketIn(room=0, track=0, sn=(20000 + i) & 0xFFFF,
                                ts=960 * i, size=50, payload=b"s"))
        res = await rt.step_once()
        egressed += [p.sn for p in res.egress if p.sub == 1]

    assert inj.stats.dropped > 10 and inj.stats.duplicated > 10
    # Every non-dropped packet went out exactly once, in order.
    assert len(egressed) == 400 - inj.stats.dropped
    assert all(b > a for a, b in zip(egressed, egressed[1:]))
