"""Multi-node over a real network bus.

Reference parity: test/multinode_test.go — N servers against one shared
Redis: cross-node room routing + signal relay, node-shutdown takeover —
plus the room-migration seeding of pkg/rtc/participant.go:823
(MaybeStartMigration), here as whole-room media-plane row handoff.

The bus is the in-repo BusServer/TCPBusClient (routing/tcpbus.py) over
real TCP sockets — NOT the in-process MemoryBus.
"""

import asyncio
import json
import socket

import aiohttp
import numpy as np

from livekit_server_tpu.config import load_config
from livekit_server_tpu.models import plane
from livekit_server_tpu.routing.tcpbus import BusServer, TCPBusClient
from livekit_server_tpu.runtime import PlaneRuntime
from livekit_server_tpu.runtime.ingest import PacketIn
from livekit_server_tpu.service.server import create_server
from tests.conftest import free_port
from tests.test_service import API_KEY, API_SECRET, SignalClient, make_config


async def start_bus() -> BusServer:
    bus = BusServer()
    await bus.start("127.0.0.1", 0)
    return bus


async def start_node(bus_port: int, **cfg_overrides):
    client = await TCPBusClient.connect("127.0.0.1", bus_port)
    srv = create_server(make_config(free_port(), **cfg_overrides), bus=client)
    await srv.start()
    return srv, client


async def test_tcpbus_kv_and_pubsub():
    """The bus speaks the MessageBus protocol over real sockets: state
    written by one client is visible to another, and pub/sub (including
    patterns) fans out across connections."""
    bus = await start_bus()
    try:
        a = await TCPBusClient.connect("127.0.0.1", bus.port)
        b = await TCPBusClient.connect("127.0.0.1", bus.port)

        await a.hset("nodes", "n1", "one")
        assert await b.hget("nodes", "n1") == "one"
        assert await b.hgetall("nodes") == {"n1": "one"}
        await b.hdel("nodes", "n1")
        assert await a.hget("nodes", "n1") is None

        await a.set("k", "v", None)
        assert await b.get("k") == "v"
        assert await b.setnx("k", "other", None) is False
        await b.delete("k")
        assert await a.setnx("k", "other", None) is True

        sub = b.subscribe("room:*")
        exact = b.subscribe("room:one")
        n = await a.publish("room:one", "hello")
        assert n == 2
        assert await sub.read(timeout=2) == "hello"
        assert await exact.read(timeout=2) == "hello"
        sub.close()
        await asyncio.sleep(0.05)
        assert await a.publish("room:two", "x") == 0  # exact sub doesn't match
        await a.close()
        await b.close()
    finally:
        bus.close()


async def test_cross_node_session_over_tcp_bus():
    """Two servers, one bus: a room pinned to node A serves a participant
    whose WebSocket terminates on node B — the signal stream relays over
    the TCP bus (redisrouter signal relay, multinode_test.go)."""
    bus = await start_bus()
    srv_a = srv_b = None
    try:
        srv_a, _ = await start_node(bus.port)
        srv_b, _ = await start_node(bus.port)
        async with aiohttp.ClientSession() as s:
            alice = SignalClient(s, srv_a.port)
            await alice.connect("shared", "alice")
            # Room is now pinned to node A.
            assert (
                await srv_b.router.get_node_for_room("shared")
                == srv_a.router.local_node.node_id
            )
            bob = SignalClient(s, srv_b.port)
            join_b = await bob.connect("shared", "bob")
            # Bob's session actually lives on node A (relayed).
            assert join_b["participant"]["identity"] == "bob"
            others = [p["identity"] for p in join_b["other_participants"]]
            assert "alice" in others
            assert "shared" in srv_a.room_manager.rooms
            assert "shared" not in srv_b.room_manager.rooms
            # Cross-node signal round trip: bob's state update reaches the
            # room on A and fans back out to alice's socket on A.
            deadline = asyncio.get_event_loop().time() + 5
            seen_bob = False
            while not seen_bob and asyncio.get_event_loop().time() < deadline:
                seen_bob = any(
                    p.get("identity") == "bob"
                    for m in alice.signals
                    for p in m.get("update", {}).get("participants", [])
                )
                await asyncio.sleep(0.05)
            assert seen_bob, f"no bob update in {alice.signals}"
            await alice.close()
            await bob.close()
    finally:
        for srv in (srv_a, srv_b):
            if srv is not None:
                await srv.stop(force=True)
        bus.close()


async def test_dead_node_takeover():
    """Node A dies with a room pinned to it; a client hitting node B gets
    the room re-homed there instead of a dead relay (RemoveDeadNodes +
    the multinode shutdown-reconnect flow)."""
    bus = await start_bus()
    srv_a = srv_b = None
    try:
        srv_a, bus_a = await start_node(bus.port)
        srv_b, _ = await start_node(bus.port)
        async with aiohttp.ClientSession() as s:
            alice = SignalClient(s, srv_a.port)
            await alice.connect("takeover", "alice")
            await alice.close()
            a_id = srv_a.router.local_node.node_id
            # Crash A: heartbeat stops and it vanishes from the registry
            # (what the dead-node reaper does after staleness) but its room
            # pin is left behind — a graceful stop would have cleaned it,
            # a crash doesn't.
            srv_a.router._stats_task.cancel()
            srv_a.router._session_task.cancel()
            util = await TCPBusClient.connect("127.0.0.1", bus.port)
            await util.hdel("nodes", a_id)
            await util.close()
            # The pin still names the dead node…
            assert await srv_b.router.get_node_for_room("takeover") == a_id
            # …but a join through B re-homes the room locally.
            bob = SignalClient(s, srv_b.port)
            join = await bob.connect("takeover", "bob")
            assert join["participant"]["identity"] == "bob"
            assert "takeover" in srv_b.room_manager.rooms
            assert (
                await srv_b.router.get_node_for_room("takeover")
                == srv_b.router.local_node.node_id
            )
            await bob.close()
    finally:
        for srv in (srv_a, srv_b):
            if srv is not None:
                await srv.stop(force=True)
        bus.close()


async def test_room_migration_snapshot_continuity():
    """Row-level handoff: media flows through node A's plane, the room
    migrates, and the SAME stream continued on node B emits contiguous
    munged SNs — the forwarder-state seeding of participant.go:823, at
    whole-room granularity."""
    dims = plane.PlaneDims(rooms=2, tracks=4, pkts=4, subs=4)
    rt_a = PlaneRuntime(dims, tick_ms=10)
    rt_b = PlaneRuntime(dims, tick_ms=10)

    rt_a.set_track(0, 0, published=True, is_video=False)
    rt_a.set_subscription(0, 0, 1, subscribed=True)
    got_a = []
    for i in range(5):
        rt_a.ingest.push(PacketIn(room=0, track=0, sn=7000 + i, ts=960 * i,
                                  size=50, payload=b"a"))
        res = await rt_a.step_once()
        got_a += [p.sn for p in res.egress if p.sub == 1]
    assert got_a == list(range(7000, 7005))

    # Handoff A → B into a DIFFERENT row (row identity is node-local).
    snap = rt_a.snapshot_room(0)
    payload = PlaneRuntime.encode_room_snapshot(snap)
    rt_b.restore_room(1, PlaneRuntime.decode_room_snapshot(payload))

    # Track metadata migrated with the snapshot, but subscription masks
    # deliberately did NOT (a restored mask on a re-allocated sub column
    # would leak media) — the rejoining subscriber re-subscribes and its
    # munger lane resumes where it left off.
    rt_b.set_subscription(1, 0, 1, subscribed=True)
    got_b = []
    for i in range(5, 10):
        rt_b.ingest.push(PacketIn(room=1, track=0, sn=7000 + i, ts=960 * i,
                                  size=50, payload=b"b"))
        res = await rt_b.step_once()
        got_b += [p.sn for p in res.egress if p.sub == 1 and p.room == 1]
    assert got_b == list(range(7005, 7010))


async def test_room_handoff_over_bus():
    """Manager-level handoff: node A publishes the room snapshot to the
    bus and unpins; node B's get_or_create_room adopts it.

    Round-2 recorded a rare INVALID_ARGUMENT flake here. Round-3
    investigation: the snapshot-vs-donated-step discipline was audited —
    every self.state reader/writer (snapshot_room, restore_room, the test
    itself) holds state_lock, and the serving loop holds it across the
    donated device dispatch, so no donated buffer is reachable while a
    step is in flight; 16 consecutive runs under 3-4x synthetic CPU load
    did not reproduce. The round-2 environment had six stray synthetic-
    load processes running since its own flake testing (since killed),
    matching the 'extreme starvation' precondition. Treat any recurrence
    as a new bug with its own traceback, not a known shrug."""
    bus = await start_bus()
    srv_a = srv_b = None
    try:
        srv_a, _ = await start_node(bus.port)
        srv_b, _ = await start_node(bus.port)
        async with aiohttp.ClientSession() as s:
            alice = SignalClient(s, srv_a.port)
            await alice.connect("mig", "alice")
            row_a = srv_a.room_manager.rooms["mig"].slots.row
            # Put distinctive state into the room row (munger offsets).
            rt = srv_a.room_manager.runtime
            rt.set_track(row_a, 0, published=True, is_video=False)
            rt.set_subscription(row_a, 0, 1, subscribed=True)
            for i in range(3):
                rt.ingest.push(PacketIn(room=row_a, track=0, sn=100 + i,
                                        ts=0, size=10, payload=b"x"))
            # The node's serving loop is running, so step_once() would race
            # its deferred fan-out (and now raises); let the loop drain the
            # pushed packets and wait for the munger lane to advance.
            for _ in range(500):
                if int(rt.munger.last_sn[row_a, 0, 1]) == 102:
                    break
                await asyncio.sleep(0.01)
            assert int(rt.munger.last_sn[row_a, 0, 1]) == 102
            await alice.close()

            assert await srv_a.room_manager.handoff_room("mig")
            assert "mig" not in srv_a.room_manager.rooms

            room_b = await srv_b.room_manager.get_or_create_room("mig")
            rt_b = srv_b.room_manager.runtime
            # Munger state for (track 0, sub 1) migrated: last outgoing SN
            # survives the hop (host-side state since the round-5 split).
            last_sn = int(rt_b.munger.last_sn[room_b.slots.row, 0, 1])
            assert last_sn == 102
    finally:
        for srv in (srv_a, srv_b):
            if srv is not None:
                await srv.stop(force=True)
        bus.close()


async def test_two_phase_migration_under_load_over_bus():
    """The migration plane's tentpole drill over real TCP sockets: audio
    flows while the room migrates A → B through the two-phase handoff.
    Every pushed SN egresses exactly once — packets landing in the freeze
    window are bridged to the target, not dropped — and the munger lane
    continues contiguously on the target (no stream reset)."""
    bus = await start_bus()
    srv_a = srv_b = None
    try:
        # Deep per-tick packet slots: under full-suite CPU load a 10ms
        # tick can stretch past several pump periods, and the default 4
        # slots per (room, track) would capacity-drop legitimate audio
        # with no migration involved at all.
        srv_a, _ = await start_node(bus.port, pkts_per_track=16)
        srv_b, _ = await start_node(bus.port, pkts_per_track=16)
        rm_a, rm_b = srv_a.room_manager, srv_b.room_manager
        rt_a, rt_b = rm_a.runtime, rm_b.runtime
        assert rm_a.migration is not None and rm_b.migration is not None

        async with aiohttp.ClientSession() as s:
            alice = SignalClient(s, srv_a.port)
            await alice.connect("live", "alice")
            row_a = rm_a.rooms["live"].slots.row
            rt_a.set_track(row_a, 0, published=True, is_video=False)
            rt_a.set_subscription(row_a, 0, 1, subscribed=True)

            got: list[int] = []   # audio SNs egressed to sub 1, either node

            def collect(res):
                got.extend(
                    p.sn for p in res.egress if p.track == 0 and p.sub == 1
                )

            rt_a.on_tick(collect)
            rt_b.on_tick(collect)
            # Subscription masks don't travel; the adopting node re-arms
            # the listener (stand-in for the client's reconnect).
            rm_b.migration.on_adopt.append(
                lambda r: rt_b.set_subscription(
                    r.slots.row, 0, 1, subscribed=True
                )
            )

            stop = asyncio.Event()
            sent: list[int] = []

            async def pump():
                sn = 500
                while not stop.is_set():
                    for rm in (rm_a, rm_b):
                        room = rm.rooms.get("live")
                        if room is not None:
                            rm.runtime.ingest.push(PacketIn(
                                room=room.slots.row, track=0, sn=sn,
                                ts=960 * (sn - 500), size=40, payload=b"s",
                            ))
                            sent.append(sn)
                            sn += 1
                            break
                    await asyncio.sleep(0.004)

            async def pumped(n: int) -> None:
                """Wait, inside a deadline, until the pump has pushed `n`
                more packets: how many it gets into a fixed sleep depends
                on the host's load (95 alone, 45 beside five workers)."""
                want = len(sent) + n
                deadline = asyncio.get_event_loop().time() + 10.0
                while (len(sent) < want
                       and asyncio.get_event_loop().time() < deadline):
                    await asyncio.sleep(0.01)
                assert len(sent) >= want, "pump stalled"

            pump_task = asyncio.ensure_future(pump())
            await pumped(35)                       # media flowing on A
            assert await rm_a.migrate_room("live")
            assert "live" not in rm_a.rooms and "live" in rm_b.rooms
            assert (
                await srv_a.router.get_node_for_room("live")
                == srv_b.router.local_node.node_id
            )
            await pumped(35)                       # media flowing on B
            stop.set()
            await pump_task
            await asyncio.sleep(0.2)               # drain the last ticks

            # 100% audio continuity across the cutover: every pushed SN
            # egressed exactly once — none dropped in the freeze window,
            # none duplicated by the bridge replay. (Set equality, not
            # order: a bridged straggler may share a tick with a direct
            # push on the target.)
            assert sorted(got) == sent, (
                f"lost={sorted(set(sent) - set(got))[:10]} "
                f"dup={sorted(sn for sn in set(got) if got.count(sn) > 1)[:10]}"
            )
            assert len(got) > 60, "pump never reached the plane"
            # The lane continued — target's last SN is the last one sent.
            row_b = rm_b.rooms["live"].slots.row
            assert int(rt_b.munger.last_sn[row_b, 0, 1]) == sent[-1]
            st = rm_a.migration.stats
            assert st["commits"] == 1 and st["rollbacks"] == 0
            await alice.close()
    finally:
        for srv in (srv_a, srv_b):
            if srv is not None:
                await srv.stop(force=True)
        bus.close()


def make_fleet_config(port: int, extra: dict | None = None):
    """Drill-speed fleet timings. The no-overlap inequalities hold at
    scale: fence_grace 0.5 ≤ 2×lease_ttl 0.8 and 0.5 < lease_ttl 0.8 +
    failover_interval 0.4 — a dark node mutes (~0.7 s) strictly before
    the earliest takeover can finish (~1.2 s)."""
    doc = {
        "keys": {API_KEY: API_SECRET},
        "port": port,
        "bind_addresses": ["127.0.0.1"],
        "plane": {"rooms": 4, "tracks_per_room": 4, "pkts_per_track": 16,
                  "subs_per_room": 4, "tick_ms": 10},
        "rtc": {"udp_port": port + 1, "tcp_port": port + 2},
        "room": {"empty_timeout_s": 60},
        "kv": {"lease_ttl_s": 0.8, "failover_interval_s": 0.4,
               "stats_interval_s": 0.2},
        "fleet": {"fence_grace_s": 0.5, "restore_lock_ttl_s": 2.0},
        "supervisor": {"checkpoint_interval_s": 0.2},
    }
    for section, values in (extra or {}).items():
        doc[section] = {**doc.get(section, {}), **values}
    return load_config(yaml_text=json.dumps(doc))


async def start_fleet_node(bus_port: int, extra: dict | None = None):
    client = await TCPBusClient.connect("127.0.0.1", bus_port)
    srv = create_server(make_fleet_config(free_port(), extra=extra), bus=client)
    await srv.start()
    return srv, client


async def _wait_for(cond, timeout: float, what: str) -> None:
    deadline = asyncio.get_event_loop().time() + timeout
    while not cond():
        assert asyncio.get_event_loop().time() < deadline, f"timed out: {what}"
        await asyncio.sleep(0.02)


async def test_split_brain_fences_minority_and_takeover_wins():
    """The fleet plane's tentpole drill: a 2|1 bus partition darks node A
    while its room keeps producing media. The minority self-fences (wire
    mute engages while the plane is still producing — the shadow SNs
    prove the mute is load-bearing), the majority completes an elected
    takeover strictly after the mute, and the heal ends with exactly one
    owner, ZERO duplicate wire packets, and A's stale checkpoint write
    rejected by the epoch CAS."""
    bus = await start_bus()
    srv_a = srv_b = None
    try:
        srv_a, _ = await start_fleet_node(bus.port)
        srv_b, _ = await start_fleet_node(bus.port)
        rm_a, rm_b = srv_a.room_manager, srv_b.room_manager
        rt_a, rt_b = rm_a.runtime, rm_b.runtime
        a_id = srv_a.router.local_node.node_id
        b_id = srv_b.router.local_node.node_id
        async with aiohttp.ClientSession() as s:
            alice = SignalClient(s, srv_a.port)
            await alice.connect("sb", "alice")
            await alice.close()
            row_a = rm_a.rooms["sb"].slots.row
            rt_a.set_track(row_a, 0, published=True, is_video=False)
            rt_a.set_subscription(row_a, 0, 1, subscribed=True)

            got: list[int] = []      # wire-visible egress (fence-gated)
            shadow: list[int] = []   # produced by A's plane WHILE fenced

            def collect_a(res):
                sns = [p.sn for p in res.egress
                       if p.track == 0 and p.sub == 1]
                # Mirror the wire gate: a fenced tick's egress never
                # reaches a socket (_dispatch_tick mute), and residual
                # packets draining after the replica closed have no
                # row→room mapping left to route them by.
                wire_visible = not rm_a.fleet.fenced and "sb" in rm_a.rooms
                (got if wire_visible else shadow).extend(sns)

            rt_a.on_tick(collect_a)
            rt_b.on_tick(
                lambda res: got.extend(
                    p.sn for p in res.egress if p.track == 0 and p.sub == 1
                )
            )

            stop = asyncio.Event()
            sent: list[int] = []

            async def pump():
                sn = 500
                while not stop.is_set():
                    pushed = False
                    # Push the SAME SN into EVERY replica: while both
                    # nodes hold the room, only the fence keeps the wire
                    # duplicate-free.
                    for rm in (rm_a, rm_b):
                        room = rm.rooms.get("sb")
                        if room is not None:
                            rm.runtime.ingest.push(PacketIn(
                                room=room.slots.row, track=0, sn=sn,
                                ts=960 * (sn - 500), size=40, payload=b"s",
                            ))
                            pushed = True
                    if pushed:
                        sent.append(sn)
                        sn += 1
                    await asyncio.sleep(0.004)

            pump_task = asyncio.ensure_future(pump())
            await asyncio.sleep(0.5)          # media + a checkpoint on A

            bus.set_partition([[b_id], [a_id]])
            # Minority goes silent on its own, within fence_grace (+ one
            # lease beat + scheduling slop).
            await _wait_for(lambda: rm_a.fleet.fenced, 3.0, "A never fenced")
            assert "fenced" in (rm_a._admission_denied("room") or "")
            # Majority elects itself and restores from A's checkpoint —
            # strictly AFTER the mute (the no-overlap timeline).
            await _wait_for(lambda: "sb" in rm_b.rooms, 6.0, "no takeover")
            assert rm_a.fleet.fenced, "takeover finished before the mute"
            rt_b.set_subscription(rm_b.rooms["sb"].slots.row, 0, 1,
                                  subscribed=True)
            await asyncio.sleep(0.3)          # dual-replica window

            bus.heal_partition()
            # A's next good lease triggers reconcile: the stale checkpoint
            # write loses its epoch CAS, which closes A's replica, and
            # only then does A unfence.
            await _wait_for(
                lambda: not rm_a.fleet.fenced and "sb" not in rm_a.rooms,
                5.0, "A never reconciled",
            )
            await asyncio.sleep(0.2)
            stop.set()
            await pump_task
            await asyncio.sleep(0.2)          # drain the last ticks

            # ZERO duplicate wire packets across partition + heal…
            dup = sorted(sn for sn in set(got) if got.count(sn) > 1)
            assert not dup, f"duplicate wire SNs: {dup[:10]}"
            # …and not because A went idle: its plane kept producing
            # wire-bound egress that ONLY the fence suppressed.
            assert shadow, "A's plane never produced while fenced"
            assert set(shadow) & set(got), "no suppressed would-be dup"
            # Stale owner's post-heal checkpoint write rejected by CAS.
            assert rm_a.fleet.fence.stats["writes_fenced"] >= 1
            assert rm_a.fleet.stats == {
                **rm_a.fleet.stats, "fences": 1, "recoveries": 1,
                "rooms_lost": 1,
            }
            assert rm_a.fleet.stats["muted_ticks"] > 0
            # Exactly one owner at a strictly higher epoch.
            epoch, holder = await rm_b.fleet.fence.read("sb")
            assert holder == b_id and epoch >= 2
            assert await srv_b.router.get_node_for_room("sb") == b_id
    finally:
        for srv in (srv_a, srv_b):
            if srv is not None:
                await srv.stop(force=True)
        bus.close()


async def test_node_kill_elected_failover_restores_every_room():
    """Node-kill drill: A dies holding two rooms while two survivors
    race the same dead-pin scan. The create-lock + epoch-CAS election
    gives every room exactly one restorer, and the media room comes back
    with 100% audio continuity (every pushed SN egresses exactly once,
    lane contiguous across the failover)."""
    bus = await start_bus()
    srvs: list = [None, None, None]
    try:
        for i in range(3):
            srvs[i], _ = await start_fleet_node(bus.port)
        srv_a, srv_b, srv_c = srvs
        rm_a, rm_b, rm_c = (s.room_manager for s in srvs)
        rt_a = rm_a.runtime
        async with aiohttp.ClientSession() as s:
            for room_name in ("k1", "k2"):
                cl = SignalClient(s, srv_a.port)
                await cl.connect(room_name, "pub")
                await cl.close()
            row_a = rm_a.rooms["k1"].slots.row
            rt_a.set_track(row_a, 0, published=True, is_video=False)
            rt_a.set_subscription(row_a, 0, 1, subscribed=True)

            got: list[int] = []
            for rm in (rm_a, rm_b, rm_c):
                rm.runtime.on_tick(
                    lambda res: got.extend(
                        p.sn for p in res.egress
                        if p.track == 0 and p.sub == 1
                    )
                )
            # Subscriptions never travel in a snapshot (restore_room
            # clears the masks — a restored bit on a re-allocated sub
            # column would leak media), so model the subscriber re-attach
            # the way production does: re-subscribe at adoption time,
            # before the room is visible to ingest.
            for rm in (rm_b, rm_c):
                rm.on_adopt.append(
                    (lambda rm_: lambda room: (
                        rm_.runtime.set_subscription(
                            room.slots.row, 0, 1, subscribed=True
                        ) if room.name == "k1" else None
                    ))(rm)
                )

            live = [rm_a, rm_b, rm_c]
            stop = asyncio.Event()
            sent: list[int] = []

            async def pump():
                sn = 900
                while not stop.is_set():
                    for rm in list(live):
                        room = rm.rooms.get("k1")
                        if room is not None:
                            rm.runtime.ingest.push(PacketIn(
                                room=room.slots.row, track=0, sn=sn,
                                ts=960 * (sn - 900), size=40, payload=b"s",
                            ))
                            sent.append(sn)
                            sn += 1
                            break
                    await asyncio.sleep(0.004)

            pump_task = asyncio.ensure_future(pump())
            await _wait_for(lambda: len(sent) >= 20, 10.0,
                            "pump never reached A")
            # Quiesce the pump and let A's lane drain, then force a fresh
            # checkpoint so the survivors restore the full lane.
            live.remove(rm_a)
            await _wait_for(
                lambda: not sent
                or int(rt_a.munger.last_sn[row_a, 0, 1]) == sent[-1],
                3.0, "A's lane never drained",
            )
            await rm_a.checkpoint_rooms()
            # Crash A: heartbeat and session relay stop; the lease lapses
            # on its own. (A's plane keeps running — its later checkpoint
            # writes must LOSE the epoch CAS once a survivor claims.)
            srv_a.router._stats_task.cancel()
            srv_a.router._session_task.cancel()

            def owners(name):
                return [rm for rm in (rm_b, rm_c) if name in rm.rooms]

            # Generous window: on a loaded shared-CPU rig a single XLA
            # compile can stall the loop 15-20 s, which once ate the whole
            # wait — the failover itself completes in ~1.2 s when the loop
            # is scheduled.
            await _wait_for(
                lambda: owners("k1") and owners("k2"), 45.0,
                "rooms never failed over",
            )
            assert len(owners("k1")) == 1 and len(owners("k2")) == 1
            winner = owners("k1")[0]
            pumped_to_a = len(sent)
            await _wait_for(lambda: len(sent) >= pumped_to_a + 20, 10.0,
                            "pump never reached the winner")
            stop.set()
            await pump_task
            row_w = winner.rooms["k1"].slots.row
            await _wait_for(
                lambda: int(winner.runtime.munger.last_sn[row_w, 0, 1])
                == sent[-1],
                3.0, "winner's lane never drained",
            )
            await asyncio.sleep(0.1)   # let the last tick's fan-out land

            # 100% audio continuity: every pushed SN egressed exactly once.
            assert sorted(got) == sent, (
                f"lost={sorted(set(sent) - set(got))[:10]} "
                f"dup={sorted(sn for sn in set(got) if got.count(sn) > 1)[:10]}"
            )
            assert len(got) >= 40, "pump never reached the plane"
            # Exactly one elected restorer per room across the fleet.
            restored = sum(
                rm.fleet.orchestrator.stats["restored"] for rm in (rm_b, rm_c)
            )
            assert restored == 2
            for name in ("k1", "k2"):
                epoch, holder = await rm_b.fleet.fence.read(name)
                assert holder == owners(name)[0].fleet.fence.node_id
                assert epoch >= 2
    finally:
        for srv in srvs:
            if srv is not None:
                await srv.stop(force=True)
        bus.close()


async def test_rebalancer_sheds_hot_node_with_continuity():
    """Load-aware rebalancing rides the migration plane: the node holding
    every room sheds its emptiest one to the idle peer, and media in the
    moved room survives the hop with every SN egressing exactly once."""
    extra = {"fleet": {
        "rebalance_enabled": True, "rebalance_interval_s": 0.3,
        "rebalance_headroom": 0.25, "rebalance_max_moves": 1,
    }}
    bus = await start_bus()
    srv_a = srv_b = None
    try:
        srv_a, _ = await start_fleet_node(bus.port, extra=extra)
        srv_b, _ = await start_fleet_node(bus.port, extra=extra)
        rm_a, rm_b = srv_a.room_manager, srv_b.room_manager
        async with aiohttp.ClientSession() as s:
            alice = SignalClient(s, srv_a.port)
            await alice.connect("keep", "alice")     # stays connected
            bob = SignalClient(s, srv_a.port)
            await bob.connect("mover", "bob")
            await bob.close()                        # mover: 0 participants
            row_a = rm_a.rooms["mover"].slots.row
            rm_a.runtime.set_track(row_a, 0, published=True, is_video=False)
            rm_a.runtime.set_subscription(row_a, 0, 1, subscribed=True)
            rm_b.migration.on_adopt.append(
                lambda r: rm_b.runtime.set_subscription(
                    r.slots.row, 0, 1, subscribed=True
                )
            )

            got: list[int] = []
            for rm in (rm_a, rm_b):
                rm.runtime.on_tick(
                    lambda res: got.extend(
                        p.sn for p in res.egress
                        if p.track == 0 and p.sub == 1
                    )
                )
            stop = asyncio.Event()
            sent: list[int] = []

            async def pump():
                sn = 300
                while not stop.is_set():
                    for rm in (rm_a, rm_b):
                        room = rm.rooms.get("mover")
                        if room is not None:
                            rm.runtime.ingest.push(PacketIn(
                                room=room.slots.row, track=0, sn=sn,
                                ts=960 * (sn - 300), size=40, payload=b"s",
                            ))
                            sent.append(sn)
                            sn += 1
                            break
                    await asyncio.sleep(0.004)

            pump_task = asyncio.ensure_future(pump())
            # The rebalancer picks the emptiest room on the hottest node:
            # "mover" (0 participants) leaves, "keep" (alice) stays.
            # Moved = adopted on B (PREPARE) and released on A (COMMIT
            # resolution) — the source replica lives until the commit.
            await _wait_for(
                lambda: "mover" in rm_b.rooms and "mover" not in rm_a.rooms,
                20.0, "no rebalance",
            )
            assert "keep" in rm_a.rooms
            moved_at = len(sent)
            await _wait_for(lambda: len(sent) >= moved_at + 20, 10.0,
                            "pump never reached the target")
            stop.set()
            await pump_task
            row_b = rm_b.rooms["mover"].slots.row
            await _wait_for(
                lambda: int(rm_b.runtime.munger.last_sn[row_b, 0, 1])
                == sent[-1],
                3.0, "target's lane never drained",
            )
            await asyncio.sleep(0.1)   # let the last tick's fan-out land

            assert sorted(got) == sent, (
                f"lost={sorted(set(sent) - set(got))[:10]} "
                f"dup={sorted(sn for sn in set(got) if got.count(sn) > 1)[:10]}"
            )
            assert rm_a.fleet.rebalancer.stats["moves"] >= 1
            assert rm_a.migration.stats["commits"] >= 1
            epoch, holder = await rm_b.fleet.fence.read("mover")
            assert holder == srv_b.router.local_node.node_id and epoch >= 2
            await alice.close()
    finally:
        for srv in (srv_a, srv_b):
            if srv is not None:
                await srv.stop(force=True)
        bus.close()


async def test_stale_commit_after_heal_dropped_by_epoch_guard():
    """Migration under partition: an asymmetric A→B link holds the
    PREPARE in flight, the source times out and rolls back, and the heal
    delivers the whole stale handshake late — the target adopts, obeys
    the late ABORT, and a COMMIT naming the dead epoch is dropped by the
    epoch guard. Exactly one node serves the room throughout."""
    extra = {"migration": {
        "ack_timeout_s": 0.3, "retry_attempts": 1,
        "retry_backoff_base_s": 0.05, "adopt_ttl_s": 1.0,
    }}
    bus = await start_bus()
    srv_a = srv_b = None
    try:
        srv_a, cl_a = await start_fleet_node(bus.port, extra=extra)
        srv_b, _ = await start_fleet_node(bus.port, extra=extra)
        rm_a, rm_b = srv_a.room_manager, srv_b.room_manager
        a_id = srv_a.router.local_node.node_id
        b_id = srv_b.router.local_node.node_id
        async with aiohttp.ClientSession() as s:
            alice = SignalClient(s, srv_a.port)
            await alice.connect("part", "alice")
            await alice.close()

            # One-way link failure: A's pushes to B are held (not lost).
            # KV still works both ways, so leases stay healthy — this is
            # a migration-plane partition, not a node death.
            bus.set_partition([], asym_pairs=[(a_id, b_id)])
            assert not await rm_a.migration.migrate_room("part", b_id)
            assert "part" in rm_a.rooms        # rolled back, still source
            stale_epoch = rm_a.migration._epoch

            bus.heal_partition()
            # The held PREPARE adopts on B, the held ABORT (or the adopt
            # reaper) releases it again — transient, never an owner.
            await _wait_for(
                lambda: rm_b.migration.stats["adoptions"] >= 1, 5.0,
                "late PREPARE never adopted",
            )
            await _wait_for(
                lambda: "part" not in rm_b.rooms
                and not rm_b.migration._adoptions,
                5.0, "late adoption never released",
            )
            # The COMMIT from the timed-out attempt finally arrives —
            # naming a dead epoch. The guard drops it instead of
            # finalizing a handoff the source already rolled back.
            before = rm_b.migration.stats["stale_commits"]
            await cl_a.publish(
                f"node_migrate:{b_id}",
                {"kind": "commit", "room": "part", "epoch": stale_epoch},
            )
            await _wait_for(
                lambda: rm_b.migration.stats["stale_commits"] > before,
                3.0, "stale COMMIT not counted",
            )
            assert "part" not in rm_b.rooms
            # Exactly one owner the whole way: pin and epoch still name A.
            assert "part" in rm_a.rooms
            assert await srv_b.router.get_node_for_room("part") == a_id
            _epoch, holder = await rm_a.fleet.fence.read("part")
            assert holder == a_id
            assert rm_a.migration.stats["rollbacks"] >= 1
    finally:
        for srv in (srv_a, srv_b):
            if srv is not None:
                await srv.stop(force=True)
        bus.close()


async def test_bus_auth():
    """A token-bearing bus is the Redis-AUTH seat: unauthenticated clients
    are refused every op (the bus carries room pins and signal relay, so
    open access is cluster takeover), tokened clients work normally."""
    bus = BusServer(token="s3cret")
    await bus.start("127.0.0.1", 0)
    try:
        intruder = await TCPBusClient.connect("127.0.0.1", bus.port)
        try:
            await intruder.hset("room_node_map", "victim", "evil-node")
            raise AssertionError("unauthenticated op accepted")
        except (RuntimeError, ConnectionError):
            pass  # refused (and the connection is dropped)

        member = await TCPBusClient.connect("127.0.0.1", bus.port, token="s3cret")
        await member.hset("nodes", "n1", "one")
        assert await member.hget("nodes", "n1") == "one"
        assert await member.hget("room_node_map", "victim") is None
        await member.close()
    finally:
        bus.close()


async def test_roomservice_ops_against_non_hosting_node():
    """Admin RPCs hit node B for a room hosted on node A and are relayed
    to the hosting node over the bus (multinode_roomservice_test.go)."""
    from livekit_server_tpu.auth import AccessToken, VideoGrant
    from tests.test_service import API_KEY, API_SECRET

    bus = await start_bus()
    srv_a = srv_b = None
    try:
        srv_a, _ = await start_node(bus.port)
        srv_b, _ = await start_node(bus.port)
        async with aiohttp.ClientSession() as s:
            alice = SignalClient(s, srv_a.port)
            await alice.connect("hosted-on-a", "alice")
            assert "hosted-on-a" in srv_a.room_manager.rooms

            t = AccessToken(API_KEY, API_SECRET)
            t.grant = VideoGrant(room_admin=True, room="hosted-on-a")
            hdr = {"Authorization": f"Bearer {t.to_jwt()}"}
            base_b = f"http://127.0.0.1:{srv_b.port}/twirp/livekit.RoomService"

            # List participants via the NON-hosting node.
            async with s.post(
                f"{base_b}/ListParticipants", json={"room": "hosted-on-a"},
                headers=hdr,
            ) as r:
                assert r.status == 200, await r.text()
                parts = (await r.json())["participants"]
                assert [p["identity"] for p in parts] == ["alice"]

            # Mutate metadata via the non-hosting node; the hosting node's
            # room object changes and alice gets the update.
            async with s.post(
                f"{base_b}/UpdateRoomMetadata",
                json={"room": "hosted-on-a", "metadata": "via-node-b"},
                headers=hdr,
            ) as r:
                assert r.status == 200, await r.text()
            assert srv_a.room_manager.rooms["hosted-on-a"].info.metadata == "via-node-b"

            # Remove alice via the non-hosting node.
            async with s.post(
                f"{base_b}/RemoveParticipant",
                json={"room": "hosted-on-a", "identity": "alice"},
                headers=hdr,
            ) as r:
                assert r.status == 200, await r.text()
            deadline = asyncio.get_event_loop().time() + 3
            while (
                (room_a := srv_a.room_manager.rooms.get("hosted-on-a")) is not None
                and "alice" in room_a.participants
                and asyncio.get_event_loop().time() < deadline
            ):
                await asyncio.sleep(0.05)
            assert room_a is None or "alice" not in room_a.participants
            await alice.close()
    finally:
        for srv in (srv_a, srv_b):
            if srv is not None:
                await srv.stop(force=True)
        bus.close()


async def test_bus_client_reconnects_and_resubscribes():
    """A dropped bus connection must not sever the node permanently (the
    go-redis auto-reconnect seat): calls fail during the outage, then
    succeed again, and live subscriptions are re-issued on the fresh
    connection."""
    bus = await start_bus()
    port = bus.port
    try:
        client = await TCPBusClient.connect("127.0.0.1", port)
        other = await TCPBusClient.connect("127.0.0.1", port)
        sub = client.subscribe("announce")
        await client.set("k", "v1")
        await asyncio.sleep(0.05)

        # Sever the client's connection out from under it (network blip).
        client._writer.transport.abort()
        deadline = asyncio.get_event_loop().time() + 3
        while client.reconnects == 0:
            assert asyncio.get_event_loop().time() < deadline, "no reconnect"
            await asyncio.sleep(0.05)
        assert await client.get("k") == "v1"          # calls work again
        await asyncio.sleep(0.05)                      # re-sub settles
        await other.publish("announce", {"hello": 1})  # pushes flow again
        msg = await sub.read(timeout=3)
        assert msg == {"hello": 1}

        # Full bus-process restart on the same port: state is fresh (like
        # a flushed Redis) but the client recovers without intervention.
        bus.close()
        client._writer.transport.abort()
        other._writer.transport.abort()
        await asyncio.sleep(0.1)
        bus2 = BusServer()
        await bus2.start("127.0.0.1", port)
        try:
            deadline = asyncio.get_event_loop().time() + 5
            while True:
                try:
                    await client.set("k2", "v2")
                    break
                except ConnectionError:
                    assert asyncio.get_event_loop().time() < deadline
                    await asyncio.sleep(0.1)
            assert await client.get("k2") == "v2"
            await client.close()
            await other.close()
        finally:
            bus2.close()
    finally:
        bus.close()

async def test_bus_client_survives_malformed_frame():
    """A malformed frame (bad JSON, or a frame with neither 'p' nor 'i')
    means the stream is desynced: the client must treat it as connection
    loss and reconnect — not die with _connected=True, which would hang
    every pending and future call forever."""
    import json as _json

    bus = await start_bus()
    try:
        client = await TCPBusClient.connect("127.0.0.1", bus.port)
        await client.set("k", "v1")

        def inject(raw: bytes) -> None:
            client._reader.feed_data(len(raw).to_bytes(4, "big") + raw)

        # Structurally invalid frame: valid JSON lacking both 'p' and 'i'.
        inject(_json.dumps({"x": 1}).encode())
        deadline = asyncio.get_event_loop().time() + 3
        while client.reconnects == 0:
            assert asyncio.get_event_loop().time() < deadline, (
                "malformed frame killed the reader without reconnecting"
            )
            await asyncio.sleep(0.05)
        assert not client.closed
        assert await client.get("k") == "v1"

        # Byte-garbage frame (json.JSONDecodeError path), on the fresh
        # connection this time.
        inject(b"\xff not json \xff")
        deadline = asyncio.get_event_loop().time() + 3
        while client.reconnects < 2:
            assert asyncio.get_event_loop().time() < deadline, "no 2nd reconnect"
            await asyncio.sleep(0.05)
        assert await client.get("k") == "v1"
        await client.close()
    finally:
        bus.close()


async def test_egress_records_from_dead_node_reaped():
    """Lifecycle reaper (redisstore.go:67-944 cleanup-worker seat): an
    egress whose worker/node dies mid-job must not stay ACTIVE in every
    node's aggregator forever — it goes FAILED after the stale window and
    expires after the ended TTL, so ListEgress stays clean cluster-wide."""
    import json as _json
    import time as _time

    from livekit_server_tpu.service.egress import EgressStatus

    bus = await start_bus()
    try:
        srv_a, cl_a = await start_node(bus.port)
        srv_b, cl_b = await start_node(bus.port)
        try:
            # A worker (lived on some third node) reports an ACTIVE egress;
            # both aggregators adopt it.
            info = {
                "egress_id": "EG_dead", "room_name": "r", "kind": "track",
                "status": int(EgressStatus.ACTIVE), "started_at": 0,
                "ended_at": 0, "error": "", "request": {},
            }
            await cl_a.publish("egress_updates", _json.dumps(info))
            await asyncio.sleep(0.1)
            assert "EG_dead" in srv_a.ioinfo.egresses
            assert "EG_dead" in srv_b.ioinfo.egresses

            # The worker's node dies (no further updates). After the stale
            # window the record is failed...
            now = _time.monotonic()
            for srv in (srv_a, srv_b):
                srv.ioinfo.reap(now + srv.ioinfo.STALE_ACTIVE_S + 1)
                rec = srv.ioinfo.egresses["EG_dead"]
                assert rec.status == EgressStatus.FAILED
                assert "lost" in rec.error
            # ...and after the ended TTL it is gone from every List.
            for srv in (srv_a, srv_b):
                srv.ioinfo.reap(
                    _time.monotonic() + srv.ioinfo.ENDED_TTL_S + 1
                )
                assert "EG_dead" not in srv.ioinfo.egresses
        finally:
            await srv_a.stop()
            await srv_b.stop()
            await cl_a.close()
            await cl_b.close()
    finally:
        bus.close()
