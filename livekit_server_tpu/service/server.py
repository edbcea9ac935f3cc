"""Server assembly + lifecycle.

Reference parity: pkg/service/server.go (LivekitServer :46-61, Start
:170-293, Stop :295-316, health :351-364) and the Wire DI graph
(wire_gen.go:38-138) — here plain constructor wiring in create_server().
Endpoints: /rtc (WS signal+media), /twirp/livekit.RoomService/* (admin),
/ (health), /metrics (prometheus text format), /debug/rooms.
"""

from __future__ import annotations

import asyncio
import secrets
import time

from aiohttp import web

from livekit_server_tpu.config.config import Config, ConfigError
from livekit_server_tpu.routing import (
    LocalNode,
    MemoryBus,
    NodeState,
    create_router,
    create_selector,
)
from livekit_server_tpu.routing.node import sample_system_stats
from livekit_server_tpu.routing.selector import NoNodesAvailable
from livekit_server_tpu.runtime.compile_ledger import LEDGER
from livekit_server_tpu.service.roommanager import RoomManager
from livekit_server_tpu.service.roomservice import RoomServiceAPI
from livekit_server_tpu.service.rtcservice import RTCService
from livekit_server_tpu.service.store import KVStore, LocalStore
from livekit_server_tpu.telemetry import TelemetryService
from livekit_server_tpu.version import __version__


class LivekitServer:
    def __init__(self, config: Config, router, store, room_manager, telemetry):
        self.config = config
        self.router = router
        self.store = store
        self.room_manager: RoomManager = room_manager
        self.telemetry: TelemetryService = telemetry
        from livekit_server_tpu.service.agents import AgentService
        from livekit_server_tpu.service.egress import EgressService
        from livekit_server_tpu.service.ingress import IngressService
        from livekit_server_tpu.service.sip import SIPService

        self.rtc_service = RTCService(self)
        self.room_api = RoomServiceAPI(self)
        self.egress = EgressService(self)
        self.ingress = IngressService(self)
        self.sip = SIPService(self)
        from livekit_server_tpu.service.ioinfo import IOInfoService

        self.ioinfo = IOInfoService(self)
        self.agents = AgentService(self)
        room_manager.agents = self.agents
        from livekit_server_tpu.utils.logger import Logger, configure

        configure(config.log_level)
        self.log = Logger(node=router.local_node.node_id[:12])
        room_manager.log = self.log
        self.app = web.Application(middlewares=[self._request_hooks])
        self.app.router.add_get("/", self.health)
        self.app.router.add_get("/rtc", self.rtc_service.handle)
        self.app.router.add_get("/rtc/validate", self.validate)
        self.app.router.add_get("/agent", self.agents.handle)
        self.app.router.add_post(
            "/twirp/livekit.RoomService/{method}", self.room_api.handle
        )
        self.app.router.add_post("/twirp/livekit.Egress/{method}", self.egress.handle)
        self.app.router.add_post("/twirp/livekit.Ingress/{method}", self.ingress.handle)
        self.app.router.add_post("/twirp/livekit.SIP/{method}", self.sip.handle)
        self.app.router.add_get("/metrics", self.metrics)
        self.app.router.add_get("/debug/rooms", self.debug_rooms)
        self.app.router.add_get("/debug/analytics", self.debug_analytics)
        self.app.router.add_get("/debug/tasks", self.debug_tasks)
        self.app.router.add_get("/debug/ticks", self.debug_ticks)
        self.app.router.add_get("/debug/overload", self.debug_overload)
        self.app.router.add_get("/debug/pager", self.debug_pager)
        self.app.router.add_get("/debug/integrity", self.debug_integrity)
        self.app.router.add_get("/debug/compiles", self.debug_compiles)
        self.app.router.add_get("/debug/egress", self.debug_egress)
        self.app.router.add_get("/debug/migration", self.debug_migration)
        self.app.router.add_get("/debug/fleet", self.debug_fleet)
        self.app.router.add_get("/debug/trace", self.debug_trace)
        self.app.router.add_get("/debug/blackbox/{room}", self.debug_blackbox)
        self._runner: web.AppRunner | None = None
        self._sites: list[web.TCPSite] = []
        self._stats_task: asyncio.Task | None = None
        self.started_at = 0.0
        # {phase: {wall_s, compile_s}} of start-up (/debug/compiles).
        self.startup: dict[str, dict[str, float]] = {}

    # -- selector ---------------------------------------------------------
    def select_node(self) -> LocalNode | None:
        """Pick an RTC node for a new room (roomallocator.go)."""
        nodes = getattr(self, "_node_cache", None) or [self.router.local_node]
        try:
            return self._selector.select_node(nodes)
        except NoNodesAvailable:
            return None

    async def _refresh_nodes(self) -> None:
        while True:
            self._node_cache = await self.router.list_nodes()
            sample_system_stats(self.router.local_node.stats)
            # Per-participant traffic rates → NodeStats packet/byte rates
            # (participant_traffic_load.go cadence).
            self.room_manager.sample_traffic()
            await asyncio.sleep(2.0)

    def room_manager_media_queue(self, room_name: str, identity: str):
        room = self.room_manager.rooms.get(room_name)
        if room is None:
            return None
        p = room.participants.get(identity)
        return getattr(p, "media_queue", None) if p else None

    # -- endpoints --------------------------------------------------------
    async def health(self, request: web.Request) -> web.Response:
        # server.go:351 — 406 when node stats are stale
        age = time.time() - self.router.local_node.stats.updated_at
        if age > 4.0 and self.started_at and time.time() - self.started_at > 4.0:
            return web.Response(status=406, text=f"node stats stale ({age:.1f}s)")
        return web.Response(text="OK")

    async def validate(self, request: web.Request) -> web.Response:
        """rtcservice.go validate — join preflight without upgrading."""
        from livekit_server_tpu.auth import TokenError, verify_token

        token = request.query.get("access_token", "")
        try:
            claims = verify_token(token, self.config.keys)
        except TokenError as e:
            return web.Response(status=401, text=str(e))
        if not claims.video.room_join:
            return web.Response(status=401, text="token lacks roomJoin")
        return web.Response(text="success")

    @web.middleware
    async def _request_hooks(self, request: web.Request, handler):
        """Twirp request logging + status metrics (the TwirpLogger /
        request-status hooks of service/server.go's Twirp server options)."""
        t0 = time.perf_counter()
        status = 500
        try:
            resp = await handler(request)
            status = resp.status
            return resp
        except web.HTTPException as e:
            status = e.status
            raise
        except asyncio.CancelledError:
            status = 499  # client went away; not a server error
            raise
        finally:
            if request.path.startswith("/twirp/"):
                svc = request.path.split("/")[2]
                method = request.match_info.get("method", "")
                self.telemetry.add(
                    "livekit_twirp_requests_total",
                    service=svc, method=method, status=str(status),
                )
                self.log.info(
                    "twirp", service=svc, method=method, status=status,
                    dur_ms=round((time.perf_counter() - t0) * 1000.0, 2),
                )

    async def debug_tasks(self, request: web.Request) -> web.Response:
        """Asyncio task dump (the pprof goroutine-profile analog, §5.1)."""
        tasks = []
        for t in asyncio.all_tasks():
            tasks.append({
                "name": t.get_name(),
                "done": t.done(),
                "coro": str(getattr(t.get_coro(), "__qualname__", t.get_coro())),
            })
        return web.json_response({"count": len(tasks), "tasks": tasks})

    async def debug_ticks(self, request: web.Request) -> web.Response:
        """Recent tick timing breakdown (§5.1 profiling surface): totals
        plus the per-tick pipeline-stage split (stage/device/fanout ms,
        depth, late, and where the tick waited) so an overlap regression
        is visible per stage rather than inferred from host_ms_per_tick."""
        rt = self.room_manager.runtime
        body = {
            "tick_ms": rt.tick_ms,
            "stats": rt.stats,
            # the depth of the last tick (`PlaneRuntime.choose_depth`)
            "pipeline_depth": rt.recent_ticks[-1]["depth"] if rt.recent_ticks else 0,
            "recent_ticks": list(getattr(rt, "recent_ticks", [])),
        }
        body["sleep_bias_us"] = round(
            max(getattr(rt, "_sleep_bias", 0.0), 0.0) * 1e6, 1
        )
        body["edge_overshoot_us"] = round(
            getattr(rt, "_edge_overshoot_us", 0.0), 1
        )
        if rt.wire_stages is not None:
            # Per-stage wire-latency decomposition (sampled attribution).
            body["wire_stages"] = rt.wire_stages.summary()
        udp = getattr(self.room_manager, "udp", None)
        if udp is not None and getattr(udp, "fwd_latency", None) is not None:
            # Measured wall-clock packet-in→wire-out latency (includes
            # tick-queueing wait) — the probe in runtime/udp.py.
            body["forward_latency"] = udp.fwd_latency.summary()
        if rt.express is not None:
            body["express"] = rt.express.debug()
            if udp is not None:
                # Express twin: arrival-driven, no tick-queue wait.
                body["forward_latency_express"] = (
                    udp.fwd_latency_express.summary()
                )
        return web.json_response(body)

    async def debug_trace(self, request: web.Request) -> web.Response:
        """Chrome/Perfetto trace export of the tick-span ring
        (?ticks=N, newest N ticks) plus the sampled wire-latency stage
        decomposition as a sidecar. Save the body to a file and load it
        in ui.perfetto.dev or chrome://tracing."""
        rt = self.room_manager.runtime
        if rt.trace is None:
            return web.json_response(
                {"error": "tracing disabled (trace.enabled: false)"},
                status=404,
            )
        try:
            n = int(request.query.get("ticks", "120"))
        except ValueError:
            return web.json_response(
                {"error": "ticks must be an integer"}, status=400
            )
        from livekit_server_tpu.telemetry import trace_export

        body: dict = {
            "traceEvents": trace_export.to_chrome(
                rt.trace.snapshot(n), rt.tick_ms
            ),
            "displayTimeUnit": "ms",
        }
        if rt.wire_stages is not None:
            # Perfetto ignores unknown top-level keys; curl consumers get
            # the stage decomposition without a second request.
            body["otherData"] = {"wire_stages": rt.wire_stages.summary()}
        return web.json_response(body)

    async def debug_blackbox(self, request: web.Request) -> web.Response:
        """One room's black-box flight-recorder lane ({room} is a room
        name, a row index, or `node` for the node lane), plus the
        retained automatic dumps."""
        rt = self.room_manager.runtime
        bb = rt.blackbox
        key = request.match_info["room"]
        if key == "node":
            row = bb.NODE
        else:
            room = self.room_manager.rooms.get(key)
            if room is not None:
                row = room.slots.row
            else:
                try:
                    row = int(key)
                except ValueError:
                    return web.json_response(
                        {"error": f"unknown room {key!r}"}, status=404
                    )
                if not 0 <= row < rt.dims.rooms:
                    return web.json_response(
                        {"error": f"row {row} out of range"}, status=404
                    )
        return web.json_response({
            "room": key,
            "row": row,
            "events": bb.dump(row),
            "dumps_total": bb.dumps,
            "last_dumps": list(bb.last_dumps),
        })

    async def metrics(self, request: web.Request) -> web.Response:
        # Recovery-machinery gauges sampled at scrape time: bus transport
        # churn lives on the client object, plane restarts on the
        # supervisor (livekit_plane_restarts_total / _room_failovers_total
        # counters are emitted by their owners via telemetry.add).
        bus = getattr(self.router, "bus", None)
        if bus is not None and hasattr(bus, "retries"):
            self.telemetry.set_gauge("livekit_bus_retries_total", bus.retries)
            self.telemetry.set_gauge("livekit_bus_reconnects_total", bus.reconnects)
        ledger = self.room_manager.runtime.compile_ledger.snapshot()
        self.telemetry.set_gauge(
            "livekit_xla_compiles_total", ledger["xla_compiles_total"]
        )
        self.telemetry.set_gauge(
            "livekit_xla_compiles_post_warmup",
            ledger["xla_compiles_post_warmup"],
        )
        self.telemetry.observe_queue_drops()
        return web.Response(
            text=self.telemetry.prometheus_text(), content_type="text/plain"
        )

    async def debug_overload(self, request: web.Request) -> web.Response:
        """Overload-governor state: ladder level, recent transitions,
        split ingest drop counters, admission rejections, bus/signal
        back-pressure drops, and the active limits."""
        from dataclasses import asdict

        from livekit_server_tpu.routing.kv import Subscription
        from livekit_server_tpu.routing.messagechannel import MessageChannel

        rm = self.room_manager
        gov = rm.governor
        ing = rm.runtime.ingest
        return web.json_response(
            {
                "governor": gov.snapshot() if gov is not None else None,
                "ingest": {
                    "dropped_capacity": ing.dropped_capacity,
                    "dropped_fault": ing.dropped_fault,
                    "dropped_policed": ing.dropped_policed,
                },
                "admission_rejected": dict(rm.admission_rejected),
                "admission_denied_reasons": dict(rm.admission_denied_reasons),
                "queue_drops": {
                    "signal_channel": MessageChannel.total_dropped,
                    "bus_subscription": Subscription.total_dropped,
                },
                "supervisor_restarts": (
                    rm.supervisor.restarts if rm.supervisor is not None else 0
                ),
                "limits": asdict(self.config.limits),
            }
        )

    async def debug_fleet(self, request: web.Request) -> web.Response:
        """Fleet-plane state: fence flag + lease age, owned room epochs,
        and the fencing / failover-election / rebalance counters."""
        fleet = self.room_manager.fleet
        return web.json_response(
            {
                "enabled": fleet is not None,
                "fleet": fleet.snapshot() if fleet is not None else None,
            }
        )

    async def debug_migration(self, request: web.Request) -> web.Response:
        """Migration-plane state: drain flag, in-flight handoffs with
        their epochs, pending adoptions, and the lifetime counters
        (commits, rollbacks, NACKs, bridged packets, stale-epoch drops)."""
        mig = self.room_manager.migration
        return web.json_response(
            {
                "enabled": mig is not None,
                "migration": mig.snapshot() if mig is not None else None,
                "frozen_rows": sorted(self.room_manager.runtime.ingest.frozen_rows),
            }
        )

    async def debug_egress(self, request: web.Request) -> web.Response:
        """Sharded egress plane: host_egress_pps, shard plan, canonical
        grouping rates, per-shard sent/busy totals, and the last tick's
        per-shard send + munge breakdowns."""
        rm = self.room_manager
        snap = rm.runtime.egress_plane.observe()
        if rm.udp is not None:
            snap["tx_total"] = rm.udp.stats.get("tx", 0)
            snap["tx_drop_total"] = rm.udp.stats.get("tx_drop", 0)
        return web.json_response(snap)

    async def debug_pager(self, request: web.Request) -> web.Response:
        """Paged room-state plane: page-pool occupancy/fragmentation,
        allocator churn counters, per-room page extents, and per-resource
        slot occupancy. `paged: false` (with the dense slot occupancy)
        when the plane runs the dense layout."""
        rm = self.room_manager
        rt = rm.runtime
        pager_stats = getattr(rt, "pager_stats", None)
        body: dict = {
            "paged": pager_stats is not None,
            "occupancy": rt.occupancy(),
        }
        if pager_stats is not None:
            body["pool"] = pager_stats()
            pager = rt.pager
            body["rooms"] = {
                room.name: {
                    "row": room.slots.row,
                    "pages": [int(p) for p in pager.pages_of_room(room.slots.row)],
                    "extent": tuple(pager.extent(room.slots.row)),
                }
                for room in rm.rooms.values()
            }
        return web.json_response(body)

    async def debug_integrity(self, request: web.Request) -> web.Response:
        """State-integrity plane: audits run, violations by rule, the
        quarantine/repair ladder's outcomes, checkpoint checksum failures
        + generation fallbacks, and supervisor restart causes."""
        from livekit_server_tpu.utils.checksum import CodecStats

        rm = self.room_manager
        sup = rm.supervisor
        return web.json_response(
            {
                "integrity": rm.integrity_stats() if rm.integrity is not None else None,
                "checksum": {
                    "frames_encoded": CodecStats.frames_encoded,
                    "frames_verified": CodecStats.frames_verified,
                    "verify_failures": CodecStats.verify_failures,
                },
                "restart_causes": (
                    dict(sup.restart_causes) if sup is not None else {}
                ),
                "supervisor_ckpt_fallbacks": (
                    sup.ckpt_fallbacks if sup is not None else 0
                ),
                "room_ckpt_fallbacks": rm.ckpt_fallbacks,
                "config": {
                    "enabled": self.config.integrity.enabled,
                    "audit_every_ticks": self.config.integrity.audit_every_ticks,
                    "max_row_repairs": self.config.integrity.max_row_repairs,
                    "storm_threshold": self.config.integrity.storm_threshold,
                    "checkpoint_generations": (
                        self.config.integrity.checkpoint_generations
                    ),
                },
            }
        )

    async def debug_compiles(self, request: web.Request) -> web.Response:
        """Recompile watchdog: XLA compile counts against the warmup
        watermark, total compile time, and the most recent compile
        events. `xla_compiles_post_warmup` > 0 means the steady-state
        tick path is retracing — a shape escaped the pow2 buckets or a
        static arg lost cache identity (GC11's runtime half)."""
        body = self.room_manager.runtime.compile_ledger.snapshot()
        # Start-up by phase: the wall of each and the ledger's compile
        # seconds inside it; warm_exec_s is what of start-up is not
        # compilation (tracing, cache reads, first executions, sockets).
        body["startup"] = dict(self.startup)
        if self.startup:
            body["startup"]["warm_exec_s"] = round(sum(
                ph["wall_s"] - ph["compile_s"] for ph in self.startup.values()
            ), 3)
        return web.json_response(body)

    async def debug_analytics(self, request: web.Request) -> web.Response:
        """Recent per-track analytics records (statsworker.go stream seat)."""
        try:
            n = max(0, int(request.query.get("n", 100)))
        except ValueError:
            return web.Response(status=400, text="n must be an integer")
        return web.json_response(
            {"track_stats": self.telemetry.track_stats[-n:] if n else []}
        )

    async def debug_rooms(self, request: web.Request) -> web.Response:
        rm = self.room_manager
        return web.json_response(
            {
                "node": self.router.local_node.node_id,
                "version": __version__,
                "rooms": {
                    name: {
                        "row": r.slots.row,
                        "participants": list(r.participants),
                        "tracks": list(r.tracks),
                        "traffic": rm.participant_traffic(r),
                    }
                    for name, r in rm.rooms.items()
                },
                "plane": rm.runtime.stats,
                "ingest_dropped": rm.runtime.ingest.dropped,
                # The receive path's reads (udp.RxSchedule), cumulative:
                # rx_reads and what held them, rx_stamp_fallback.
                "udp": {
                    k: v for k, v in (rm.udp.stats if rm.udp else {}).items()
                    if k == "rx" or k.startswith("rx_")
                },
                # Cumulative, for readers by difference over a window:
                # the host spans' totals and the sampled wire-latency
                # stages' sums (both empty with trace.enabled false).
                "spans": rm.runtime.spans.snapshot(),
                "wire_stages": (
                    rm.runtime.wire_stages.cumulative()
                    if rm.runtime.wire_stages is not None else {}
                ),
            }
        )

    # -- lifecycle --------------------------------------------------------
    def startup_phase(self, phase: str, mark: tuple[float, float]) -> None:
        """Close one phase of start-up, begun at `mark`
        (`startup_mark()`): its wall and the compile ledger's seconds
        inside it, into `self.startup` (/debug/compiles). A stamp pair:
        the phases cross awaits."""
        t0, compile_ms0 = mark
        self.startup[phase] = {
            "wall_s": round(time.perf_counter() - t0, 3),
            "compile_s": round((LEDGER.total_ms - compile_ms0) / 1e3, 3),
        }

    async def start(self) -> None:
        # Identify this node's bus connection to the BusServer before any
        # other op: the partition-injection harness severs/heals by node
        # id, and pub/sub sender attribution needs it.
        bus = getattr(self.router, "bus", None)
        if bus is not None and hasattr(bus, "set_ident"):
            bus.set_ident(self.router.local_node.node_id)
        await self.router.register_node()
        if hasattr(self.router, "remove_dead_nodes"):
            await self.router.remove_dead_nodes()
        # Warm-compile the media-plane step before accepting traffic so the
        # first tick doesn't stall the event loop mid-session (XLA compiles
        # once per (shapes, params); later ticks hit the cache).
        mark = startup_mark()
        await self.room_manager.runtime.step_once()
        self.startup_phase("warm_step", mark)
        # ...and the programs a join, a migration or a repair would
        # otherwise compile mid-session.
        mark = startup_mark()
        async with self.room_manager.runtime.state_lock:
            self.room_manager.runtime.warm_compile()
        self.startup_phase("warm_compile", mark)
        # Watermark for the recompile watchdog: anything XLA compiles
        # after this point is a steady-state retrace (surfaced at
        # /debug/compiles and livekit_xla_compiles_total).
        self.room_manager.runtime.mark_warm()
        # Native UDP media transport on the RTC port (rtc/config.go UDPMux).
        mark = startup_mark()
        if self.config.rtc.udp_port:
            from livekit_server_tpu.runtime.udp import start_udp_transport

            try:
                self.room_manager.udp = await start_udp_transport(
                    self.room_manager.runtime.ingest,
                    self.config.bind_addresses[0],
                    self.config.rtc.udp_port,
                    crypto=self.room_manager.crypto,
                    require_encryption=self.config.rtc.require_encryption,
                    nack_resolver=self.room_manager.runtime.resolve_nacks,
                )
                # Client PLIs over RTCP reach signal-plane publishers too.
                self.room_manager.udp.on_pli = self.room_manager.handle_pli
                # Sharded egress plane: the runtime owns the orchestrator
                # (shard plans, canonical grouping, per-shard stats); the
                # transport routes tick egress through it from here on.
                self.room_manager.udp.attach_egress_plane(
                    self.room_manager.runtime.egress_plane
                )
                # Sampled wire-latency attribution: the transport observes
                # per-stage stamps on each send (runtime/trace.py).
                self.room_manager.udp.wire_stages = (
                    self.room_manager.runtime.wire_stages
                )
                self.room_manager.udp.spans = self.room_manager.runtime.spans
                # The serving loop tells the receive path when a tick's
                # chain begins and ends (udp.RxSchedule reads round them).
                if self.room_manager.udp.rx_schedule is not None:
                    self.room_manager.runtime.attach_rx(
                        self.room_manager.udp.rx_schedule
                    )
                # Express lane (plane.express_max_subs > 0): interactive
                # rooms forward on packet arrival through this transport
                # instead of the batched tick (runtime/express.py).
                if self.room_manager.runtime.express is not None:
                    self.room_manager.udp.attach_express(
                        self.room_manager.runtime.express
                    )
                self.room_manager.udp.send_side_bwe = (
                    self.config.rtc.congestion_control.send_side_bwe
                )
                if self.config.rtc.pacer == "no-queue":
                    self.room_manager.udp.pacer_spread_ms = (
                        self.config.plane.tick_ms / 2.0
                    )
                elif self.config.rtc.pacer == "leaky-bucket":
                    # Per-subscriber byte budgets from the device pacer op
                    # gate egress; over-budget packets defer FIFO.
                    self.room_manager.udp.pacer_mode = "leaky-bucket"
                if self.config.room.playout_delay_max_ms > 0:
                    # Video egress carries the playout-delay extension
                    # (rtpextension/playoutdelay.go; config room section).
                    self.room_manager.udp.playout_delay = (
                        self.config.room.playout_delay_min_ms,
                        self.config.room.playout_delay_max_ms,
                    )
                for room in self.room_manager.rooms.values():
                    room.udp = self.room_manager.udp
                # TCP media fallback (transportmanager.go:73 ladder): same
                # sealed frames, length-prefixed; always encrypted — so it
                # cannot exist on a node running without an AEAD backend.
                if self.config.rtc.tcp_port and self.room_manager.crypto is not None:
                    from livekit_server_tpu.runtime.tcp import start_tcp_transport

                    try:
                        self.tcp_media = await start_tcp_transport(
                            self.room_manager.udp,
                            self.room_manager.crypto,
                            self.config.bind_addresses[0],
                            self.config.rtc.tcp_port,
                        )
                    except OSError:
                        pass  # port busy: UDP path still works
                # Embedded media relay (turn.go:47 seat): a second UDP hop
                # for clients that cannot reach rtc.udp_port directly.
                if self.config.relay.enabled:
                    from livekit_server_tpu.runtime.relay import start_media_relay

                    rcfg = self.config.relay
                    # Relay tokens are minted and verified only by this
                    # process, so the HMAC secret never needs to be derived
                    # from (or leak) API-key material — and a config-derived
                    # secret would be the constant "dev" in keyless dev mode,
                    # making tokens forgeable. A fresh random secret per
                    # process is strictly stronger and costs nothing.
                    secret = secrets.token_bytes(32)
                    # A wildcard bind is not a connectable upstream
                    # destination (0.0.0.0→loopback only works on Linux);
                    # the relay's per-allocation sockets dial loopback.
                    up_host = self.config.bind_addresses[0]
                    if up_host in ("", "0.0.0.0", "::"):
                        up_host = "127.0.0.1"
                    try:
                        self.media_relay = await start_media_relay(
                            self.config.bind_addresses[0],
                            rcfg.udp_port,
                            (up_host, self.config.rtc.udp_port),
                            secret,
                            ttl_s=float(rcfg.allocation_ttl_s),
                            max_allocations=rcfg.max_allocations,
                        )
                        # Signal-layer mint point (request_relay handler).
                        # Never advertise a wildcard bind as the relay host —
                        # clients can't route to 0.0.0.0; without a concrete
                        # external_host the relay runs but is not advertised.
                        advert = rcfg.external_host or self.config.bind_addresses[0]
                        if advert in ("", "0.0.0.0", "::"):
                            self.log.warn(
                                "relay enabled but bind address is a wildcard "
                                "and relay.external_host is unset; not "
                                "advertising relay to clients"
                            )
                        else:
                            self.room_manager.udp.relay_info = (
                                advert,
                                rcfg.udp_port,
                                secret,
                                float(rcfg.allocation_ttl_s),
                            )
                    except OSError:
                        pass  # relay port busy: direct path still works
            except OSError:
                pass  # port busy: WS media path still works
        self.startup_phase("udp_start", mark)
        await self.ioinfo.start()
        await self.room_api.start()
        self.room_manager.start()
        self._stats_task = asyncio.ensure_future(self._refresh_nodes())
        self._runner = web.AppRunner(self.app)
        await self._runner.setup()
        for addr in self.config.bind_addresses:
            site = web.TCPSite(self._runner, addr, self.config.port)
            await site.start()
            self._sites.append(site)
        self.started_at = time.time()

    async def stop(self, force: bool = False) -> None:
        self.router.local_node.state = NodeState.SHUTTING_DOWN
        await self.router.drain()
        mig = self.room_manager.migration
        if not force and mig is not None:
            # Graceful stop IS a node drain: every local room migrates to
            # a peer through the two-phase handoff (bounded concurrency,
            # admissions refused throughout); rooms with no willing peer
            # stay and are torn down by room_manager.stop() below.
            try:
                await mig.drain_node()
            except Exception as e:  # noqa: BLE001 — stopping anyway
                self.log.warn("graceful drain failed", error=str(e))
        elif not force:
            # Bus-less single node: nobody to migrate to. Wait briefly for
            # participants to leave on their own (server.go:295).
            for _ in range(50):
                if not any(r.participants for r in self.room_manager.rooms.values()):
                    break
                await asyncio.sleep(0.1)
        if self._stats_task:
            self._stats_task.cancel()
        if self.room_manager.udp is not None and self.room_manager.udp.transport:
            self.room_manager.udp.transport.close()
        if getattr(self, "tcp_media", None) is not None:
            self.tcp_media.close()
        if getattr(self, "media_relay", None) is not None:
            self.media_relay.close()
        await self.ioinfo.stop()
        await self.room_api.stop()
        await self.room_manager.stop()
        await self.router.unregister_node()
        if self._runner is not None:
            await self._runner.cleanup()

    @property
    def port(self) -> int:
        return self.config.port


async def connect_bus(config: Config):
    """Resolve the configured multi-node bus (redisrouter's Redis client
    seat): kv.kind == "tcp" dials the in-repo BusServer at kv.address."""
    if config.kv.kind == "tcp":
        if not config.kv.address:
            # Booting a cluster-configured node standalone would silently
            # split-brain it out of the cluster; fail loudly instead.
            raise ConfigError("kv.kind is 'tcp' but kv.address is empty")
        from livekit_server_tpu.routing.tcpbus import TCPBusClient

        return await TCPBusClient.connect_address(
            config.kv.address, token=config.kv.auth_token
        )
    if config.kv.kind in ("", "memory"):
        return None
    # An unknown kind must not fall through to a private in-process bus —
    # the node would boot "clustered" against a registry only it can see.
    raise ConfigError(
        f"unsupported kv.kind {config.kv.kind!r}: no external KV client is "
        "bundled; run `livekit-server-tpu bus` and use kv.kind='tcp'"
    )


def startup_mark() -> tuple[float, float]:
    """(now, the compile ledger's milliseconds so far): where a phase
    of start-up begins (`LivekitServer.startup_phase` closes it)."""
    return time.perf_counter(), LEDGER.total_ms


def create_server(config: Config, bus=None, mesh=None) -> LivekitServer:
    """The Wire graph (wire_gen.go InitializeServer) as explicit wiring."""
    # The ledger counts from here, not from the runtime's construction:
    # the plane's initial state compiles too.
    LEDGER.install()
    mark = startup_mark()
    node = LocalNode(region=config.region)
    sample_system_stats(node.stats)
    if bus is None and config.kv.kind == "memory":
        router = create_router(node, None)
        store = LocalStore()
    else:
        bus = bus if bus is not None else MemoryBus()
        router = create_router(
            node, bus,
            lease_ttl=config.kv.lease_ttl_s,
            stats_interval=config.kv.stats_interval_s,
        )
        store = KVStore(bus)
    telemetry = TelemetryService(config)
    rm = RoomManager(config, router, store, mesh=mesh, telemetry=telemetry)
    server = LivekitServer(config, router, store, rm, telemetry)
    server._selector = create_selector(config.node_selector, config.region)
    if rm.migration is not None:
        # Drain-target ranking reuses the placement selector, so a drain
        # spreads rooms the same way the router places new ones.
        rm.migration.selector = server._selector
    server.startup_phase("create_server", mark)
    return server
