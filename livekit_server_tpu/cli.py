"""CLI entry point.

Reference parity: cmd/server/main.go (startServer :250-304, getConfig
:191) and commands.go (generate-keys, create-join-token, list-nodes,
ports). Flags are generated from the config schema exactly like the
reference's GenerateCLIFlags (main.go:126).

Usage:
    python -m livekit_server_tpu serve --config livekit.yaml
    python -m livekit_server_tpu generate-keys
    python -m livekit_server_tpu create-join-token --room r --identity i
    python -m livekit_server_tpu list-nodes
    python -m livekit_server_tpu ports
"""

from __future__ import annotations

import argparse
import asyncio
import json
import os
import signal
import sys

from livekit_server_tpu.auth import AccessToken, VideoGrant
from livekit_server_tpu.config import Config, generate_cli_flags, load_config
from livekit_server_tpu.utils import ids
from livekit_server_tpu.version import __version__


def _build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(prog="livekit-server-tpu")
    p.add_argument("--version", action="version", version=__version__)
    sub = p.add_subparsers(dest="command")

    serve = sub.add_parser("serve", help="run the server")
    serve.add_argument("--config", help="path to YAML config")
    serve.add_argument("--dev", action="store_true", help="development mode")
    generate_cli_flags(serve)

    sub.add_parser("generate-keys", help="generate an API key/secret pair")

    tok = sub.add_parser("create-join-token", help="mint a join token")
    tok.add_argument("--room", required=True)
    tok.add_argument("--identity", required=True)
    tok.add_argument("--config", help="path to YAML config (for keys)")
    tok.add_argument("--key", help="API key (defaults to first config key)")

    sub.add_parser("ports", help="print the ports the server uses")

    bus = sub.add_parser(
        "bus", help="run the standalone message bus (the multi-node KV seat)"
    )
    bus.add_argument("--host", default="127.0.0.1",
                     help="bind address; a non-loopback bind requires --token")
    bus.add_argument("--port", type=int, default=7850)
    bus.add_argument("--token", default=os.environ.get("LIVEKIT_BUS_TOKEN", ""),
                     help="shared auth secret (env LIVEKIT_BUS_TOKEN); the bus "
                          "is the cluster control plane — never expose it bare")

    nodes = sub.add_parser("list-nodes", help="list cluster nodes")
    nodes.add_argument("--config", help="path to YAML config")

    drain = sub.add_parser(
        "drain",
        help="ask a node to migrate its rooms off and stop admitting "
             "(the live-migration plane's node drain)",
    )
    drain.add_argument("--config", help="path to YAML config (for the bus)")
    drain.add_argument("--node", required=True,
                       help="node id to drain (see list-nodes)")
    return p


def main(argv: list[str] | None = None) -> int:
    args = _build_parser().parse_args(argv)
    if args.command == "generate-keys":
        print(f"API Key: {ids.new_api_key()}")
        print(f"API Secret: {ids.new_api_secret()}")
        return 0
    if args.command == "bus":

        if args.host not in ("127.0.0.1", "localhost", "::1") and not args.token:
            print("refusing to bind the bus beyond loopback without --token",
                  flush=True)
            return 2

        async def run_bus():
            from livekit_server_tpu.routing.tcpbus import BusServer

            srv = BusServer(token=args.token)
            await srv.start(args.host, args.port)
            print(f"bus listening on {args.host}:{srv.port}", flush=True)
            stop = asyncio.Event()
            loop = asyncio.get_running_loop()
            for sig in (signal.SIGINT, signal.SIGTERM):
                loop.add_signal_handler(sig, stop.set)
            await stop.wait()
            srv.close()

        asyncio.run(run_bus())
        return 0
    if args.command == "ports":
        cfg = Config()
        print(f"http/ws: {cfg.port}")
        print(f"rtc udp: {cfg.rtc.udp_port}")
        print(f"rtc tcp: {cfg.rtc.tcp_port}")
        print(f"port range: {cfg.rtc.port_range_start}-{cfg.rtc.port_range_end}")
        return 0
    if args.command == "create-join-token":
        cfg = load_config(
            yaml_path=args.config if args.config else None,
            yaml_text=None if args.config else "development: true",
        )
        key = args.key or next(iter(cfg.keys))
        tok = AccessToken(key, cfg.keys[key])
        tok.identity = args.identity
        tok.grant = VideoGrant(room_join=True, room=args.room)
        print(tok.to_jwt())
        return 0
    if args.command == "list-nodes":
        cfg = load_config(
            yaml_path=args.config if args.config else None,
            yaml_text=None if args.config else "development: true",
        )
        from livekit_server_tpu.service.server import connect_bus, create_server

        async def run():
            # Without the shared bus the router falls back to a private
            # in-memory registry and only ever lists this invocation.
            server = create_server(cfg, bus=await connect_bus(cfg))
            await server.router.register_node()
            for n in await server.router.list_nodes():
                print(json.dumps(n.to_dict()))
            await server.router.unregister_node()

        asyncio.run(run())
        return 0
    if args.command == "drain":
        cfg = load_config(
            yaml_path=args.config if args.config else None,
            yaml_text=None if args.config else "development: true",
        )
        from livekit_server_tpu.service.server import connect_bus

        async def run_drain():
            bus = await connect_bus(cfg)
            if bus is None:
                print("drain needs a shared bus (kv.kind='tcp'); a "
                      "single-node server just stops", flush=True)
                return 2
            n = await bus.publish(f"node_migrate:{args.node}", {"kind": "drain"})
            if n == 0:
                print(f"node {args.node} is not listening (already gone?)",
                      flush=True)
                return 1
            print(f"drain requested on {args.node}", flush=True)
            return 0

        return asyncio.run(run_drain())
    if args.command == "serve":
        yaml_text = None if args.config else (
            "development: true" if args.dev else None
        )
        cfg = load_config(yaml_path=args.config, yaml_text=yaml_text, cli_args=args)
        return asyncio.run(_serve(cfg))
    _build_parser().print_help()
    return 1


async def _serve(cfg: Config) -> int:
    from livekit_server_tpu.service.server import connect_bus, create_server
    from livekit_server_tpu.utils.compile_cache import setup_compile_cache

    setup_compile_cache()
    server = create_server(cfg, bus=await connect_bus(cfg))
    await server.start()
    print(
        f"livekit-server-tpu v{__version__} listening on "
        f"{cfg.bind_addresses}:{cfg.port} "
        f"(plane: {cfg.plane.rooms}r×{cfg.plane.tracks_per_room}t×"
        f"{cfg.plane.subs_per_room}s @ {cfg.plane.tick_ms}ms)",
        flush=True,
    )
    stop = asyncio.Event()
    loop = asyncio.get_running_loop()
    for sig in (signal.SIGINT, signal.SIGTERM):
        loop.add_signal_handler(sig, stop.set)
    await stop.wait()
    print("shutting down...", flush=True)
    await server.stop()
    return 0


if __name__ == "__main__":
    sys.exit(main())
