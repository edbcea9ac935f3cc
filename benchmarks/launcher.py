"""The server process of a run: the only one that imports JAX and holds the chip.

    python -m benchmarks.launcher <spec.json>

It does what `cli._serve` does — `setup_compile_cache()`, `create_server(cfg)`,
`server.start()` (warm-compile, then `mark_warm()`) — on the loopback ports
the spec names, AEAD required, then writes the device, the warm-up record
and the native libraries' state to the spec's `info` file and serves until
told otherwise on stdin:

    trace <seconds>   take a `jax.profiler` trace of that many seconds, now
    finish            read the device's memory, reduce the trace, stop, exit

It refuses to start where the first device is not a TPU (unless the spec
says `rehearse`), and where a compiler exists and a Python twin would carry
parse, munge or egress. In a traced run the calls into each layer are
wrapped in `TraceAnnotation`s from here, so that an idle gap of the device
can be named by what the host was doing; the program itself is not touched.
"""

from __future__ import annotations

import asyncio
import json
import shutil
import sys
import threading
import time
from pathlib import Path

ANNOTATED = ("_stage_host", "_device_step", "_fan_out")


def say(msg: str) -> None:
    print(f"[launcher] {msg}", file=sys.stderr, flush=True)


def native_state() -> dict:
    """Which implementation carries parse / munge / egress (`chip_smoke.
    native_report`'s rule: with a compiler present, never the Python twin)."""
    from livekit_server_tpu import native

    have = {"parse": bool(getattr(native.rtp, "native", False)),
            "munge": native.munge is not None, "egress": native.egress is not None}
    gxx = shutil.which("g++")
    if gxx and not all(have.values()):
        raise RuntimeError(f"a compiler exists ({gxx}) but a Python twin would "
                           f"carry the run: {have}")
    return have | {"compiler": gxx}


def annotate(runtime, supervisor) -> None:
    """Host spans round the calls into each layer, written into the
    profiler's own trace. Names are the layer's method; a rename in the
    program silences the span (and the gap it would have named), no more."""
    import jax

    def wrap(obj, name):
        fn = getattr(obj, name, None)
        if fn is None:
            say(f"no {type(obj).__name__}.{name} to annotate")
            return
        label = f"bench/{name.lstrip('_')}"
        if asyncio.iscoroutinefunction(fn):
            async def inner(*a, **k):
                with jax.profiler.TraceAnnotation(label):
                    return await fn(*a, **k)
        else:
            def inner(*a, **k):
                with jax.profiler.TraceAnnotation(label):
                    return fn(*a, **k)
        setattr(obj, name, inner)

    for name in ANNOTATED:
        wrap(runtime, name)
    if supervisor is not None:
        wrap(supervisor, "checkpoint_now")


def plant_fault(name: str, server) -> None:
    """Break the timed path underneath, for the benchmark's own tests: never
    reached from `run.py`'s command line."""
    runtime = server.room_manager.runtime
    if name == "alter":
        # flip a payload byte where the packet enters the tick's staging
        push_batch, calls = runtime.ingest.push_batch, [0]

        def altered(room, track, layer, sn, ts, ts_aligned, temporal, keyframe,
                    layer_sync, begin_pic, marker, pid, tl0, keyidx, size,
                    frame_ms, audio_level, arrival_rtp, pay_start, pay_length,
                    blob, *a, **k):
            calls[0] += 1
            if calls[0] % 20 == 0 and len(room) and int(pay_start[0]) >= 0:
                blob = bytearray(bytes(blob))
                blob[int(pay_start[0]) + int(pay_length[0]) - 1] ^= 0x55
                blob = bytes(blob)
            return push_batch(room, track, layer, sn, ts, ts_aligned, temporal,
                              keyframe, layer_sync, begin_pic, marker, pid, tl0,
                              keyidx, size, frame_ms, audio_level, arrival_rtp,
                              pay_start, pay_length, blob, *a, **k)
        runtime.ingest.push_batch = altered
    elif name == "stale_state":
        # a step that returns its state unchanged (CPU only: no donation)
        step = runtime._step

        def stale(state, *packed):
            _, buf = step(state, *packed)
            return state, buf
        runtime._step = stale
    else:
        raise ValueError(f"unknown fault {name!r}")


async def serve(spec: dict) -> int:
    import jax

    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    if not spec.get("rehearse") and device["platform"] != "tpu":
        say(f"no TPU (JAX reports {device}); nothing was run")
        return 3

    from livekit_server_tpu.config import load_config
    from livekit_server_tpu.service.server import create_server
    from livekit_server_tpu.utils.compile_cache import setup_compile_cache

    native = native_state()
    cache = setup_compile_cache()
    cfg = load_config(yaml_text=json.dumps(spec["server_config"]))
    t0 = time.monotonic()
    server = create_server(cfg)
    await server.start()            # warm-compiles the tick, then mark_warm()
    warm_s = time.monotonic() - t0
    runtime = server.room_manager.runtime
    ledger = runtime.compile_ledger.snapshot()
    for fault in spec.get("faults", ()):
        plant_fault(fault, server)
    if spec.get("trace"):
        annotate(runtime, server.room_manager.supervisor)
    info = {"device": device, "warmup_s": warm_s, "compile_cache": cache,
            "xla_compiles_total": ledger["xla_compiles_total"],
            "compile_s": runtime.compile_ledger.warmup_ms / 1e3,
            "native": native, "jax": jax.__version__,
            "dims": list(runtime.dims), "tick_ms": runtime.tick_ms,
            "runtime": type(runtime).__name__}
    Path(spec["info"]).write_text(json.dumps(info))
    say(f"serving on :{cfg.port} / udp :{cfg.rtc.udp_port}; warm-up {warm_s:.2f} s, "
        f"{ledger['xla_compiles_total']} XLA compiles, cache {cache}")

    loop = asyncio.get_running_loop()
    commands: asyncio.Queue[str] = asyncio.Queue()

    def read_stdin() -> None:
        for line in sys.stdin:
            loop.call_soon_threadsafe(commands.put_nowait, line.strip())
        loop.call_soon_threadsafe(commands.put_nowait, "finish")   # parent gone

    threading.Thread(target=read_stdin, daemon=True).start()
    trace_dir = Path(spec["trace_dir"]) if spec.get("trace") else None
    tracing: asyncio.Task | None = None
    traced = {}

    async def take_trace(seconds: float) -> None:
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0          # no Python frames: they slow the loop
        options.host_tracer_level = 2
        t_start = time.time_ns()
        jax.profiler.start_trace(str(trace_dir), profiler_options=options)
        await asyncio.sleep(seconds)
        # serialising the trace is seconds of work: off the event loop
        await asyncio.to_thread(jax.profiler.stop_trace)
        traced.update(requested_s=seconds, t_start_ns=t_start,
                      stop_s=(time.time_ns() - t_start) / 1e9 - seconds)

    while True:
        words = (await commands.get()).split()
        if not words:
            continue
        if words[0] == "trace" and trace_dir is not None and tracing is None:
            tracing = asyncio.ensure_future(take_trace(float(words[1])))
        elif words[0] == "finish":
            break
    final = dict(info)
    if tracing is not None:
        await tracing
        final["trace"] = traced
    final["memory_peak_bytes"] = max(
        (d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in devs)
    final["compiles_after"] = runtime.compile_ledger.snapshot()
    t0 = time.monotonic()
    await asyncio.wait_for(server.stop(), 60)
    final["stop_s"] = time.monotonic() - t0
    if tracing is not None:
        from benchmarks import xplane

        found = sorted(trace_dir.rglob("*.xplane.pb"))
        if found:
            final["trace"]["file"] = str(found[-1])
            final["trace"]["reduced"] = xplane.reduce(found[-1])
    Path(spec["info"]).write_text(json.dumps(final))
    return 0


def main(argv: list[str]) -> int:
    with open(argv[1]) as f:
        spec = json.load(f)
    return asyncio.run(serve(spec))


if __name__ == "__main__":
    sys.exit(main(sys.argv))
