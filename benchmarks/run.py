#!/usr/bin/env python3
"""One run of one cell of the benchmark.

    python3 benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A process tree of its own: this parent, which never imports JAX; one server
process (`benchmarks/launcher.py`), which alone holds the chip; and client
processes (`benchmarks/client/worker.py`), sharded by room. Set-up is the
server's start and warm-up, every participant's join over `/rtc`, and a
lead-in of media; the window is `--seconds` of open-loop media; then the
drain, the counters by difference and the last line. `--trace 1` is a run
of its own that polls `/debug/ticks` through the window and has the launcher
take a profiler trace of the window's last seconds; it reports the per-layer
metrics and the breakdown. `--rehearse` runs the same path at toy size on
whatever JAX finds (the CPU), and prints no number under a metric's name.

Everything that belongs to one configuration, one cell or one per-layer
metric is a file found by its name in BENCHMARK.json (README.md).
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import socket
import subprocess
import sys
import tempfile
import threading
import time
import urllib.request
from pathlib import Path

import numpy as np

T_START = time.time()
ROOT = Path(__file__).resolve().parent.parent
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from benchmarks import layer_metrics, reference, stats, traffic  # noqa: E402

API_KEY, API_SECRET = "benchkey", "benchsecret-benchsecret-benchsecret"
START_TIMEOUT_S = 1100        # a first run compiles
# the comparisons that decide `correct`, each exact: the limit is 0
LIMITS = dict.fromkeys(reference.NUMBERS + ("compiles_after_warmup",), 0)


def say(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def free_port(kind=socket.SOCK_STREAM) -> int:
    with socket.socket(socket.AF_INET, kind) as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def udp_counters() -> dict[str, int]:
    """This host's UDP counters: what the kernel dropped for want of socket
    buffer is loss the host made, not the server."""
    try:
        with open("/proc/net/snmp") as f:
            names, values = [ln.split()[1:] for ln in f if ln.startswith("Udp:")]
    except (OSError, ValueError):
        return {}
    return dict(zip(names, map(int, values)))


def load_cell(name: str) -> tuple[dict, dict, dict, dict]:
    """(BENCHMARK.json, its entry for the cell, the configuration's file,
    the cell's workload file)."""
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    cell = next((w for w in bench["workloads"] if w["name"] == name), None)
    if cell is None:
        raise SystemExit(f"no workload {name!r} in BENCHMARK.json; it has "
                         f"{[w['name'] for w in bench['workloads']]}")
    entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    config = json.loads((ROOT / entry["file"]).read_text())
    workload = json.loads(
        (ROOT / bench["paths"][0] / "workloads" / f"{name}.json").read_text())
    return bench, cell, config, workload


class Server:
    """The launcher process and the server's debug endpoints."""

    def __init__(self, rundir: Path, server_config: dict, *, rehearse: bool,
                 trace: bool, faults: tuple[str, ...] = ()):
        self.port, self.udp_port = free_port(), free_port(socket.SOCK_DGRAM)
        self.info_file = rundir / "launcher.info.json"
        cfg = json.loads(json.dumps(server_config))
        cfg.setdefault("rtc", {})["udp_port"] = self.udp_port
        cfg.update(keys={API_KEY: API_SECRET}, port=self.port,
                   bind_addresses=["127.0.0.1"])
        spec = {"server_config": cfg, "info": str(self.info_file), "rehearse": rehearse,
                "trace": trace, "trace_dir": str(rundir / "trace"), "faults": list(faults)}
        (rundir / "launcher.spec.json").write_text(json.dumps(spec))
        self.log = open(rundir / "launcher.log", "wb")
        self.proc = subprocess.Popen(
            [sys.executable, "-m", "benchmarks.launcher", str(rundir / "launcher.spec.json")],
            cwd=ROOT, stdin=subprocess.PIPE, stdout=self.log, stderr=self.log,
            env=os.environ | {"PYTHONPATH": str(ROOT)})

    def wait_started(self) -> dict:
        deadline = time.monotonic() + START_TIMEOUT_S
        while not self.info_file.exists():
            if self.proc.poll() is not None:
                raise RuntimeError(f"the server process exited with {self.proc.returncode} "
                                   "before it served")
            if time.monotonic() > deadline:
                raise TimeoutError("the server did not start")
            time.sleep(0.05)
        return json.loads(self.info_file.read_text())

    def get(self, path: str) -> dict:
        with urllib.request.urlopen(f"http://127.0.0.1:{self.port}{path}", timeout=10) as r:
            return json.load(r)

    def snapshot(self) -> dict:
        return {"rooms": self.get("/debug/rooms"), "overload": self.get("/debug/overload"),
                "compiles": self.get("/debug/compiles")}

    def tell(self, line: str) -> None:
        self.proc.stdin.write(line.encode() + b"\n")
        self.proc.stdin.flush()

    def finish(self) -> dict:
        """Have the launcher read the device's memory, reduce its trace and
        stop; its last record, which lacks `compiles_after` where it died."""
        try:
            self.tell("finish")
            self.proc.wait(timeout=240)
        except (OSError, subprocess.TimeoutExpired) as e:
            say(f"the server process did not finish: {e!r}")
        return json.loads(self.info_file.read_text())

    def kill(self) -> None:
        if self.proc.poll() is None:
            self.proc.kill()
        self.proc.wait()
        self.log.close()


class Worker:
    def __init__(self, rundir: Path, index: int, spec: dict):
        self.out = rundir / f"worker{index}"
        spec = spec | {"worker": index, "out": str(self.out)}
        spec_file = rundir / f"worker{index}.spec.json"
        spec_file.write_text(json.dumps(spec))
        self.log = open(rundir / f"worker{index}.log", "wb")
        self.proc = subprocess.Popen(
            [sys.executable, "-m", "benchmarks.client.worker", str(spec_file)],
            cwd=ROOT, stdin=subprocess.PIPE, stdout=subprocess.PIPE, stderr=self.log,
            env=os.environ | {"PYTHONPATH": str(ROOT)})

    def expect(self, key: str, timeout: float) -> dict:
        """The next line of the worker's stdout, which must hold `key`."""
        box: list[bytes] = []
        reader = threading.Thread(target=lambda: box.append(self.proc.stdout.readline()),
                                  daemon=True)
        reader.start()
        reader.join(timeout)
        if not box or not box[0]:
            raise RuntimeError(f"client process {self.out.name} gave no {key!r} line "
                               f"(exit code {self.proc.poll()})")
        msg = json.loads(box[0])
        if key not in msg:
            raise RuntimeError(f"client process said {msg}, not {key!r}")
        return msg

    def go(self, t0_ns: int) -> None:
        self.proc.stdin.write(f"go {t0_ns}\n".encode())
        self.proc.stdin.flush()

    def kill(self) -> None:
        if self.proc.poll() is None:
            self.proc.kill()
        self.proc.wait()
        self.log.close()


def poll_ticks(server: Server, until: float, every_s: float, into: dict) -> None:
    """Merge `/debug/ticks`' ring (120 deep) by `idx`, from now until `until`:
    the first poll's records are of before the window and only mark where it
    begins, the last poll is made at its close."""
    first = max((rec["idx"] for rec in server.get("/debug/ticks")["recent_ticks"]), default=-1)
    while True:
        closing = time.time() >= until
        try:
            for rec in server.get("/debug/ticks")["recent_ticks"]:
                if rec["idx"] > first:
                    into[rec["idx"]] = rec
        except OSError as e:
            say(f"/debug/ticks: {e}")
        if closing:
            return
        time.sleep(min(every_s, max(0.0, until - time.time())))


def tail(path: Path, n: int = 3000) -> str:
    try:
        return path.read_bytes()[-n:].decode(errors="replace")
    except OSError:
        return ""


def run_cell(name: str, seed: int, seconds: float, trace: bool, rehearse: bool, *,
             faults: tuple[str, ...] = (), server_overrides: dict | None = None,
             workload_overrides: dict | None = None) -> tuple[int, dict | None]:
    """One run. → (exit code, the result line as a dict, or None).

    `faults` (planted in the launcher), `server_overrides` (merged into the
    server's config, section by section) and `workload_overrides` (merged
    into the cell's workload file) are for the benchmark's own tests and
    sweeps (`tests/`, `sweep.py`): the command line reaches none of them."""
    bench, cell, config, workload = load_cell(name)
    server_config = json.loads(json.dumps(config["server_config"]))
    if rehearse:
        toy = workload["rehearsal"]
        server_config["plane"].update(toy["plane"])
        workload = workload | {k: v for k, v in toy.items() if k != "plane"}
    workload = workload | (workload_overrides or {})
    for section, values in (server_overrides or {}).items():
        server_config.setdefault(section, {}).update(values)
    # a client process with no room would have nothing to join
    workload["client_processes"] = min(workload["client_processes"], workload["live_rooms"])
    plan = traffic.make_plan(workload, seed)
    pin, pout = traffic.offered_pps(plan)
    say(f"{name} seed {seed}: {plan.rooms} live rooms x {plan.participants} participants, "
        f"{len(plan.tracks)} tracks; offered {pin:.0f} pkt/s in, {pout:.0f} out; "
        f"plane {server_config['plane']}; {workload['client_processes']} client processes")

    rundir = Path(tempfile.mkdtemp(prefix="bench-run-"))
    server, workers = None, []
    try:
        server = Server(rundir, server_config, rehearse=rehearse, trace=trace, faults=faults)
        try:
            info = server.wait_started()
        except RuntimeError as e:
            say(f"{e}\n{tail(rundir / 'launcher.log')}")
            return 3, None
        on_chip = info["device"]["platform"] == "tpu"
        if not rehearse and (not on_chip or info["device"]["count"] != cell["chips"]):
            say(f"the cell asks for {cell['chips']} TPU chip(s); JAX reports {info['device']}")
            return 3, None
        say(f"server warm in {info['warmup_s']:.2f} s ({info['xla_compiles_total']} XLA "
            f"compiles, {info['compile_s']:.2f} s compiling, cache {info['compile_cache']}); "
            f"native {info['native']}")

        lead_s = float(workload["lead_in_s"])
        spec = {"workload": workload, "seed": seed, "workers": workload["client_processes"],
                "port": server.port, "udp_port": server.udp_port,
                "api_key": API_KEY, "api_secret": API_SECRET, "lead_in_s": lead_s,
                "seconds": seconds, "ack_every_ms": workload["ack_every_ms"]}
        workers = [Worker(rundir, i, spec) for i in range(workload["client_processes"])]
        try:
            ready = [w.expect("ready", 300) for w in workers]
        except RuntimeError as e:
            say(f"{e}\n" + "\n".join(tail(w.out.with_suffix(".log")) for w in workers))
            return 4, None
        say(f"joined: {sum(r['participants'] for r in ready)} participants, "
            f"{sum(r['tracks'] for r in ready)} tracks published, every punch acknowledged")

        udp_before = udp_counters()
        # the window's first instant: a lead-in of media from now, so that
        # every subscriber's video has locked on and the allocator has set
        # its targets before anything is counted
        t0 = time.time() + lead_s + 0.25
        for w in workers:
            w.go(int(t0 * 1e9))
        setup_s = t0 - T_START
        ticks: dict[int, dict] = {}
        if trace:
            time.sleep(max(0.0, t0 - time.time()))
            before = server.snapshot()
            trace_s = min(float(workload["trace_s"]), seconds / 2)
            poller = threading.Thread(
                target=poll_ticks, daemon=True,
                args=(server, t0 + seconds, min(0.5, 0.03 * info["tick_ms"]), ticks))
            poller.start()
            # the window's last seconds: stopping a trace is 8-10 s of serialising on a
            # thread that shares the server's GIL, and in the middle of the window that
            # made every checkpoint a late streak and walked the governor up (call 17)
            time.sleep(max(0.0, t0 + seconds - trace_s - time.time()))
            server.tell(f"trace {trace_s}")
            time.sleep(max(0.0, t0 + seconds - time.time()))
            after = server.snapshot()
            poller.join()
        else:
            before = server.snapshot()        # before the lead-in: nothing polls the window
        for w in workers:
            w.expect("done", lead_s + seconds + 120)
        if not trace:
            after = server.snapshot()
        udp_after = udp_counters()
        final = server.finish()
        if "compiles_after" not in final:
            say(f"the server process died under the run (exit code {server.proc.poll()}); "
                f"no result\n{tail(rundir / 'launcher.log')}")
            return 5, None
        results = [json.loads(w.out.with_suffix(".json").read_text()) for w in workers]
        due_ns, arrival_ns = np.concatenate(
            [np.load(str(w.out) + ".times.npy") for w in workers], axis=1)
    finally:
        for w in workers:
            w.kill()
        if server is not None:
            server.kill()
        keep = os.environ.get("BENCH_KEEP_RUNDIR")      # a look at a run's files, by hand
        if keep:
            shutil.copytree(rundir, keep, dirs_exist_ok=True)
        shutil.rmtree(rundir, ignore_errors=True)

    t0_ns, window_ns = int(t0 * 1e9), int(seconds * traffic.NS)
    e2e = stats.end_to_end(due_ns, arrival_ns, t0_ns, window_ns)
    check = {k: sum(r[k] for r in results) for k in reference.NUMBERS}
    check["compiles_after_warmup"] = final["compiles_after"]["xla_compiles_post_warmup"]
    attempted = reference.expected_deliveries(plan, int(lead_s * traffic.NS), window_ns)
    if attempted != sum(r["expected"] for r in results):
        raise RuntimeError("the parent's reference and the clients' disagree on what is due")
    correct = all(check[k] <= LIMITS[k] for k in LIMITS) and e2e["samples"] > 0
    counted = lambda path: (layer_metrics.dig(after, path)          # noqa: E731
                            - layer_metrics.dig(before, path))
    gov = after["overload"]["governor"] or {}
    clients = {"join_s": max(r["join_t1"] for r in results) - min(r["join_t0"] for r in results),
               "gen_late_ms_max": max(r["gen_late_ms"] for r in results)}
    in_window = (due_ns >= t0_ns) & (due_ns < t0_ns + window_ns)
    lat_by_due = ((arrival_ns - due_ns) / 1e6)[in_window][np.argsort(due_ns[in_window])]
    # what a sweep reads beside the metrics, and what found the host's freezes
    seen = {"governor_level": gov.get("level"), "governor_transitions": gov.get("transitions"),
            "ingest_dropped": counted("rooms.ingest_dropped"),
            "late_ticks": counted("rooms.plane.late_ticks"),
            "ticks": counted("rooms.plane.ticks"),
            "kernel_udp_drops": {k: udp_after[k] - udp_before[k] for k in
                                 ("RcvbufErrors", "SndbufErrors", "InErrors") if k in udp_after},
            "latency_by_fifth_ms": [float(np.median(part))
                                    for part in np.array_split(lat_by_due, 5) if len(part)],
            "latency_ladder_ms": {f"p{q}": stats.percentile(np.sort(lat_by_due), q)
                                  for q in (90, 95, 98, 99, 99.5, 99.9, 100)} if len(lat_by_due) else {},
            "gen_stalls_s_late_ms_send_ms": [r["gen_stalls"] for r in results],
            "warmup_s": info["warmup_s"], **clients}
    say(f"{e2e['samples']} latency samples; {sum(r['sent'] for r in results)} packets sent, "
        f"{sum(r['received_whole'] for r in results)} received whole (lead-in included), "
        f"{sum(r['padding_probes'] for r in results)} padding probes; drained in "
        f"{max(r['drained_s'] for r in results):.2f} s; generator at most "
        f"{clients['gen_late_ms_max']:.3f} ms late in the window (longest seal + sendmmsg "
        f"{max(r['gen_send_ms'] for r in results):.3f} ms); arrivals stamped by "
        f"{results[0]['arrival_stamp']}; kernel UDP drops on this host "
        f"{seen['kernel_udp_drops'] or 'not readable'} (receive buffer {results[0]['rcvbuf']} B "
        f"a socket); governor level {gov.get('level')}, transitions {gov.get('transitions')}; "
        f"ingest_dropped {seen['ingest_dropped']}; late ticks {seen['late_ticks']} of "
        f"{seen['ticks']}")

    device = info["device"] | {"memory_peak_bytes": final["memory_peak_bytes"]}
    line: dict = {"correct": bool(correct), "attempted": attempted, "failed": check["missing"]}
    wanted = [m for m in bench["per_layer" if trace else "end_to_end"]
              if "workloads" not in m or name in m["workloads"]]
    if trace:
        reduced = final.get("trace", {}).get("reduced", {})
        ctx = {"ticks": [ticks[i] for i in sorted(ticks)], "before": before, "after": after,
               "launcher": final, "trace": reduced, "clients": clients, "on_chip": on_chip,
               "latency": e2e,
               "plan": {"live_rooms": plan.rooms, "dims": final["dims"],
                        "tick_ms": final["tick_ms"]}}
        say(f"traced run: {len(ctx['ticks'])} tick records of the window; trace "
            f"{ {k: v for k, v in final.get('trace', {}).items() if k != 'reduced'} }; "
            f"planes {reduced.get('planes')}; host spans {reduced.get('host_spans')}; "
            f"device programs {reduced.get('modules')}")
        read = {m["name"]: layer_metrics.read(m["name"], ctx) for m in wanted}
        metrics = {k: {"value": v, "unit": u} for k, (v, u) in read.items() if v is not None}
        if on_chip and reduced.get("busy_s"):
            device |= {"busy_s": reduced["busy_s"], "window_s": reduced["window_s"]}
            line["breakdown"] = {"device_ops": reduced["device_ops"],
                                 "idle_gaps": reduced["idle_gaps"]}
    else:
        values = e2e | {"setup_s": setup_s}
        metrics = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                   for m in wanted if m["name"] in values}
    if rehearse:      # toy sizes on the CPU: nothing under a metric's own name
        metrics = {f"rehearsal.{k}": v for k, v in metrics.items()}
    line |= {"metrics": metrics, "device": device, "seen": seen,
             "check": {k: {"value": check[k], "limit": LIMITS[k]} for k in LIMITS}}
    for k in LIMITS:
        say(f"check {k}: {check[k]} (limit {LIMITS[k]})")
    say(f"correct {correct}: attempted {attempted} deliveries, failed {check['missing']}")
    print(json.dumps(line), flush=True)
    return 0, line


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="toy sizes on whatever JAX finds; no number under a metric's name")
    args = ap.parse_args(argv)
    code, _ = run_cell(args.workload, args.seed, args.seconds, bool(args.trace),
                       args.rehearse)
    return code


if __name__ == "__main__":
    sys.exit(main())
