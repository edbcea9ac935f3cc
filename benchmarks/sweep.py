#!/usr/bin/env python3
"""The knee sweep of a cell: the same run at a ladder of live rooms, one line
a run. The knee is the largest number of live rooms at which, in every run
there, no expected delivery is missing, the governor stays at 0,
`ingest_dropped` is 0 and latency does not climb through the window.

    python3 benchmarks/sweep.py --workload <cell> --rooms 4,8,16 [--runs 2]
        [--seconds 10] [--tick-ms 20] [--out chiprun_out/sweep.jsonl]

A tool for the PR that sets or moves a cell's `live_rooms`; the driver never
runs it. What it found is in PERF.md section 4.
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

from benchmarks import run  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rooms", help="comma-separated live rooms (default: the cell's own)")
    ap.add_argument("--runs", type=int, default=1)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--seed", type=int, default=1000)
    ap.add_argument("--tick-ms", type=int)
    ap.add_argument("--pace-ms", type=float)
    ap.add_argument("--control-drop-pct", type=float,
                    help="the control: the server's fault injector sheds this share of ingest")
    ap.add_argument("--trace", type=int, default=0)
    ap.add_argument("--rooms-per-client", type=int, default=4)
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--out")
    args = ap.parse_args()
    seed = args.seed
    overrides = {}
    if args.tick_ms:
        overrides["plane"] = {"tick_ms": args.tick_ms}
    if args.control_drop_pct:
        overrides["faults"] = {"enabled": True, "seed": 1, "drop_pct": args.control_drop_pct}
    for rooms in (map(int, args.rooms.split(",")) if args.rooms else [None]):
        for _ in range(args.runs):
            seed += 1
            clients = (None if rooms is None             # the cell as its file has it
                       else max(1, min(8, -(-rooms // args.rooms_per_client))))
            changed = {"live_rooms": rooms, "client_processes": clients,
                       "pace_ms": args.pace_ms}
            code, line = run.run_cell(
                args.workload, seed, args.seconds, bool(args.trace), args.rehearse,
                workload_overrides={k: v for k, v in changed.items() if v is not None},
                server_overrides=overrides or None)
            row = {"workload": args.workload, "live_rooms": rooms, "clients": clients,
                   "tick_ms": args.tick_ms, "pace_ms": args.pace_ms, "seed": seed, "exit": code} | (line or {})
            print("SWEEP " + json.dumps(row), flush=True)
            if args.out:
                Path(args.out).parent.mkdir(parents=True, exist_ok=True)
                with open(args.out, "a") as f:
                    f.write(json.dumps(row) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
