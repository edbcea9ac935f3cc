"""The tick's share of its roofline: least time for the live rooms' work
over the traced device time of a tick, in per cent."""

import json
import sys
from pathlib import Path

from benchmarks import roofline, xplane


def read(ctx):
    pattern = json.loads(Path(__file__).with_suffix(".json").read_text())["module_pattern"]
    found = xplane.tick_program(ctx["trace"], pattern)
    if found is None or not found[1]:
        return None
    name, runs, seconds = found
    R, T, K, S = ctx["plan"]["dims"]
    kind = ctx["launcher"]["device"]["kind"]
    least, bound = roofline.least_tick_s(kind, ctx["plan"]["live_rooms"], T, K, S)
    all_rows, _ = roofline.least_tick_s(kind, R, T, K, S)
    print(f"[roofline] {name}: {runs} runs, {1e3 * seconds / runs:.4f} ms each; least "
          f"time for {ctx['plan']['live_rooms']} live rooms {1e6 * least:.4f} us "
          f"({roofline.tick_bytes(ctx['plan']['live_rooms'], T, K, S)} B, bound by {bound}); "
          f"for all {R} rows {1e6 * all_rows:.3f} us "
          f"({roofline.tick_bytes(R, T, K, S)} B)", file=sys.stderr)
    return 100.0 * least / (seconds / runs)
