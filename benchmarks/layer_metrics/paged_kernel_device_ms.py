"""Device time a tick of the tick program's Mosaic calls, from the profiler
trace."""

import json
from pathlib import Path

from benchmarks import paged_roofline


def read(ctx):
    pattern = json.loads(Path(__file__).with_suffix(".json").read_text())["module_pattern"]
    seconds = paged_roofline.mosaic_seconds_per_tick(ctx["trace"], pattern)
    return None if seconds is None else 1e3 * seconds
