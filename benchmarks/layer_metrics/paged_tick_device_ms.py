"""Device time of the paged live step's program, a run, from the profiler
trace: what `tick_device_ms` reads in the dense cells."""

import json
from pathlib import Path

from benchmarks import xplane


def read(ctx):
    pattern = json.loads(Path(__file__).with_suffix(".json").read_text())["module_pattern"]
    found = xplane.tick_program(ctx["trace"], pattern)
    if found is None or not found[1]:
        return None
    _, runs, seconds = found
    return 1e3 * seconds / runs
