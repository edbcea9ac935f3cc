"""Slow latency smoke: a short real-socket express-lane run.

Registered behind ``python -m tools.check --latency`` (and pytest's
``slow`` marker — tier-1 excludes it): boots the 2-room interactive
shape from bench.py's wire section with the express lane enabled and
asserts the tier actually engages and stays under a deliberately loose
wire-p99 bound. The bound is a smoke detector for regressions that
re-introduce tick-queue waits on the express path (an order of
magnitude above the target measured in BASELINE.md), not a perf gate —
shared CI boxes are noisy.
"""

import pytest

from bench import wire_bench
from livekit_server_tpu.models import plane

pytestmark = pytest.mark.slow

# Loose by design: the express path's measured local p99 is ~1-2 orders
# below this; a tick-queued regression lands above it even on a busy box
# (2 ms ticks → batching alone costs ≥ a window + pipeline depth).
P99_BOUND_MS = 50.0


async def test_express_wire_p99_smoke():
    dims = plane.PlaneDims(rooms=2, tracks=8, pkts=8, subs=6)
    out = await wire_bench(
        dims,
        tick_ms=2,
        duration_s=3.0,
        warm_ticks=30,
        video_tracks=4,
        audio_tracks=4,
        express_max_subs=dims.subs,
    )
    assert out.get("task_errors") is None or not out["task_errors"]
    assert out["express_samples"] > 0, "express tier never carried traffic"
    assert out["express"]["active_rooms"], "no room promoted to express"
    assert out["p99_wire_express_ms"] < P99_BOUND_MS, (
        f"express wire p99 {out['p99_wire_express_ms']} ms ≥ "
        f"{P99_BOUND_MS} ms — arrival-driven sends are queueing somewhere "
        f"(late causes: {out['late_cause']})"
    )
