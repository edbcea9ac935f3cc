"""Per-layer metrics, each a file of its own under `layer_metrics/`, found by
the name in `BENCHMARK.json`. `<name>.json` states the layer, the unit, the
end-to-end metric it should move and where the number comes from: either a
declarative `source` read out of what a traced run gathered (the context
below), or `{"kind": "reader"}` with a `<name>.py` beside it that holds
`read(ctx) -> float | None`. A source that finds nothing to read gives
None, and the harness leaves the metric out of the line.

The context of a traced run:
  ticks      the window's `/debug/ticks` records, merged by `idx`
  before / after   {"rooms": /debug/rooms, "overload": /debug/overload,
             "compiles": /debug/compiles} at the window's two ends
  launcher   the launcher's record (device, warm-up, memory, trace)
  trace      the reduced profiler trace (`xplane.reduce`), {} where none
  clients    what the client processes measured of themselves
  latency    `stats.end_to_end` of this (traced) run
  plan       {"live_rooms", "dims": [R, T, K, S], "tick_ms"}
  on_chip    whether the server's first device is a TPU
"""

from __future__ import annotations

import importlib.util
import json
from pathlib import Path

from benchmarks import stats

HERE = Path(__file__).with_name("layer_metrics")


def dig(tree, path: str):
    """`a.b.c` out of nested dicts; None where a step is missing."""
    for key in path.split("."):
        if not isinstance(tree, dict) or key not in tree:
            return None
        tree = tree[key]
    return tree


def _difference(ctx: dict, path: str):
    a, b = dig(ctx["before"], path), dig(ctx["after"], path)
    return None if a is None or b is None else b - a


def read(name: str, ctx: dict, directory: Path = HERE) -> tuple[float | None, str]:
    """(the metric's value or None, its unit)."""
    spec = json.loads((directory / f"{name}.json").read_text())
    src = spec["source"]
    if spec.get("needs_chip") and not ctx["on_chip"]:
        return None, spec["unit"]
    kind, value = src["kind"], None
    if kind == "ticks":
        value = stats.stat([t[src["field"]] for t in ctx["ticks"]
                            if src["field"] in t], src["stat"])
    elif kind == "difference":
        value = _difference(ctx, src["path"])
    elif kind == "share_of_differences":
        num, den = _difference(ctx, src["num"]), _difference(ctx, src["den"])
        value = None if num is None or not den else num / den
    elif kind == "value":
        value = dig(ctx, src["path"])
    elif kind == "reader":
        module_spec = importlib.util.spec_from_file_location(
            f"benchmarks.layer_metrics.{name.replace('.', '_')}", directory / f"{name}.py")
        module = importlib.util.module_from_spec(module_spec)
        module_spec.loader.exec_module(module)
        value = module.read(ctx)
    else:
        raise ValueError(f"{name}: unknown source kind {kind!r}")
    if value is None:
        return None, spec["unit"]
    return float(value) * src.get("scale", 1), spec["unit"]
