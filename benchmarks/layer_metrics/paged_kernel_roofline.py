"""The paged decide kernel's share of its roofline: least time for the live
pages' decide work over the traced device time of the tick program's Mosaic
calls, in per cent."""

import json
import sys
from pathlib import Path

from benchmarks import layer_metrics, paged_roofline, stats


def read(ctx):
    spec = json.loads(Path(__file__).with_suffix(".json").read_text())
    seconds = paged_roofline.mosaic_seconds_per_tick(ctx["trace"], spec["module_pattern"])
    live = stats.stat([t["live_pages"] for t in ctx["ticks"] if "live_pages" in t], "mean")
    plane = layer_metrics.dig(ctx["after"], "rooms.plane") or {}
    TP, SP = plane.get("pager_tpage"), plane.get("pager_spage")
    if not seconds or not live or not TP or not SP:
        return None
    K, L = ctx["plan"]["dims"][2], spec["layers"]
    kind = ctx["launcher"]["device"]["kind"]
    least, bound = paged_roofline.least_decide_s(kind, live, TP, K, SP, L)
    print(f"[roofline] paged decide: Mosaic calls {1e3 * seconds:.4f} ms a tick; least time "
          f"for {live:.2f} live pages of {TP} x {K} x {SP} {1e6 * least:.4f} us "
          f"({paged_roofline.page_bytes(TP, K, SP, L)} B a page, bound by {bound}); pool "
          f"{plane.get('pager_pool_pages')} pages", file=sys.stderr)
    return 100.0 * least / seconds
