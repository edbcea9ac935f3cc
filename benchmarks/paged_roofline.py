"""What the paged plane's decide kernel must move and compute for the live
pages of a tick, from the page's dims alone, the least time a chip could
take for it, and the device time the trace gives its Mosaic calls. Kept
with the benchmark, beside `roofline.py`, so that it does not move when the
kernel does.

The decide work of one live page of TP tracks x SP subscribers (SP <= 32,
so W = 1 mask word), K packet slots a track a tick, L spatial layers,
whatever implements it: read the page's selector state, the masks and flags
its subscription base is made of and the packet fields the decision reads,
as the plane stores them (`models/plane`: int32 selector lanes and packed
packet words, one-byte masks and flags); write the three egress masks a
packet slot, the new current layers, the key-frame requests, the send sums
and the stats and tracker routings the tick's core takes from it. A page
that is not mapped asks for nothing: the count is of live pages, not of the
grid's padded bucket, so a padded grid reads as a lower share, never as a
higher one. The element counts are checked against the kernel's operand
shapes in `tests/test_paged_roofline.py`.

Operations: one selection decision a (track, packet slot, subscriber) and
one routing a (track, packet slot, layer) for each of the eight routed
rows, counted generously at 32 scalar operations each; the work is
elementwise, so this only has to show that bytes, not operations, bound it.
"""

from __future__ import annotations

from benchmarks import roofline, xplane

MOSAIC_CALL = "tpu_custom_call"      # what `xplane.short_name` keeps of a Pallas kernel
PACKET_FIELDS_READ = 11              # layer, temporal, keyframe, layer_sync, end_frame, valid,
#                                      size, sn, ts, arrival_rtp, begin_pic


def reads(TP: int, K: int, SP: int, L: int) -> dict[str, tuple[int, int]]:
    """name → (elements a live page, bytes an element as the plane stores it)."""
    return {
        "selector state: current and target, spatial and temporal": (4 * TP * SP, 4),
        "subscribed, sub_muted": (2 * TP * SP, 1),
        "is_svc, is_video, published, pub_muted": (4 * TP, 1),
        "packet fields": (PACKET_FIELDS_READ * TP * K, 4),
        "the page's id": (1, 4),
    }


def writes(TP: int, K: int, SP: int, L: int) -> dict[str, tuple[int, int]]:
    W = -(-SP // 32)
    return {
        "send, drop and switch masks": (3 * TP * K * W, 4),
        "current spatial and temporal": (2 * TP * SP, 4),
        "need_keyframe": (TP * SP, 1),
        "pkts_sent, sent_bytes": (2 * SP, 4),
        "fwd_packets, fwd_bytes": (2, 4),
        "routed stats": (5 * TP * K * L, 4),
        "routed tracker": (3 * TP * L, 4),
    }


def page_bytes(TP: int, K: int, SP: int, L: int) -> int:
    return sum(n * size for table in (reads, writes)
               for n, size in table(TP, K, SP, L).values())


def page_ops(TP: int, K: int, SP: int, L: int) -> int:
    return 32 * TP * K * SP + 32 * 8 * TP * K * L


def least_decide_s(device_kind: str, live_pages: float, TP: int, K: int, SP: int,
                   L: int) -> tuple[float, str]:
    """(the least seconds the chip could take for the decide work of that
    many live pages, which peak bounds it)."""
    p = roofline.peaks(device_kind)
    by_bytes = live_pages * page_bytes(TP, K, SP, L) / p["hbm_bytes_per_s"]
    by_ops = live_pages * page_ops(TP, K, SP, L) / p["flops_per_s"]
    return (by_bytes, "bytes") if by_bytes >= by_ops else (by_ops, "operations")


def mosaic_seconds_per_tick(reduced: dict, pattern: str) -> float | None:
    """Device seconds a run of the tick's program that its Mosaic calls took:
    the entries of the reduction's longest operations whose name holds
    `tpu_custom_call`, summed, over the runs of the traced program whose name
    holds `pattern`. None where no such program ran or the longest operations
    hold no such entry."""
    found = xplane.tick_program(reduced, pattern)
    if found is None or not found[1]:
        return None
    calls = [seconds for name, seconds in reduced.get("device_ops", ())
             if MOSAIC_CALL in name]
    return sum(calls) / found[1] if calls else None
