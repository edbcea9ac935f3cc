"""What a tick must move and compute, from the plane's dims alone, and the
least time a chip could take for it. Kept with the benchmark so that it
does not move when the implementation does.

A tick, whatever implements it, reads and writes once the state rows of the
rooms that are live, reads their inputs and writes their outputs. Per room
row, for T tracks, K packet slots a track a tick, S subscribers (W = words of
a 32-bit subscriber mask), as the served plane lays them out today
(`models/plane`: PlaneState, `pack_tick_inputs`, `pack_tick_outputs`; the
coefficients were read off its shapes once, PR 26, and are checked against
them at both served widths in `tests/test_roofline.py`):

    state   323 T + 82 S + 26 T S            bytes
    inputs  4 (13 T K + 8 S + T)             bytes
    outputs 4 (3 T K W + 6 T K + 2 T S + 11 T + 5 S + 8)   bytes

Operations: one selection decision a (packet slot, subscriber) and one
allocation step a (track, subscriber), counted generously at 32 and 64
scalar operations each; the tick is elementwise, so this only has to show
that bytes, not operations, bound it.
"""

from __future__ import annotations

import json
from pathlib import Path

PEAKS_FILE = Path(__file__).with_name("peaks.json")


def peaks(device_kind: str) -> dict:
    """The chip's published peaks. A kind the table does not hold is an
    error, never a default."""
    table = json.loads(PEAKS_FILE.read_text())
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in {PEAKS_FILE.name}; "
                       f"known: {sorted(table)}")
    return table[device_kind]


def state_bytes_per_room(T: int, K: int, S: int) -> int:
    return 323 * T + 82 * S + 26 * T * S


def input_bytes_per_room(T: int, K: int, S: int) -> int:
    return 4 * (13 * T * K + 8 * S + T)


def output_bytes_per_room(T: int, K: int, S: int) -> int:
    W = -(-S // 32)
    return 4 * (3 * T * K * W + 6 * T * K + 2 * T * S + 11 * T + 5 * S + 8)


def tick_bytes(rooms: int, T: int, K: int, S: int) -> int:
    """Bytes a tick must move for `rooms` room rows: state read and written
    once, inputs read, outputs written."""
    return rooms * (2 * state_bytes_per_room(T, K, S)
                    + input_bytes_per_room(T, K, S) + output_bytes_per_room(T, K, S))


def tick_ops(rooms: int, T: int, K: int, S: int) -> int:
    return rooms * (32 * T * K * S + 64 * T * S)


def least_tick_s(device_kind: str, rooms: int, T: int, K: int, S: int) -> tuple[float, str]:
    """(the least seconds the chip could take for such a tick, which peak
    bounds it)."""
    p = peaks(device_kind)
    by_bytes = tick_bytes(rooms, T, K, S) / p["hbm_bytes_per_s"]
    by_ops = tick_ops(rooms, T, K, S) / p["flops_per_s"]
    return (by_bytes, "bytes") if by_bytes >= by_ops else (by_ops, "operations")
