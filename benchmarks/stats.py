"""The arithmetic of the end-to-end metrics: raw percentiles (no bins) over
every sample, and rates over the whole window."""

from __future__ import annotations

import math


def percentile(sorted_values, q: float) -> float:
    """The q-th percentile (0..100) of values sorted ascending, by linear
    interpolation between closest ranks (numpy's default rule)."""
    n = len(sorted_values)
    if n == 0:
        raise ValueError("no samples")
    pos = (n - 1) * q / 100.0
    lo = math.floor(pos)
    hi = min(lo + 1, n - 1)
    return float(sorted_values[lo] + (sorted_values[hi] - sorted_values[lo]) * (pos - lo))


def end_to_end(due_ns, arrival_ns, t0_ns: int, window_ns: int) -> dict:
    """The window's end-to-end numbers from every media packet that arrived
    whole: `due_ns` when its publisher was due to send it, `arrival_ns` the
    kernel's stamp at the subscriber's socket (numpy int64 arrays).

    Latency is taken over all packets due in the window, whenever they
    arrived, so a stall counts the wait it imposes; the rate is every packet
    that arrived inside the window over all of the window's seconds, so a
    stall that holds packets past the close shows there too."""
    import numpy as np

    due_ns, arrival_ns = np.asarray(due_ns), np.asarray(arrival_ns)
    end_ns = t0_ns + window_ns
    due_in = (due_ns >= t0_ns) & (due_ns < end_ns)
    lat_ms = np.sort((arrival_ns[due_in] - due_ns[due_in]) / 1e6)
    arrived = int(((arrival_ns >= t0_ns) & (arrival_ns < end_ns)).sum())
    out = {"samples": int(lat_ms.size),
           "delivered_pps": arrived / (window_ns / 1e9)}
    if lat_ms.size:
        out |= {f"fwd_latency_p{q}_ms": percentile(lat_ms, q) for q in (50, 90, 99)}
    return out


def stat(values: list[float], name: str) -> float | None:
    """p<q>, max, mean or sum of a list; None where it is empty."""
    if not values:
        return None
    if name == "max":
        return float(max(values))
    if name == "mean":
        return float(sum(values) / len(values))
    if name == "sum":
        return float(sum(values))
    if name.startswith("p"):
        return percentile(sorted(values), float(name[1:]))
    raise ValueError(f"unknown statistic {name!r}")
