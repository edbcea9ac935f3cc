"""The per-layer metrics that read the program's own spans (`sfu/...`,
`livekit_server_tpu/runtime/trace.py`) and none of which needs a chip: a
traced run at toy size on the CPU has to print every one under
`rehearsal.<name>` with a finite value, so that a key renamed in the
program fails here and not as a silent `None` on the chip."""

import math

from benchmarks import run

SPAN_METRICS = ("rx_ms_per_tick", "rx_pkts_per_wakeup", "staging_wait_ms", "sleep_ms_p50",
                "dispatch_delay_ms_p95", "handoff_ms_p95", "checkpoint_ms",
                "device_dispatch_ms_p50", "device_fetch_ms_p50", "egress_wait_ms",
                "send_ms_p50", "warm_exec_s")


def test_a_traced_rehearsal_prints_every_span_metric():
    code, line = run.run_cell("meet-tick20.steady", 4_100_000_011, 3.0, True, True)
    assert code == 0 and line["correct"] is True, line
    metrics = line["metrics"]
    for name in SPAN_METRICS:
        assert f"rehearsal.{name}" in metrics, (name, sorted(metrics))
        assert math.isfinite(metrics[f"rehearsal.{name}"]["value"]), name
    # a wake-up brings a packet at least (how many more is the host's load); the waits are real time
    assert metrics["rehearsal.rx_pkts_per_wakeup"]["value"] >= 1.0
    for name in ("rx_ms_per_tick", "staging_wait_ms", "sleep_ms_p50", "checkpoint_ms",
                 "device_dispatch_ms_p50", "device_fetch_ms_p50", "egress_wait_ms",
                 "warm_exec_s"):
        assert metrics[f"rehearsal.{name}"]["value"] > 0.0, name
