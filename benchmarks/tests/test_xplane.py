"""The trace reduction on a small recorded trace: six ticks of
`meet-tick20.steady`'s server (then `meet-default.steady`, nine rooms of 4,
batched) on one TPU v5 lite (my chip run, PR 26, call 8), cut by
`cut_trace.py` to the device's program and operation lines and the
launcher's host spans."""

from pathlib import Path

import pytest

from benchmarks import layer_metrics, xplane

TRACE = Path(__file__).with_name("data") / "tick_trace.xplane.pb"


@pytest.fixture(scope="module")
def reduced():
    return xplane.reduce(TRACE)


def test_busy_time_programs_and_spans(reduced):
    assert reduced["planes"] == ["/device:TPU:0", "/host:CPU"]
    assert reduced["chips_traced"] == 1
    assert reduced["window_s"] == pytest.approx(0.106152763)
    assert reduced["busy_s"] == pytest.approx(0.001323132)
    assert reduced["modules"] == {"jit_tick(11959282601242290979)":
                                  [6, pytest.approx(0.001338057)]}
    assert {k: v[0] for k, v in reduced["host_spans"].items()} == {
        "bench/stage_host": 6, "bench/fan_out": 6, "bench/device_step": 6}
    # operations never overlap on one chip, so their sum is the busy time
    assert len(reduced["device_ops"]) == 10
    name, seconds = reduced["device_ops"][0]
    assert name == "%tick.2 custom-call tpu_custom_call"       # the Pallas kernel
    assert seconds == pytest.approx(0.000319598)
    assert all(len(n) <= 120 for n, _ in reduced["device_ops"])


def test_idle_gaps_are_named_by_the_host_span_they_fall_in(reduced):
    gaps = dict(reduced["idle_gaps"])
    idle = reduced["window_s"] - reduced["busy_s"]
    assert gaps[xplane.UNNAMED_GAP] == pytest.approx(0.068048745)
    assert gaps["bench/device_step"] == pytest.approx(0.034858476)
    # the device call's span is all idle but the program's own 0.22 ms a tick
    assert gaps["bench/device_step"] == pytest.approx(
        reduced["host_spans"]["bench/device_step"][1] - reduced["busy_s"], rel=1e-6)
    assert gaps[xplane.UNNAMED_GAP] < idle < sum(gaps.values())


def test_the_device_metrics_read_it(reduced):
    ctx = {"trace": reduced, "on_chip": True, "launcher": {"device": {"kind": "TPU v5 lite"}},
           "plan": {"live_rooms": 9, "dims": [64, 16, 16, 32]}}
    tick_ms, _ = layer_metrics.read("tick_device_ms", ctx)
    assert tick_ms == pytest.approx(1e3 * 0.001338057 / 6)
    idle, unit = layer_metrics.read("device_idle_share", ctx)
    assert unit == "%" and idle == pytest.approx(100 * (1 - 0.001323132 / 0.106152763))
    roof, unit = layer_metrics.read("tick_roofline", ctx)
    # 9 live rooms' 641,664 B at 819 GB/s against 0.223 ms
    assert unit == "%" and roof == pytest.approx(100 * (641664 / 819e9) / (0.001338057 / 6))
    assert 0 < roof < 100
    # a CPU rehearsal prints no device metric
    assert layer_metrics.read("tick_device_ms", ctx | {"on_chip": False})[0] is None


def test_a_trace_without_a_device_plane_says_nothing_of_the_device(reduced):
    assert xplane.tick_program({"modules": {}}, "tick") is None
    assert xplane.tick_program(reduced, "no-such-program") is None
    ctx = {"trace": {"window_s": 1.0, "host_spans": {}}, "on_chip": True,
           "launcher": {"device": {"kind": "TPU v5 lite"}},
           "plan": {"live_rooms": 1, "dims": [1, 1, 1, 1]}}
    for metric in ("tick_device_ms", "tick_roofline", "device_idle_share"):
        assert layer_metrics.read(metric, ctx)[0] is None


def test_short_names():
    assert xplane.short_name("%copy.7 = pred[64,16]{1,0:T(8,128)(4,1)S(1)} copy(pred[64,16]{0,1} %x)") \
        == "%copy.7 copy"
    assert xplane.short_name("jit_tick(123)") == "jit_tick(123)"
