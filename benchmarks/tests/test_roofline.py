"""The bytes-from-shapes function against the plane's own shapes at both
served widths, and the peaks table."""

import jax
import numpy as np
import pytest

from benchmarks import roofline

WIDTHS = {"serve default": (64, 16, 16, 32), "cfg4": (1024, 10, 8, 10)}


def _nbytes(tree) -> int:
    return sum(int(np.prod(x.shape)) * x.dtype.itemsize for x in jax.tree.leaves(tree))


@pytest.mark.parametrize("dims", WIDTHS.values(), ids=WIDTHS.keys())
def test_bytes_match_the_planes_shapes(dims):
    from livekit_server_tpu.models import plane

    R, T, K, S = dims
    pd = plane.PlaneDims(R, T, K, S)
    state = jax.eval_shape(lambda: plane.init_state(pd))
    assert R * roofline.state_bytes_per_room(T, K, S) == _nbytes(state)
    out_words = sum(int(np.prod(a.shape)) for a in plane.unpack_tick_outputs(
        np.zeros(R * roofline.output_bytes_per_room(T, K, S) // 4, np.int32), pd))
    assert 4 * out_words == R * roofline.output_bytes_per_room(T, K, S)
    pkt = len(plane.PKT_FIELDS) * R * T * K * 4
    assert pkt + 8 * R * S * 4 + R * T * 4 == R * roofline.input_bytes_per_room(T, K, S)


def test_recorded_sizes():
    # PERF.md: state 6,809,600 B and 4,456,448 B out at cfg4, 940,032 B out at
    # the serve defaults (my chip runs, PR 25)
    assert 1024 * roofline.state_bytes_per_room(10, 8, 10) == 6_809_600
    assert 1024 * roofline.output_bytes_per_room(10, 8, 10) == 4_456_448
    assert 64 * roofline.output_bytes_per_room(16, 16, 32) == 940_032


def test_least_time_scales_with_live_rooms_and_is_bound_by_bytes():
    one, bound = roofline.least_tick_s("TPU v5 lite", 1, 10, 8, 10)
    many, _ = roofline.least_tick_s("TPU v5 lite", 1024, 10, 8, 10)
    assert bound == "bytes" and many == pytest.approx(1024 * one)
    assert one == pytest.approx(roofline.tick_bytes(1, 10, 8, 10) / 819e9)


def test_unknown_device_kind_raises():
    with pytest.raises(KeyError, match="no peaks for device kind"):
        roofline.peaks("TPU v9 imaginary")
    with pytest.raises(KeyError):
        roofline.least_tick_s("cpu", 1, 4, 4, 4)
