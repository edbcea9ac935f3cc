"""The paged decide kernel's bytes-from-dims function against the kernel's
own operand shapes at the paged configuration's widths, and the two readers
that use it on a made-up reduction."""

import json

import jax
import numpy as np
import pytest

from benchmarks import layer_metrics, paged_roofline, run

CELL = "meet-tick20-paged.steady"
L = 3


def _paged_dims():
    from livekit_server_tpu.models import paged

    plane_cfg = run.load_cell(CELL)[2]["server_config"]["plane"]
    return paged.PagedDims(
        plane_cfg["rooms"], plane_cfg["tracks_per_room"], plane_cfg["pkts_per_track"],
        plane_cfg["subs_per_room"], tpage=plane_cfg["pager_tpage"],
        spage=plane_cfg["pager_spage"], pool_pages=plane_cfg["pager_pool_pages"])


def _elements(tree, rows: int) -> int:
    """Elements a row of the leading axis, over a tree of shapes."""
    return sum(int(np.prod(x.shape)) for x in jax.tree.leaves(tree)) // rows


def test_element_counts_match_the_kernels_operands():
    from livekit_server_tpu.analysis.devicecheck import _zero_inputs
    from livekit_server_tpu.models import plane
    from livekit_server_tpu.ops import paged_kernel

    pd = _paged_dims()
    P, TP, K, SP = pd.pool_pages, pd.tpage, pd.pkts, pd.spage
    assert (P, TP, K, SP) == (1024, 4, 16, 8) and L == paged_kernel.NUM_LAYERS
    pooled = pd.pooled()
    NL = 64

    def operands():
        s, inp = plane.init_state(pooled), _zero_inputs(pooled)
        base = s.ctrl.subscribed & ~s.ctrl.sub_muted
        return paged_kernel._decide_inputs(s.sel, s.meta.is_svc, s.meta.is_video, base, inp)

    def products():
        ops = operands()
        return paged_kernel._pallas_live_call(
            np.zeros(NL, np.int32), ops, None, TP=TP, K=K, SP=SP, N=0, L=L,
            wire_overhead=42, top_k=0, interpret=True)

    ops = jax.eval_shape(operands)
    assert len(ops) == 7 + paged_roofline.PACKET_FIELDS_READ
    reads = paged_roofline.reads(TP, K, SP, L)
    # the kernel takes the subscription base as one mask and two of the four
    # flags; the base is made of two masks and the other two flags, and the
    # page's id is the prefetched scalar
    assert sum(n for n, _ in reads.values()) == _elements(ops, P) + TP * SP + 2 * TP + 1
    assert reads["packet fields"][0] == _elements(ops[7:], P)
    writes = paged_roofline.writes(TP, K, SP, L)
    outs = jax.eval_shape(products)
    assert len(outs) == 12 and all(o.shape[0] == NL for o in outs)
    assert sum(n for n, _ in writes.values()) == _elements(outs, NL)
    assert writes["routed stats"][0] == _elements(outs[10], NL)
    # masks and flags are a byte in the plane's state where the kernel's
    # operands are all 32-bit: the least bytes lie under the operands' bytes
    in_words = 4 * (_elements(ops, P) + 1 + _elements(outs, NL))
    assert 0.9 * in_words < paged_roofline.page_bytes(TP, K, SP, L) < in_words
    assert paged_roofline.page_bytes(TP, K, SP, L) == 8524


def test_least_time_scales_with_live_pages_and_is_bound_by_bytes():
    one, bound = paged_roofline.least_decide_s("TPU v5 lite", 1, 4, 16, 8, L)
    many, _ = paged_roofline.least_decide_s("TPU v5 lite", 18, 4, 16, 8, L)
    assert bound == "bytes" and many == pytest.approx(18 * one)
    assert one == pytest.approx(paged_roofline.page_bytes(4, 16, 8, L) / 819e9)
    with pytest.raises(KeyError, match="no peaks for device kind"):
        paged_roofline.least_decide_s("cpu", 1, 4, 16, 8, L)


REDUCED = {
    "modules": {"jit_tick(123)": [100, 0.020], "jit_apply_ctrl_delta(5)": [3, 0.0001]},
    "device_ops": [["%fusion.1 fusion", 0.012],
                   ["%custom-call.5 custom-call tpu_custom_call", 0.003],
                   ["%custom-call.9 custom-call tpu_custom_call", 0.001],
                   ["%copy.2 copy", 0.0005]],
}


def _ctx(trace, ticks=(18, 18, 18, 18), plane=None):
    plane = {"ticks": 9, "pager_tpage": 4, "pager_spage": 8,
             "pager_pool_pages": 1024} if plane is None else plane
    return {"trace": trace, "ticks": [{"idx": i, "live_pages": n} for i, n in enumerate(ticks)],
            "before": {"rooms": {"plane": plane}}, "after": {"rooms": {"plane": plane}},
            "launcher": {"device": {"kind": "TPU v5 lite"}}, "on_chip": True,
            "plan": {"live_rooms": 9, "dims": [64, 16, 16, 32], "tick_ms": 20}}


def test_the_readers_on_a_made_up_reduction():
    ctx = _ctx(json.loads(json.dumps(REDUCED)))
    assert layer_metrics.read("live_pages", ctx) == (18.0, "pages")
    ms, unit = layer_metrics.read("paged_kernel_device_ms", ctx)
    assert unit == "ms" and ms == pytest.approx(1e3 * 0.004 / 100)
    assert layer_metrics.read("paged_tick_device_ms", ctx) == (pytest.approx(0.2), "ms")
    share, unit = layer_metrics.read("paged_kernel_roofline", ctx)
    least = 18 * 8524 / 819e9
    assert unit == "%" and share == pytest.approx(100 * least / 0.00004) and 0 < share < 100


@pytest.mark.parametrize("name", ["live_pages", "paged_tick_device_ms", "paged_kernel_device_ms",
                                  "paged_kernel_roofline"])
def test_a_program_without_the_step_or_the_counter_reads_nothing(name):
    """The parent of the PR that brought these: its live step is two programs,
    `jit_decide` and `jit_rest`, its tick record has no `live_pages`, and
    `/debug/rooms` `plane` no page dims. Nothing is read and nothing raises."""
    parent = {"modules": {"jit_decide(1)": [100, 0.004], "jit_rest(2)": [100, 0.016]},
              "device_ops": REDUCED["device_ops"]}
    ctx = _ctx(parent, plane={"ticks": 9})
    for t in ctx["ticks"]:
        del t["live_pages"]
    assert layer_metrics.read(name, ctx)[0] is None
    # nor on the CPU, nor where the longest operations hold no Mosaic call
    assert layer_metrics.read(name, _ctx({}) | {"on_chip": False})[0] is None or name == "live_pages"
    no_call = {"modules": REDUCED["modules"], "device_ops": [["%fusion.1 fusion", 0.012]]}
    assert layer_metrics.read(name, _ctx(no_call))[0] is None or "kernel" not in name
