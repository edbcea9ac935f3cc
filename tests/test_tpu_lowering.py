"""Compile rehearsal for the chip: the kernels and step programs of the
served path, compiled for a described (not attached) TPU v5e at the
widths the server runs them.

Nothing runs here — a compile that passes says only that the chip's
compiler accepts the program; `chip_smoke.py` is the chip run. The
topology is described inside a fixture (one process at a time may load
the TPU's library, and pytest-xdist workers each import every test
file), and this is the only file that describes one. The code under
test asks `jax.default_backend()` and would take its CPU composition
under JAX_PLATFORMS=cpu, so the `as_tpu` fixture answers "tpu" for the
duration of a test.
"""

from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from livekit_server_tpu.models import plane

SERVE_DEFAULT = (64, 16, 16, 32)     # config.PlaneConfig defaults
CFG4 = (1024, 10, 8, 10)             # BASELINE.json cfg4 / chip_smoke.py


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # noqa: BLE001 — no libtpu, or it is locked
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def as_tpu(monkeypatch):
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")


def _on(tree, sharding):
    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding),
        tree,
    )


def _sds(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _custom_calls(compiled) -> int:
    return compiled.as_text().count('custom_call_target="tpu_custom_call"')


def _packed_inputs(dims: plane.PlaneDims):
    """Abstract (pkt, fb, tf, tick_ms, roll_quality) as the runtime
    uploads them (plane.pack_tick_inputs)."""
    from livekit_server_tpu.analysis.devicecheck import _zero_inputs

    zeros = jax.tree.map(
        lambda x: np.zeros(x.shape, x.dtype),
        jax.eval_shape(lambda: _zero_inputs(dims)),
    )
    return jax.eval_shape(
        lambda: tuple(jnp.asarray(a) for a in plane.pack_tick_inputs(zeros))
    )


@pytest.mark.parametrize("dims", [SERVE_DEFAULT, CFG4], ids=["serve", "cfg4"])
def test_served_step_lowers(one_chip, as_tpu, dims):
    """The program `PlaneRuntime` dispatches every tick (packed upload →
    media_plane_tick → packed outputs), with both room-batched kernels."""
    from livekit_server_tpu.ops import audio, bwe
    from livekit_server_tpu.runtime.plane_runtime import _packed_tick

    d = plane.PlaneDims(*dims)
    state = _on(jax.eval_shape(lambda: plane.init_state(d)), one_chip)
    packed = _on(_packed_inputs(d), one_chip)
    # A fresh jit, not the runtime's shared one: that may already hold a
    # CPU-branch trace of this shape from another test in this process.
    step = jax.jit(
        # allow_pause False: what `serve` builds from the default config
        _packed_tick(audio.AudioLevelParams(), bwe.BWEParams(allow_pause=False)),
        donate_argnums=(0,),
    )
    compiled = step.lower(state, *packed).compile()
    assert _custom_calls(compiled) == 2   # decide_rooms + allocate_budget_rooms


# Room counts for the two `pick_room_block` branches no served default
# reaches: 100 has no 128-multiple divisor (whole-array block, lane dim
# padded by Mosaic); a width whose smallest legal block is
# over the ~4 MB working-set cap (block 128 under the raised vmem limit);
# 256 rooms make that two grid steps.
ROOM_BLOCK_CASES = {
    "whole_array": dict(R=100, T=10, K=8, S=10),
    "over_budget": dict(R=256, T=10, K=8, S=80),
}


def _check_case(case: str, R: int, per_room_bytes: int) -> None:
    """The case's shape really takes the `pick_room_block` branch it names."""
    from livekit_server_tpu.ops.selector import pick_room_block

    over = per_room_bytes * 128 > (4 << 20)
    block = pick_room_block(R, per_room_bytes)
    if case == "whole_array":
        assert R % 128 != 0 and block == R
    else:
        assert over and block == 128 < R


@pytest.mark.parametrize("case", list(ROOM_BLOCK_CASES))
def test_decide_rooms_lowers(one_chip, case):
    from livekit_server_tpu.ops import pacer, selector

    R, T, K, S = ROOM_BLOCK_CASES[case].values()
    per_room = 4 * (T * (7 * K + 9 * S + 3 * K * ((S + 31) // 32)) + 2 * S + 2)
    _check_case(case, R, per_room)
    sel = selector.SelectorState(*[
        _sds(one_chip, (R, T, S), jnp.int32)
        for _ in selector.SelectorState._fields
    ])
    rt = lambda dt: _sds(one_chip, (R, T), dt)          # noqa: E731
    rtk = lambda dt: _sds(one_chip, (R, T, K), dt)      # noqa: E731

    def f(sel, svc, vid, base, sp, tp, kf, sync, eof, valid, size):
        return selector.decide_rooms(
            sel, svc, vid, base, sp, tp, kf, sync, eof, valid, size,
            wire_overhead=pacer.WIRE_OVERHEAD_BYTES, use_pallas=True,
        )

    compiled = jax.jit(f).lower(
        sel, rt(bool), rt(bool), _sds(one_chip, (R, T, S), bool),
        rtk(jnp.int32), rtk(jnp.int32), rtk(bool), rtk(bool), rtk(bool),
        rtk(bool), rtk(jnp.int32),
    ).compile()
    assert _custom_calls(compiled) == 1


ALLOC_CASES = {
    "whole_array": dict(R=100, T=10, S=10),
    "over_budget": dict(R=256, T=32, S=64),
}


@pytest.mark.parametrize("case", list(ALLOC_CASES))
def test_allocate_budget_rooms_lowers(one_chip, case):
    from livekit_server_tpu.ops import allocation, selector

    R, T, S = ALLOC_CASES[case].values()
    per_room = 4 * (T * allocation.NUM_LAYERS + 5 * T * S + 2 * S)
    _check_case(case, R, per_room)
    rst = lambda dt: _sds(one_chip, (R, S, T), dt)      # noqa: E731
    compiled = jax.jit(
        lambda b, ms, mt, mu, bud: allocation.allocate_budget_rooms(
            b, ms, mt, mu, bud, use_pallas=True)
    ).lower(
        _sds(one_chip, (R, T, 4, 4), jnp.float32), rst(jnp.int32),
        rst(jnp.int32), rst(bool), _sds(one_chip, (R, S), jnp.float32),
    ).compile()
    assert _custom_calls(compiled) == 1


def test_device_mixer_lowers(one_chip):
    """The batched MCU mix at the room count where `AudioMixer` leaves
    the host loop for the device (plain XLA: a contraction, no kernel)."""
    from livekit_server_tpu.runtime import mixer

    R, T, S, N = mixer.DEVICE_MIX_MIN_ROOMS, 8, 16, 960   # 20 ms @ 48 kHz
    compiled = mixer._device_mix(T, S, N).lower(
        _sds(one_chip, (R, T, N), jnp.float32), _sds(one_chip, (R, T), bool),
        _sds(one_chip, (R, S), jnp.int32),
    ).compile()
    text = compiled.as_text()
    assert _custom_calls(compiled) == 0
    assert "convolution" in text or "dot(" in text or "fusion" in text


def test_sharded_tick_lowers_for_four_chips(topo, as_tpu):
    """`mesh.make_sharded_tick` over the four described chips at 4 x cfg4
    rooms (`chip_smoke.py --chips 4`): one kernel pair per shard, and no
    collective anywhere in the program."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from livekit_server_tpu.analysis.devicecheck import _zero_inputs
    from livekit_server_tpu.parallel.mesh import ROOM_AXIS, make_mesh, make_sharded_tick

    d = plane.PlaneDims(4 * CFG4[0], *CFG4[1:])
    mesh = make_mesh(topo.devices)
    assert mesh.devices.size == 4

    def on_mesh(tree):
        return jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(
                x.shape, x.dtype,
                sharding=NamedSharding(mesh, P(ROOM_AXIS) if x.ndim else P())),
            tree,
        )

    state = on_mesh(jax.eval_shape(lambda: plane.init_state(d)))
    inp = on_mesh(jax.eval_shape(lambda: _zero_inputs(d)))
    compiled = make_sharded_tick(mesh).lower(state, inp).compile()
    text = compiled.as_text()
    assert _custom_calls(compiled) == 2
    for collective in ("all-reduce", "all-gather", "all-to-all",
                       "collective-permute", "reduce-scatter"):
        assert collective not in text, collective


# -- the ragged paged kernel (ops/paged_kernel.py) ---------------------------

def _paged_dims(which: str):
    from livekit_server_tpu.analysis.devicecheck import canonical_dims
    from livekit_server_tpu.models import paged

    if which == "config_default":      # tpage 4, spage 8, pool 1024
        return canonical_dims()[1]
    return paged.PagedDims(rooms=4, tracks=4, pkts=4, subs=8,   # test_paged_kernel.PD
                           tpage=2, spage=4, pool_pages=16)


@pytest.mark.parametrize("which", ["config_default", "test_shape"])
def test_paged_fused_tick_lowers(one_chip, as_tpu, which):
    """`paged_plane_tick_fused` with the Pallas page kernel, half the pool
    live: the kernel, the live core's allocation kernel, and the two
    kernels of the representative dead-page tick."""
    from livekit_server_tpu.analysis.devicecheck import _zero_inputs
    from livekit_server_tpu.models import paged

    pd = _paged_dims(which)
    pooled = pd.pooled()
    state = _on(jax.eval_shape(lambda: plane.init_state(pooled)), one_chip)
    inp = _on(jax.eval_shape(lambda: _zero_inputs(pooled)), one_chip)
    table = _on(jax.eval_shape(lambda: paged.init_table(pd)), one_chip)
    live_rows = _sds(one_chip, (pd.pool_pages // 2,), jnp.int32)
    live_inv = _sds(one_chip, (pd.pool_pages,), jnp.int32)
    compiled = jax.jit(
        lambda s, i, t, lr, li: paged.paged_plane_tick_fused(
            s, i, t, lr, li, use_pallas=True)
    ).lower(state, inp, table, live_rows, live_inv).compile()
    assert _custom_calls(compiled) == 4


def test_served_paged_step_lowers(one_chip, as_tpu):
    """The one program `PagedPlaneRuntime` dispatches a live tick (packed
    upload → `paged_plane_tick_fused` → packed outputs) at the widths of
    `config-sample.yaml` with `pager_enabled`, at the live bucket a few
    small rooms fall in (a sixteenth of the pool): its XLA module is
    `jit_tick`, and it holds the four kernels of the fused tick."""
    from livekit_server_tpu.models import paged
    from livekit_server_tpu.ops import audio, bwe
    from livekit_server_tpu.runtime import paged_runtime

    pd = _paged_dims("config_default")
    pooled = pd.pooled()
    state = _on(jax.eval_shape(lambda: plane.init_state(pooled)), one_chip)
    table = _on(jax.eval_shape(lambda: paged.init_table(pd)), one_chip)
    packed = _on(_packed_inputs(pooled), one_chip)
    # the function under the cache: a fresh jit, as in test_served_step_lowers
    step = paged_runtime._build_live_step.__wrapped__(
        audio.AudioLevelParams(), bwe.BWEParams(allow_pause=False), True, False)
    lowered = step.lower(
        state, table, _sds(one_chip, (pd.pool_pages // 16,), jnp.int32),
        _sds(one_chip, (pd.pool_pages,), jnp.int32), *packed)
    assert lowered.as_text().lstrip().startswith("module @jit_tick")
    compiled = lowered.compile()
    assert _custom_calls(compiled) == 4
    assert "paged_decide" in compiled.as_text()


def test_paged_decide_mix_lowers(one_chip):
    """Decide + page-local mix as one grid (20 ms of 48 kHz PCM a track)."""
    from livekit_server_tpu.analysis.devicecheck import _zero_inputs
    from livekit_server_tpu.ops import pacer, paged_kernel

    pd = _paged_dims("config_default")
    pooled = pd.pooled()
    P, TP, SP, N = pd.pool_pages, pd.tpage, pd.spage, 960
    state = _on(jax.eval_shape(lambda: plane.init_state(pooled)), one_chip)
    inp = _on(jax.eval_shape(lambda: _zero_inputs(pooled)), one_chip)

    def f(s, i, pcm, level, active, sub_track, gain, live_rows):
        base = (s.ctrl.subscribed & ~s.ctrl.sub_muted
                & (s.meta.published & ~s.meta.pub_muted)[:, :, None])
        return paged_kernel.decide_mix_pages(
            s.sel, s.meta.is_svc, s.meta.is_video, base, i,
            pcm, level, active, sub_track, gain, live_rows,
            wire_overhead=pacer.WIRE_OVERHEAD_BYTES, use_pallas=True,
        )

    compiled = jax.jit(f).lower(
        state, inp, _sds(one_chip, (P, TP, N), jnp.float32),
        _sds(one_chip, (P, TP), jnp.float32), _sds(one_chip, (P, TP), bool),
        _sds(one_chip, (P, SP), jnp.int32),
        _sds(one_chip, (P, TP), jnp.float32),
        _sds(one_chip, (P // 2,), jnp.int32),
    ).compile()
    assert _custom_calls(compiled) == 1
