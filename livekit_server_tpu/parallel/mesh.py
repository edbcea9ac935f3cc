"""Device mesh + shardings for the room axis.

Reference parity: the multi-node scale-out layer (pkg/routing/redisrouter.go
node registry + room pinning; SURVEY.md §2.3, §5.8). Where the reference
distributes rooms across *processes* connected by Redis pub/sub, this build
distributes rooms across *chips* connected by ICI: every media-plane tensor
carries a leading `[R]` room axis, sharded with
`NamedSharding(mesh, P("rooms", ...))`. One compiled program steps all
shards; per-room work never crosses chips, so no collectives are required on
the hot path — cross-room reductions (node telemetry) are the only psum.
"""

from __future__ import annotations

import functools
from typing import Any, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from livekit_server_tpu.analysis.registry import device_entry
from livekit_server_tpu.models import plane

ROOM_AXIS = "rooms"


def make_mesh(devices: Sequence[jax.Device] | None = None, n_devices: int | None = None) -> Mesh:
    """1-D mesh over the room axis.

    Rooms are embarrassingly parallel in the data plane (the reference's
    insight too: a room lives entirely on one node — roomallocator.go), so a
    1-D mesh is the right shape; within a shard, the tracks/packets/
    subscriber axes batch onto the MXU/VPU of that chip.
    """
    if devices is None:
        devices = jax.devices()
    if n_devices is not None:
        devices = devices[:n_devices]
    return Mesh(np.asarray(devices), (ROOM_AXIS,))


def room_sharding(mesh: Mesh) -> NamedSharding:
    """Sharding for any tensor with a leading [R] room axis."""
    return NamedSharding(mesh, P(ROOM_AXIS))


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


def shard_tree(tree: Any, mesh: Mesh) -> Any:
    """device_put every leaf with its leading axis split over the mesh.

    Scalar leaves (e.g. tick_ms) are replicated.
    """
    rs = room_sharding(mesh)
    rep = replicated(mesh)

    def put(x):
        x = jnp.asarray(x)
        return jax.device_put(x, rep if x.ndim == 0 else rs)

    return jax.tree.map(put, tree)


def page_sharding(mesh: Mesh) -> NamedSharding:
    """Sharding for the PAGED plane's pooled buffers: the leading axis is
    the page-pool axis [P] instead of [R], split over the same 1-D mesh.
    Unlike rooms, pages are NOT embarrassingly parallel — the paged tick
    gathers a room's sub column across its track pages (tmembers), so the
    paged mesh path uses plain GSPMD jit (the partitioner inserts the
    cross-shard gathers) rather than the dense tick's shard_map. The
    pager's allocator keeps a room's grid contiguous (one pow2 run), so
    most tmembers gathers stay shard-local anyway."""
    return NamedSharding(mesh, P(ROOM_AXIS))


def shard_pool(tree: Any, mesh: Mesh) -> Any:
    """device_put the pooled plane state / page table with every leaf's
    leading (page or room) axis split over the mesh; scalars replicate."""
    ps = page_sharding(mesh)
    rep = replicated(mesh)

    def put(x):
        x = jnp.asarray(x)
        return jax.device_put(x, rep if x.ndim == 0 else ps)

    return jax.tree.map(put, tree)


@device_entry("mesh.sharded_tick", builder=True)
def make_sharded_tick(
    mesh: Mesh,
    audio_params: Any | None = None,
    bwe_params: Any | None = None,
    donate: bool = True,
    red_enabled: bool = True,
):
    """jit of the full media-plane tick with room-axis in/out shardings.

    Returns a function (state, inputs) -> (state, outputs); `state` is
    donated so the per-tick state update is in-place in HBM.
    """
    from livekit_server_tpu.ops import audio as audio_ops, bwe as bwe_ops

    ap = audio_params or audio_ops.AudioLevelParams()
    bp = bwe_params or bwe_ops.BWEParams()

    def tick(state, inp):
        return plane.media_plane_tick(state, inp, ap, bp, red_enabled=red_enabled)

    def pspecs(tree):
        return jax.tree.map(
            lambda x: P() if np.ndim(x) == 0 else P(ROOM_AXIS), tree
        )

    # shard_map, not bare GSPMD jit: the tick's hot kernels are Pallas
    # custom calls with a grid over the room axis, which the GSPMD
    # partitioner cannot split. shard_map traces the tick PER SHARD
    # (local room count), so the Pallas grids are shard-local by
    # construction and no collectives exist on the hot path (rooms are
    # embarrassingly parallel — roomallocator.go's one-node-per-room
    # insight, mapped to chips).
    cache: dict[str, Any] = {}

    def build(state, inp):
        """The jitted shard_map, built once from the first call's tree
        structure (arrays or abstract shapes)."""
        if "fn" not in cache:
            shapes = jax.eval_shape(lambda s, i: (s, i), state, inp)
            out_shapes = jax.eval_shape(tick, *shapes)
            smapped = jax.shard_map(
                tick, mesh=mesh, in_specs=pspecs(shapes),
                out_specs=pspecs(out_shapes), check_vma=False,
            )
            cache["fn"] = jax.jit(
                smapped, donate_argnums=(0,) if donate else ()
            )
        return cache["fn"]

    @functools.wraps(tick)
    def compiled(state, inp):
        return build(state, inp)(state, inp)

    compiled.lower = lambda state, inp: build(state, inp).lower(state, inp)
    return compiled
