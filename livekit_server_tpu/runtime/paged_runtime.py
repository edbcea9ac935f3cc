"""PagedPlaneRuntime: the tick loop re-based onto pooled HBM pages.

PlaneRuntime's host side — ctrl mirrors, munger, sequencer, ingest,
express lane, fan-out, governor — speaks LOGICAL dense [R, T, S] shapes
end to end. This subclass swaps only the device layout underneath it via
the five seam hooks (plane_runtime.py): the device state becomes ONE
pool of P `[tpage, K, spage]` pages (models/paged.py) indirected through
a device-resident page table whose host canonical copy lives in the
RoomPager (runtime/pager.py). Rooms claim page grids through
PagedSlotAllocator instead of pre-paying the dense worst case, so
rooms/chip follows the actual room-size distribution.

Upload protocol (the PR 3 dirty-row delta, extended with the page lane):
at every tick edge `_upload_ctrl` first drains the pager's PageDelta —
table-row scatter, compaction row moves, fresh/freed page re-init — and
then ships the dirtied rooms' ctrl at PAGE granularity (each dirty
room's pages gather [TP]/[TP, SP] blocks out of the logical mirrors).
Device-state invariant: a FREE page always holds pristine init state
(pages are re-initialized when freed, and a never-mapped page was
init at allocation of the pool), so free pages compute no sends and
carry no stale tenant state.

Checkpoints, row repair, and migration all serialize the LOGICAL form
(LayoutXlate translates at the boundary), which keeps snapshot bytes
identical across pool layouts and lets rooms migrate dense↔paged.

Tick variants (`paged_kernel` ctor knob / `plane.paged_kernel`): "off"
runs the stock full-pool jit tick; "auto" (TPU) / "on" / "interpret"
run the live-extent path: ONE device program a tick, named `tick` like
the stock one, built on `paged.paged_plane_tick_fused` (unpack, the
`ops/paged_kernel.py` grid-over-live-pages decide kernel, live phases
1-2, scatter, pack; state donated). The tick record carries what its
grid ran over: `live_pages` (mapped pages, unpadded) beside
`page_live_fraction`, and `stats["paged_kernel_steps"]` counts the
padded grid steps. The kernel's time is the profiler trace's to give
(the Mosaic call `paged_decide` inside `jit_tick`), not the host
clock's. `live_rows` is refreshed in `_sync_pages` under the same epoch
pinning as `_step_xlate`. Zero live pages short-circuits to a broadcast
dead-page tick. Forced "off" under a pool mesh (the sharded tick stays
stock).

Staleness discipline (graftcheck GC08): page indices are only valid
under the pager epoch they were read at. Everything here that crosses a
thread or an await uses an epoch-pinned `LayoutXlate` snapshot —
`_step_xlate` is pinned at upload time (when the device table last
matched the pager) and used by the worker thread to translate that
step's outputs/mirror; fresh page indices are re-fetched under the
state lock. Inputs staged between an epoch bump and the next upload are
bounded one tick stale: packets for pages that moved or freed land on
re-initialized (unsubscribed) pages and drop, never misroute.
"""

from __future__ import annotations

import functools
from typing import Any

import jax
import numpy as np

from livekit_server_tpu.models import paged, plane
from livekit_server_tpu.runtime.pager import RoomPager
from livekit_server_tpu.runtime.plane_runtime import (
    PlaneRuntime,
    _build_ctrl_delta,
    _build_row_write,
    _row_bucket,
    _row_buckets,
)
from livekit_server_tpu.runtime.slots import PagedSlotAllocator


@functools.lru_cache(maxsize=None)
def _build_paged_step(audio_params, bwe_params, red_enabled=True):
    """Packed-wire paged step (the pooled analog of _build_step): one
    input upload, one output fetch; state donated, table read-only."""

    def tick(state, table, pkt, fb, tf, tick_ms, roll_quality):
        inp = plane.unpack_tick_inputs(pkt, fb, tf, tick_ms, roll_quality)
        state, out = paged.paged_plane_tick(
            state, inp, table, audio_params, bwe_params,
            red_enabled=red_enabled,
        )
        return state, plane.pack_tick_outputs(out)

    return jax.jit(tick, donate_argnums=(0,))


@functools.lru_cache(maxsize=None)
def _build_live_step(audio_params, bwe_params, red_enabled, interpret):
    """The live-extent tick as one program (`paged.paged_plane_tick_fused`
    between the packed wire's unpack and pack): state donated, table and
    live-row indices read-only. Named `tick`, as the stock and the dead
    steps are: the XLA module is `jit_tick` whichever of them serves."""

    def tick(state, table, live_rows, live_inv, pkt, fb, tf, tick_ms,
             roll_quality):
        inp = plane.unpack_tick_inputs(pkt, fb, tf, tick_ms, roll_quality)
        state, out = paged.paged_plane_tick_fused(
            state, inp, table, live_rows, live_inv,
            audio_params, bwe_params, red_enabled=red_enabled,
            use_pallas=None, interpret=interpret,
        )
        return state, plane.pack_tick_outputs(out)

    return jax.jit(tick, donate_argnums=(0,))


@functools.lru_cache(maxsize=None)
def _build_dead_step(audio_params, bwe_params, red_enabled, max_tpages):
    """Zero-live-pages tick: no grid to schedule. State is untouched (the
    freeze-the-dead invariant — every free page already holds pristine
    init state) and the outputs are the representative dead page's,
    broadcast across the pool."""

    def tick(state, pkt, fb, tf, tick_ms, roll_quality):
        inp = plane.unpack_tick_inputs(pkt, fb, tf, tick_ms, roll_quality)
        P, TP, K = inp.sn.shape
        SP = inp.estimate.shape[1]
        rep = paged.dead_page_outputs(
            max_tpages, TP, K, SP, inp,
            audio_params, bwe_params, red_enabled,
        )
        out = paged.broadcast_dead_outputs(rep, P)
        return state, plane.pack_tick_outputs(out)

    # state passes through untouched, so donation is a pure alias (no
    # copy either way on CPU, but on TPU the undonated form re-
    # materializes the whole pool in fresh HBM every dead tick).
    return jax.jit(tick, donate_argnums=(0,))


@functools.lru_cache(maxsize=None)
def _build_table_delta():
    return jax.jit(paged.apply_table_delta, donate_argnums=(0,))


@functools.lru_cache(maxsize=None)
def _build_reinit():
    return jax.jit(paged.reinit_pages, donate_argnums=(0,))


@functools.lru_cache(maxsize=None)
def _build_moves():
    return jax.jit(paged.move_state_rows, donate_argnums=(0,))


def _pad_rows(to: int, *arrays):
    """Pad each array's leading axis to `to` by repeating row 0
    (duplicate scatter indices carry identical values)."""
    out = []
    for a in arrays:
        if 0 < len(a) < to:
            a = np.concatenate([a, np.repeat(a[:1], to - len(a), axis=0)])
        out.append(a)
    return out


class PagedPlaneRuntime(PlaneRuntime):
    """PlaneRuntime over the pooled paged device layout."""

    def __init__(self, dims: paged.PagedDims, *, mesh=None,
                 paged_kernel: str = "auto", **kwargs):
        if not isinstance(dims, paged.PagedDims):
            raise TypeError("PagedPlaneRuntime requires paged.PagedDims")
        self.pdims = dims
        # Live-extent tick variant (ops/paged_kernel.py): "auto" runs it
        # where the Pallas kernel actually exists (TPU), "on" forces the
        # live path everywhere (kernel on TPU, gathered-decide fallback
        # on CPU), "interpret" runs the kernel in Pallas interpret mode
        # (CPU CI parity), "off" keeps the stock full-pool tick.
        if isinstance(paged_kernel, bool):
            paged_kernel = "on" if paged_kernel else "off"
        if paged_kernel not in ("auto", "on", "off", "interpret"):
            raise ValueError(
                f"paged_kernel must be auto|on|off|interpret, "
                f"got {paged_kernel!r}"
            )
        if mesh is not None and paged_kernel != "off":
            # The fused path is single-chip: its cross-page member
            # gathers defeat GSPMD pool sharding. The sharded pooled
            # tick stays the stock one (parallel/mesh.py page_sharding).
            from livekit_server_tpu.utils.logger import Logger

            if paged_kernel != "auto":
                Logger(plane="paged").warn(
                    "paged_kernel forced off: pool-mesh sharding uses "
                    "the stock pooled tick", requested=paged_kernel,
                )
            paged_kernel = "off"
        self._pk_mode = paged_kernel
        self._pk_interpret = paged_kernel == "interpret"
        self._pk_enabled = paged_kernel in ("on", "interpret") or (
            paged_kernel == "auto" and jax.default_backend() == "tpu"
        )
        self.pager = RoomPager(
            dims.rooms, dims.tracks, dims.subs,
            tpage=dims.tpage, spage=dims.spage, pool_pages=dims.pool_pages,
        )
        # Pool-axis mesh kept separate: the base class's mesh path is the
        # shard_map'd DENSE tick; the paged tick has cross-page gathers,
        # so its mesh story is plain GSPMD jit over page-sharded leaves.
        self._pmesh = mesh
        self._xlate: paged.LayoutXlate | None = None
        self._xlate_epoch = -1
        self._lfill = None
        self._pfill = None
        P, MT = dims.pool_pages, dims.max_tpages
        # What the DEVICE table should currently hold (pager mirrors as
        # of the last page sync) — the SDC audit's comparison baseline;
        # the live pager may legitimately be ahead (queued delta).
        self._dev_tables = (
            np.full(P, -1, np.int32), np.full(P, -1, np.int32),
            np.full(P, -1, np.int32), np.full((P, MT), -1, np.int32),
        )
        self.table_repairs = 0
        # Live-row cache for the kernel grid and the live-fraction gauge:
        # derived from `_dev_tables` (the device table as of the last
        # page sync), refreshed by `_sync_pages` — same epoch pinning as
        # `_step_xlate` (GC08). `_live_rows` is the bucket-padded mapped
        # pool ids (padding repeats a LIVE row — models/paged.py needs a
        # live representative, never a dead one); `_live_inv` maps pool
        # id → compact index (dead rows 0, read only clipped+masked).
        self._live_rows = np.empty(0, np.int32)
        self._live_inv = np.zeros(P, np.int32)
        self._live_n = 0
        # Row counts the padded device programs are compiled at, all of
        # them in warm_compile: page scatters (table delta, ctrl, re-init,
        # moves) at a few coarse sizes; the table delta's room rows at the
        # size paired with its page bucket; the live extent of the ragged
        # tick at powers of two from a sixteenth of the pool up, so its
        # grid stays within 2x of the live pages wherever that matters.
        self._page_buckets = _row_buckets(P)
        room_buckets = _row_buckets(dims.rooms)
        self._table_buckets = [
            (b, _row_bucket(min(b, dims.rooms), room_buckets))
            for b in self._page_buckets
        ]
        self._live_buckets = _row_buckets(P, first=max(P // 16, 1), factor=2)
        super().__init__(dims.logical, mesh=None, **kwargs)
        # The base ctor wired a dense SlotAllocator; rooms actually claim
        # page grids, so admission/occupancy route through the pager.
        self.slots = PagedSlotAllocator(self.pager)
        self._step_xlate = self._xlate_cached()
        self.stats.update({
            "page_delta_uploads": 0, "page_rows_uploaded": 0,
            "pages_reinit": 0, "page_moves": 0,
            # Kernel grid accounting: steps == the padded live-page
            # bucket per tick — the "work ∝ live pages" probe the bench
            # and tier-1 assert against.
            "paged_kernel_ticks": 0, "paged_kernel_steps": 0,
            # The layout, for whoever reads /debug/rooms `plane` and has
            # the logical dims only (constants, not counters).
            "pager_tpage": dims.tpage, "pager_spage": dims.spage,
            "pager_pool_pages": dims.pool_pages,
        })

    # -- seam hooks -------------------------------------------------------

    def _init_device_state(self):
        import jax.numpy as jnp

        del jnp  # (import kept symmetrical with the base hook style)
        self.table = paged.init_table(self.pdims)
        self._page_template = paged.page_init_template(self.pdims)
        return plane.init_state(self.pdims.pooled())

    def _init_step(self) -> None:
        self._paged_step = _build_paged_step(self._ap, self._bp, self.red_enabled)
        self._apply_delta = _build_ctrl_delta()
        self._row_write = _build_row_write()
        self._table_delta = _build_table_delta()
        self._reinit = _build_reinit()
        self._move = _build_moves()
        if self._pmesh is not None:
            from livekit_server_tpu.parallel.mesh import shard_pool

            self.state = shard_pool(self.state, self._pmesh)
            self.table = shard_pool(self.table, self._pmesh)

        def step(state, *packed):
            # Reads self.table at call time: the upload that precedes
            # each dispatch leaves the device table at the pinned epoch.
            return self._paged_step(state, self.table, *packed)

        self._step = step
        if self._pk_enabled:
            self._live_tick = _build_live_step(
                self._ap, self._bp, self.red_enabled, self._pk_interpret
            )
            self._dead_step = _build_dead_step(
                self._ap, self._bp, self.red_enabled, self.pdims.max_tpages
            )
            self._step = self._live_step

    def _live_step(self, state, *packed):
        """Live-extent device step: one dispatch. Live rows read at call
        time: `_sync_pages` at the preceding upload edge pinned them with
        the device table."""
        lr = self._live_rows
        if lr.shape[0] == 0:
            return self._dead_step(state, *packed)
        return self._live_tick(state, self.table, lr, self._live_inv, *packed)

    def _pack_inputs(self, inp: plane.TickInputs) -> tuple:
        pkt, fb, tf, tick_ms, roll = plane.pack_tick_inputs(inp)
        pkt_p, fb_p, tf_p = self._xlate_cached().stage_inputs(
            np.asarray(pkt), np.asarray(fb), np.asarray(tf)
        )
        return (pkt_p, fb_p, tf_p, tick_ms, roll)

    def _unpack_outputs(self, buf) -> plane.TickOutputs:
        out = plane.unpack_tick_outputs(
            np.asarray(buf), self.pdims.pooled(), self.red_enabled
        )
        # _step_xlate, not _xlate_cached(): the event loop may have
        # alloc'd/freed pages while this step ran on the worker thread —
        # the outputs belong to the table the step actually saw (GC08).
        return self._step_xlate.outputs_to_logical(out)

    def _sel_mirror(self, state) -> tuple:
        sel_np = jax.tree.map(np.asarray, state.sel)
        sel_lg = self._step_xlate.sel_to_logical(sel_np, self._logical_fill().sel)
        return (
            sel_lg.current_spatial, sel_lg.current_temporal,
            sel_lg.target_spatial, sel_lg.target_temporal,
        )

    # -- layout translation caches ---------------------------------------

    def _xlate_cached(self) -> paged.LayoutXlate:
        """The translation snapshot for the CURRENT pager epoch. The
        index arrays are copied, so a cached instance stays valid as a
        point-in-time snapshot after further pager churn."""
        if self._xlate is None or self._xlate_epoch != self.pager.epoch:
            self._xlate = paged.LayoutXlate(
                self.pdims,
                self.pager.pg_room.copy(),
                self.pager.pg_tp.copy(),
                self.pager.pg_sp.copy(),
            )
            self._xlate_epoch = self.pager.epoch
        return self._xlate

    def _logical_fill(self):
        """Logical-dense init-state template (numpy, broadcast views):
        the fill for unmapped regions in pooled→logical translation and
        the shape/dtype spec for snapshot validation."""
        if self._lfill is None:
            d = self.dims
            tpl = plane.init_state(plane.PlaneDims(1, d.tracks, d.pkts, d.subs))
            self._lfill = jax.tree.map(
                lambda a: np.broadcast_to(
                    np.asarray(a), (d.rooms,) + a.shape[1:]
                ),
                tpl,
            )
        return self._lfill

    def _pooled_fill(self):
        if self._pfill is None:
            P = self.pdims.pool_pages
            tpl = jax.tree.map(np.asarray, self._page_template)
            self._pfill = jax.tree.map(
                lambda a: np.broadcast_to(a, (P,) + a.shape[1:]), tpl
            )
        return self._pfill

    # -- page-table delta lane --------------------------------------------

    def _sync_pages(self) -> None:
        """Drain the pager's pending page events into the device: table
        rows, compaction row moves, then fresh/freed page re-init (moves
        must land before the re-init wipes their sources). Re-pins
        `_step_xlate` — after this, device table == pager mirrors."""
        import jax.numpy as jnp

        delta = self.pager.drain_delta()
        if not delta.empty:
            (page_rows, tm, pgr, pgt, pgs, room_rows, rps) = (
                paged.pack_table_delta(self.pager, delta)
            )
            self._apply_table_rows(page_rows, tm, pgr, pgt, pgs, room_rows, rps)
            if len(delta.moves):
                src, dst = delta.moves[:, 0], delta.moves[:, 1]
                src, dst = _pad_rows(
                    _row_bucket(len(src), self._page_buckets), src, dst
                )
                self.state = self._move(
                    self.state, jnp.asarray(src), jnp.asarray(dst)
                )
                self.stats["page_moves"] += len(delta.moves)
            reinit = np.concatenate([delta.fresh_pages, delta.freed_pages])
            if len(reinit):
                (reinit,) = _pad_rows(
                    _row_bucket(len(reinit), self._page_buckets),
                    reinit.astype(np.int32),
                )
                self.state = self._reinit(
                    self.state, jnp.asarray(reinit), self._page_template
                )
                self.stats["pages_reinit"] += len(reinit)
            # Rooms whose grid changed must re-assert ctrl onto their
            # (possibly fresh/relocated) pages at this same edge.
            self._dirty_rows.update(int(r) for r in delta.rooms)
            self._dev_tables = (
                self.pager.pg_room.copy(), self.pager.pg_tp.copy(),
                self.pager.pg_sp.copy(), self.pager.tmembers.copy(),
            )
            if self.integrity is not None:
                # Page identity changed under the audit mirror's feet;
                # re-baseline instead of flagging relocated cursors.
                self.integrity.on_layout_change()
            self.stats["page_delta_uploads"] += 1
            self.stats["page_rows_uploaded"] += len(page_rows)
            self._refresh_live_rows()
        self._step_xlate = self._xlate_cached()

    def _apply_table_rows(self, page_rows, tm, pgr, pgt, pgs,
                          room_rows, rps) -> None:
        """Scatter table rows into the device table, padded to the first
        (pages, rooms) bucket pair that holds both. A room index past the
        table is dropped by the scatter: that is the padding where no room
        row changed."""
        pb, rb = next(
            (p, r) for p, r in self._table_buckets
            if len(page_rows) <= p and len(room_rows) <= r
        )
        if not len(room_rows):
            room_rows = np.full(1, self.pdims.rooms, np.int32)
            rps = np.zeros((1,) + self.pager.rooms_pages.shape[1:], np.int32)
        page_rows, tm, pgr, pgt, pgs = _pad_rows(pb, page_rows, tm, pgr, pgt, pgs)
        room_rows, rps = _pad_rows(rb, room_rows, rps)
        self.table = self._table_delta(
            self.table, page_rows, tm, pgr, pgt, pgs, room_rows, rps
        )

    def _refresh_live_rows(self) -> None:
        """Rebuild the live-row cache from the device-table mirror (see
        __init__). Called whenever `_dev_tables` changes; the bucket
        keeps the kernel grid compiling once per size class."""
        pg_room = self._dev_tables[0]
        rows = np.nonzero(pg_room >= 0)[0].astype(np.int32)
        inv = np.zeros(len(pg_room), np.int32)
        inv[rows] = np.arange(len(rows), dtype=np.int32)
        self._live_n = len(rows)
        if len(rows):
            (rows,) = _pad_rows(_row_bucket(len(rows), self._live_buckets), rows)
        self._live_rows = rows
        self._live_inv = inv

    def _upload_ctrl(self) -> None:
        """Page lane first (table delta / moves / re-init), then the
        dirty rooms' ctrl shipped at PAGE granularity: each page row is a
        [TP] / [TP, SP] block gathered from the logical host mirrors, so
        the pooled apply_ctrl_delta scatter is unchanged — page ids are
        just its row indices."""
        self._sync_pages()
        rows = self._dirty_rows
        if not self._ctrl_dirty and not rows:
            return
        if self._ctrl_dirty or len(rows) > self.ctrl_delta_max_rows:
            page_rows = np.nonzero(self.pager.pg_room >= 0)[0].astype(np.int32)
            self.stats["ctrl_full_uploads"] += 1
        else:
            parts = [self.pager.pages_of_room(int(r)) for r in sorted(rows)]
            page_rows = (
                np.concatenate(parts).astype(np.int32)
                if parts else np.empty(0, np.int32)
            )
            self.stats["ctrl_delta_uploads"] += 1
            self.stats["ctrl_delta_rows"] += len(rows)
        if len(page_rows):
            pr, meta_rows, ctrl_rows = self._pack_ctrl_pages(
                self.meta, self._effective_ctrl(), page_rows,
                pad_to=_row_bucket(len(page_rows), self._page_buckets),
            )
            self.state = self._apply_delta(self.state, pr, meta_rows, ctrl_rows)
            self.stats["ctrl_upload_bytes"] += meta_rows.nbytes + ctrl_rows.nbytes
        self._dirty_rows = set()
        self._ctrl_dirty = False

    def _pack_ctrl_pages(self, meta, ctrl, page_rows, pad_to=None):
        """pack_ctrl_rows at page granularity: gather each mapped page's
        [TP] meta / [TP, SP] ctrl block out of the logical mirrors."""
        d = self.pdims
        pr = np.sort(np.asarray(page_rows, np.int32))
        if pad_to is not None and len(pr) < pad_to:
            pr = np.concatenate([pr, np.repeat(pr[:1], pad_to - len(pr))])
        rooms = self.pager.pg_room[pr]
        tps = self.pager.pg_tp[pr]
        sps = self.pager.pg_sp[pr]
        meta_rows = np.stack([
            np.asarray(m)
            .reshape(d.rooms, d.max_tpages, d.tpage)[rooms, tps]
            .astype(np.int32)
            for m in meta
        ])
        ctrl_rows = np.stack([
            np.asarray(c)
            .reshape(d.rooms, d.max_tpages, d.tpage, d.max_spages, d.spage)
            [rooms, tps, :, sps]
            .astype(np.int32)
            for c in ctrl
        ])
        return pr, meta_rows, ctrl_rows

    def warm_compile(self) -> None:
        """The paged form of the base warm-up. Every device program here
        is compiled once per bucket of its row count (`__init__` has the
        lists): first use of a bucket in steady state would stall the
        tick for the compile, and the ingest ring overflows meanwhile.
        So run each at every bucket now, writing back the values already
        there (page 0 and room 0 of an empty pool: the scatters are
        no-ops), and the live-extent tick on a scratch copy of the
        state. Callers hold state_lock."""
        import jax.numpy as jnp

        self._sync_pages()
        self._logical_fill()
        self._pooled_fill()
        d, pg = self.pdims, self.pager
        P = d.pool_pages
        page0 = self._warm_ctrl_delta(self._page_buckets)
        for n in self._page_buckets:
            rows = jnp.zeros(n, jnp.int32)
            self.state = self._reinit(self.state, rows, self._page_template)
            self.state = self._move(self.state, rows, rows)
        zero = np.zeros(1, np.int32)
        for n, _ in self._table_buckets:
            prow = np.zeros(n, np.int32)
            self._apply_table_rows(
                prow, pg.tmembers[prow], pg.pg_room[prow], pg.pg_tp[prow],
                pg.pg_sp[prow], zero, pg.rooms_pages[zero],
            )
        # the page-row write of restore_room / repair_room_row: one
        # program, a room's whole page grid
        n = d.max_tpages * d.max_spages
        self.state = self._row_write(
            self.state, np.zeros(n, np.int32),
            jax.tree.map(lambda a: np.repeat(a[None], n, axis=0), page0),
        )
        if self._pk_enabled:
            pool = d.pooled()
            packed = (
                np.zeros((len(plane.PKT_FIELDS), P, pool.tracks, pool.pkts),
                         np.int32),
                np.zeros((8, P, pool.subs), np.float32),
                np.zeros((1, P, pool.tracks), np.float32),
                np.int32(self.tick_ms), np.int32(0),
            )
            keep = (self._live_rows, self._live_inv)
            self._live_inv = np.zeros(P, np.int32)
            for n in self._live_buckets:
                self._live_rows = np.zeros(n, np.int32)
                scratch = jax.tree.map(jnp.copy, self.state)
                jax.block_until_ready(self._live_step(scratch, *packed))
            self._live_rows, self._live_inv = keep

    # -- kernel grid accounting --------------------------------------------

    def _device_step(self, st):
        """Stamp what this step's grid runs over onto the StagedTick
        before it crosses back to the event loop: the live rows are
        pinned while state_lock is held, which this call's caller does;
        by `_complete` a later upload may have moved them."""
        if self._pk_enabled:
            st.live_pages = self._live_n
        return super()._device_step(st)

    def _tick_rec_extras(self, st) -> dict:
        """recent_ticks extras + the per-tick stats fold (runs exactly
        once per completed tick, on the event loop)."""
        if not self._pk_enabled:
            return {}
        self.stats["paged_kernel_ticks"] += 1
        if st.live_pages:
            # the grid is the live pages padded to their bucket
            self.stats["paged_kernel_steps"] += _row_bucket(
                st.live_pages, self._live_buckets
            )
        return {
            "live_pages": st.live_pages,
            "page_live_fraction": round(
                st.live_pages / self.pdims.pool_pages, 4
            ),
        }

    # -- integrity plane ---------------------------------------------------

    def map_audit_mask(self, mask: np.ndarray) -> np.ndarray:
        """[P] per-page audit mask → [R] per-room mask, plus the page-
        table SDC check: the device table is delta-maintained from the
        pager's canonical mirrors, so any divergence from the last-sync
        snapshot is corruption — repair the table rows from the host
        canonical immediately and flag the touched rooms (their state
        computed through a corrupt indirection, so it is suspect too).
        Runs on the worker thread with state_lock held (via maybe_audit)."""
        from livekit_server_tpu.runtime import integrity

        room_mask = self._step_xlate.page_mask_to_rooms(mask).astype(np.int32)
        bad_rooms = self._audit_page_table()
        if bad_rooms is not None:
            room_mask[bad_rooms] |= np.int32(integrity.BIT_TABLE)
        return room_mask

    def _audit_page_table(self):
        mr, mt, ms, mtm = self._dev_tables
        dr = np.asarray(self.table.pg_room)
        dt = np.asarray(self.table.pg_tp)
        ds = np.asarray(self.table.pg_sp)
        dtm = np.asarray(self.table.tmembers)
        bad = (dr != mr) | (dt != mt) | (ds != ms) | (dtm != mtm).any(axis=1)
        if not bad.any():
            return None
        rows = np.nonzero(bad)[0].astype(np.int32)
        # Host canonical is authoritative: re-scatter the diverged rows.
        self._apply_table_rows(
            rows, mtm[rows], mr[rows], mt[rows], ms[rows],
            np.empty(0, np.int32), None,
        )
        self.table_repairs += len(rows)
        R = self.dims.rooms
        bad_rooms = np.zeros(R, bool)
        for owner in (mr[bad], dr[bad]):  # true owner + phantom pointee
            valid = (owner >= 0) & (owner < R)
            bad_rooms[owner[valid]] = True
        return bad_rooms

    # -- checkpoint / repair / migration (LOGICAL wire form) ---------------

    def _to_logical_state(self):
        """Device pooled state → logical PlaneState (numpy). Flushes the
        page lane first so the translation epoch matches the device
        table. Callers hold state_lock."""
        self._sync_pages()
        pooled_np = jax.tree.map(np.asarray, self.state)
        return self._xlate_cached().state_to_logical(
            pooled_np, self._logical_fill()
        )

    def _write_logical_row(self, row: int, leaves: list) -> None:
        """Scatter one LOGICAL room row into every page of the room's
        grid (re-establishing the duplicate-everywhere invariant). Page
        ids are fetched fresh under the lock after a page-lane flush —
        never held across an await (GC08)."""
        self._sync_pages()
        pages = self.pager.pages_of_room(row)
        if len(pages) == 0:
            return
        d = self.pdims
        tps = self.pager.pg_tp[pages].astype(np.int64)
        sps = self.pager.pg_sp[pages].astype(np.int64)
        _, sdef = jax.tree.flatten(self.state)
        row_tree = jax.tree.unflatten(sdef, leaves)
        kinds = paged._kind_tree(row_tree)

        def rowfun(kind, lrow, pooled_leaf):
            a = np.ascontiguousarray(np.asarray(lrow))
            if kind == paged._K_TRACK:
                w = a.size // d.tracks
                v = a.reshape(d.max_tpages, d.tpage, w)[tps]
            elif kind == paged._K_SUB:
                w = a.size // d.subs
                v = a.reshape(d.max_spages, d.spage, w)[sps]
            else:
                w = a.size // (d.tracks * d.subs)
                v = a.reshape(
                    d.max_tpages, d.tpage, d.max_spages, d.spage, w
                )[tps, :, sps]
            return v.reshape((len(pages),) + pooled_leaf.shape[1:])

        rows_tree = jax.tree.map(
            lambda kind, lrow, leaf: rowfun(kind, lrow, leaf).astype(leaf.dtype),
            kinds, row_tree, self.state,
        )
        # Padded to a room's whole grid (repeating the first page): one
        # program for every room size, compiled in warm_compile.
        n = d.max_tpages * d.max_spages
        pages, *leaves = _pad_rows(
            n, np.asarray(pages, np.int32), *jax.tree.leaves(rows_tree)
        )
        self.state = self._row_write(
            self.state, pages, jax.tree.unflatten(sdef, leaves)
        )

    def snapshot(self) -> dict[str, Any]:
        logical = self._to_logical_state()
        flat, _ = jax.tree.flatten(logical)
        return {
            "tick_index": self.tick_index,
            "arrays": [np.asarray(a) for a in flat],
            "munger": self.munger.snapshot(),
        }

    def snapshot_room(self, row: int) -> dict[str, Any]:
        logical = self._to_logical_state()
        flat, treedef = jax.tree.flatten(logical)
        arrays = [np.array(a[row]) for a in flat]
        tree = jax.tree.unflatten(treedef, arrays)
        tree = tree._replace(
            meta=plane.TrackMeta(*[np.array(m[row]) for m in self.meta]),
            ctrl=plane.SubControl(*[np.array(c[row]) for c in self.ctrl]),
        )
        return {
            "arrays": jax.tree.flatten(tree)[0]
            + self.munger.snapshot_room(row)
        }

    def repair_room_row(self, row: int, snap: dict[str, Any]) -> None:
        lflat, _ = jax.tree.flatten(self._logical_fill())
        self._check_row_leaves(lflat, snap["arrays"])
        dev_arrays = snap["arrays"][: len(lflat)]
        self.munger.restore_room(row, snap["arrays"][len(lflat):])
        self._write_logical_row(row, dev_arrays)
        # Same post-repair hygiene as the dense path: the replay ring
        # references pre-repair SN spaces; host mirrors stay
        # authoritative and re-assert at the next edge.
        self.host_seq.clear_room(row)
        self._dirty_rows.add(row)

    def restore_room(self, row: int, snap: dict[str, Any]) -> None:
        self.host_seq.clear_room(row)
        lflat, ldef = jax.tree.flatten(self._logical_fill())
        self._check_row_leaves(lflat, snap["arrays"])
        dev_arrays = snap["arrays"][: len(lflat)]
        snap_tree = jax.tree.unflatten(
            ldef, [np.asarray(a) for a in dev_arrays]
        )
        # The incoming room's live tracks may exceed this row's current
        # page extent (the adopter allocated minimally): grow the grid to
        # cover every published track column BEFORE writing the row, so
        # migrated publisher state lands instead of truncating.
        pub = np.asarray(snap_tree.meta.published)
        live = np.nonzero(pub)[0]
        need_t = int(live[-1]) + 1 if len(live) else 1
        if len(self.pager.pages_of_room(row)) == 0:
            self.pager.alloc_room(row, tracks=need_t)
        else:
            self.pager.grow_room(row, tracks=need_t)
        self.munger.restore_room(row, snap["arrays"][len(lflat):])
        self._write_logical_row(row, dev_arrays)
        for host_arr, snap_arr in zip(self.meta, snap_tree.meta):
            host_arr[row] = snap_arr
        # Subscription masks are not carried (see the dense docstring):
        # destination sub columns are allocated fresh.
        self.ctrl.subscribed[row] = False
        self.ctrl.sub_muted[row] = False
        self.ctrl.max_spatial[row] = plane.MAX_LAYERS - 1
        self.ctrl.max_temporal[row] = 3
        self._dirty_rows.add(row)
        if self.integrity is not None:
            self.integrity.on_row_restore(row)

    def restore(self, snap: dict[str, Any]) -> None:
        import jax.numpy as jnp

        from livekit_server_tpu.runtime.munge import HostMunger

        self._sync_pages()
        lflat, ldef = jax.tree.flatten(self._logical_fill())
        arrays = snap.get("arrays")
        if arrays is None or len(arrays) != len(lflat):
            raise ValueError(
                f"full snapshot has {0 if arrays is None else len(arrays)} "
                f"leaves, plane has {len(lflat)} — snapshot/plane versions "
                "differ"
            )
        for i, (leaf, a) in enumerate(zip(lflat, arrays)):
            a = np.asarray(a)
            if tuple(a.shape) != tuple(leaf.shape):
                raise ValueError(
                    f"full snapshot leaf {i} shape {tuple(a.shape)} != "
                    f"plane shape {tuple(leaf.shape)} — dims mismatch"
                )
            if not np.can_cast(a.dtype, np.dtype(leaf.dtype), casting="same_kind"):
                raise ValueError(
                    f"full snapshot leaf {i} dtype {a.dtype} incompatible "
                    f"with plane dtype {np.dtype(leaf.dtype)}"
                )
        logical = jax.tree.unflatten(ldef, [np.asarray(a) for a in arrays])
        # Rooms live in THIS node's pager keep their state; logical rows
        # without pages (not resident here) drop — the checkpoint stays
        # layout-independent, placement is the restoring node's business.
        pooled = self._xlate_cached().state_to_pooled(
            logical, self._pooled_fill()
        )
        pflat, pdef = jax.tree.flatten(pooled)
        self.state = jax.tree.unflatten(pdef, [jnp.asarray(a) for a in pflat])
        if self._pmesh is not None:
            from livekit_server_tpu.parallel.mesh import shard_pool

            self.state = shard_pool(self.state, self._pmesh)
        if "munger" in snap:
            self.munger.restore(snap["munger"])
        else:
            self.munger = HostMunger(self.dims)
        self.tick_index = snap["tick_index"]
        self._ctrl_dirty = True
        if self.integrity is not None:
            self.integrity.on_full_restore()

    # -- admin -------------------------------------------------------------

    def compact(self) -> int:
        """Defragment the page pool (host side now; the device moves +
        table delta replay at the next tick-edge sync). Returns the
        number of device row moves queued."""
        return len(self.pager.compact())

    def pager_stats(self) -> dict:
        st = self.pager.stats()
        st["table_repairs"] = self.table_repairs
        st["paged_kernel"] = self._pk_mode if self._pk_enabled else "off"
        st["page_live_fraction"] = round(
            self._live_n / self.pdims.pool_pages, 4
        )
        return st
