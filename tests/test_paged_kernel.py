"""Ragged-aware pooled-tick kernel (ops/paged_kernel.py + the live-
extent path in models/paged.py): the freeze-the-dead invariant the live
path relies on, model-level bit-parity of the fused tick (CPU fallback
AND Pallas interpret mode) against the stock pooled tick, page-local mix
parity against ops/mix.py, the runtime acceptance gate (paged_kernel
="interpret" vs "off" through grow-on-join and a compaction move), the
grid-steps ∝ live-pages accounting, the zero-live-pages tick, and the
`plane.paged_kernel` config knob."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from livekit_server_tpu.config import ConfigError, load_config
from livekit_server_tpu.models import paged, plane
from livekit_server_tpu.ops import mix, paged_kernel
from livekit_server_tpu.runtime.ingest import PacketIn
from livekit_server_tpu.runtime.paged_runtime import PagedPlaneRuntime

PD = paged.PagedDims(rooms=4, tracks=4, pkts=4, subs=8,
                     tpage=2, spage=4, pool_pages=16)

# -- shared model-level fixture: hand-built page table -----------------------
# room 0 = one page (tp0, sp0); room 1 = the full 2x2 grid. 5 live pages,
# 11 dead, live_rows padded to the pow2 bucket of 8 with a LIVE row.


def _table_and_rows():
    P, MT = PD.pool_pages, PD.max_tpages
    pg_room = np.full(P, -1, np.int32)
    pg_tp = np.full(P, -1, np.int32)
    pg_sp = np.full(P, -1, np.int32)
    tmembers = np.full((P, MT), -1, np.int32)
    pg_room[0], pg_tp[0], pg_sp[0] = 0, 0, 0
    tmembers[0] = [0, -1]
    grid = {(0, 0): 1, (1, 0): 2, (0, 1): 3, (1, 1): 4}
    for (tp, sp), pid in grid.items():
        pg_room[pid], pg_tp[pid], pg_sp[pid] = 1, tp, sp
    for sp in range(2):
        row = [grid[(0, sp)], grid[(1, sp)]]
        for tp in range(2):
            tmembers[grid[(tp, sp)]] = row
    table = paged.PageTable(
        rooms_pages=jnp.full((PD.rooms, MT * PD.max_spages), -1, jnp.int32),
        tmembers=jnp.asarray(tmembers),
        pg_room=jnp.asarray(pg_room),
        pg_tp=jnp.asarray(pg_tp),
        pg_sp=jnp.asarray(pg_sp),
    )
    live = np.where(pg_room >= 0)[0].astype(np.int32)
    live_rows = np.concatenate(
        [live, np.repeat(live[:1], 8 - len(live))]).astype(np.int32)
    live_inv = np.zeros(P, np.int32)
    live_inv[live] = np.arange(len(live), dtype=np.int32)
    return table, live, live_rows, live_inv


def _populated_state(rng, dims=PD, live=None):
    P, TP, SP = dims.pool_pages, dims.tpage, dims.spage
    if live is None:
        _, live, _, _ = _table_and_rows()
    state = plane.init_state(dims.pooled())
    sub = np.zeros((P, TP, SP), bool)
    mut = np.zeros((P, TP, SP), bool)
    vid = np.zeros((P, TP), bool)
    svc = np.zeros((P, TP), bool)
    pub = np.zeros((P, TP), bool)
    for p in live:
        sub[p] = rng.random((TP, SP)) < 0.7
        mut[p] = rng.random((TP, SP)) < 0.1
        vid[p] = rng.random(TP) < 0.6
        svc[p] = (rng.random(TP) < 0.3) & vid[p]
        pub[p] = rng.random(TP) < 0.9
    return state._replace(
        meta=state.meta._replace(
            is_video=jnp.asarray(vid), published=jnp.asarray(pub),
            is_svc=jnp.asarray(svc)),
        ctrl=state.ctrl._replace(
            subscribed=jnp.asarray(sub), sub_muted=jnp.asarray(mut)),
    )


def _rand_inputs(rng, live, dims=PD):
    P, TP, K, SP = dims.pool_pages, dims.tpage, dims.pkts, dims.spage

    def pk(lo, hi):
        a = np.zeros((P, TP, K), np.int32)
        for p in live:
            a[p] = rng.integers(lo, hi, (TP, K))
        return a

    def pkb(prob):
        a = np.zeros((P, TP, K), bool)
        for p in live:
            a[p] = rng.random((TP, K)) < prob
        return a

    def sb(shape, lo, hi):
        a = np.zeros(shape, np.float32)
        for p in live:
            a[p] = rng.uniform(lo, hi, shape[1:])
        return a

    kw = dict(
        sn=pk(0, 65536), ts=pk(0, 1 << 30), layer=pk(0, 3),
        temporal=pk(0, 4), keyframe=pkb(0.2), layer_sync=pkb(0.3),
        begin_pic=pkb(0.4), end_frame=pkb(0.4), pid=pk(0, 100),
        tl0=pk(0, 100), keyidx=pk(0, 30), size=pk(40, 1200),
        frame_ms=pk(0, 20), audio_level=pk(0, 127),
        arrival_rtp=pk(0, 1 << 28),
        ts_jump=np.zeros((P, TP, K), np.int32), valid=pkb(0.8),
        estimate=sb((P, SP), 1e5, 5e6),
        estimate_valid=sb((P, SP), 0, 1) > 0.5,
        nacks=sb((P, SP), 0, 3),
        pub_rtt_ms=sb((P, TP), 0, 80),
        fb_delay_ms=sb((P, SP), 0, 30), fb_recv_bps=sb((P, SP), 1e5, 4e6),
        fb_valid=sb((P, SP), 0, 1) > 0.4,
        fb_enabled=sb((P, SP), 0, 1) > 0.2,
        sub_reset=np.zeros((P, SP), bool),
        pad_num=np.zeros((P, SP), np.int32),
        pad_track=np.full((P, SP), -1, np.int32),
        tick_ms=np.int32(10), roll_quality=np.int32(0),
    )
    return plane.TickInputs(**{k: jnp.asarray(v) for k, v in kw.items()})


# The two EMA'd float leaves (`prev * (1 - a) + x * a`): XLA:CPU contracts the
# mul+add into an FMA in some of these graphs and not in others, 1-2 ulp
# apart. Every other leaf, and every integer or boolean one, is exact.
ULP_LEAVES = {"track_bps": 2, "slope_ema": 2}


def _trees_equal(a, b):
    la, _ = jax.tree_util.tree_flatten_with_path(a)
    for (path, x), y in zip(la, jax.tree.leaves(b)):
        x, y = np.asarray(x), np.asarray(y)
        ulp = ULP_LEAVES.get(getattr(path[-1], "name", None))
        if ulp and x.dtype == np.float32:
            np.testing.assert_array_max_ulp(x, y, maxulp=ulp)
        elif not np.array_equal(x, y):
            return False
    return True


# -- freeze-the-dead ---------------------------------------------------------

def test_free_pages_frozen_under_stock_tick():
    """The invariant the live-extent path rests on: a FREE page's state
    is bit-identical to the init template after any number of stock
    ticks (without the freeze, pacer tokens / BWE counters / tracker
    windows advance even under zero input)."""
    rng = np.random.default_rng(3)
    table, live, _, _ = _table_and_rows()
    state = _populated_state(rng)
    tpl = plane.init_state(PD.pooled())
    step = jax.jit(lambda s, i: paged.paged_plane_tick(s, i, table))
    for t in range(3):
        state, _ = step(state, _rand_inputs(rng, live))
    dead = np.setdiff1d(np.arange(PD.pool_pages), live)
    for got, want in zip(jax.tree.leaves(state), jax.tree.leaves(tpl)):
        got, want = np.asarray(got), np.asarray(want)
        assert np.array_equal(got[dead], want[dead])


# -- model-level fused-tick parity -------------------------------------------

def test_fused_tick_bit_parity_fallback_and_interpret():
    """paged_plane_tick_fused (live-extent: kernel decide + compact
    phases + scatter + representative dead fill) is bit-identical to the
    stock full-pool tick — state AND outputs, every pool row — in both
    the gathered CPU fallback and Pallas interpret mode."""
    rng = np.random.default_rng(7)
    table, live, live_rows, live_inv = _table_and_rows()
    state = _populated_state(rng)
    stock = jax.jit(lambda s, i: paged.paged_plane_tick(s, i, table))
    fused_fb = jax.jit(lambda s, i: paged.paged_plane_tick_fused(
        s, i, table, live_rows, live_inv, use_pallas=False))
    fused_ik = jax.jit(lambda s, i: paged.paged_plane_tick_fused(
        s, i, table, live_rows, live_inv, use_pallas=False, interpret=True))
    s_stock = s_fb = s_ik = state
    for t in range(3):
        inp = _rand_inputs(rng, live)
        s_stock, o_stock = stock(s_stock, inp)
        s_fb, o_fb = fused_fb(s_fb, inp)
        s_ik, o_ik = fused_ik(s_ik, inp)
        assert _trees_equal(s_stock, s_fb) and _trees_equal(o_stock, o_fb), t
        assert _trees_equal(s_stock, s_ik) and _trees_equal(o_stock, o_ik), t


# -- page-local mix ----------------------------------------------------------

def test_mix_pages_matches_mix_tick():
    """Kernel mix (multiset kth-largest gate + weights matmul per page)
    equals ops/mix.mix_tick on the gathered live rows, including level
    TIES at the top-k boundary."""
    rng = np.random.default_rng(13)
    P, TP, SP, N = 16, 8, 4, 96
    live = np.array([1, 4, 5, 9, 10, 11, 12, 13], np.int32)
    pcm = rng.standard_normal((P, TP, N)).astype(np.float32) * 0.3
    level = rng.random((P, TP)).astype(np.float32)
    level[:, 2] = level[:, 5] = level[:, 7]     # exercise tie semantics
    active = rng.random((P, TP)) < 0.7
    sub_track = rng.integers(-1, TP, (P, SP)).astype(np.int32)
    gain = rng.uniform(0.5, 1.5, (P, TP)).astype(np.float32)
    ref = mix.mix_tick(jnp.asarray(pcm[live]), jnp.asarray(level[live]),
                       jnp.asarray(active[live]),
                       jnp.asarray(sub_track[live]), jnp.asarray(gain[live]))
    got = paged_kernel.mix_pages(pcm, level, active, sub_track, gain, live,
                                 interpret=True, use_pallas=False)
    assert np.array_equal(np.asarray(ref), np.asarray(got))


def test_decide_mix_single_pass():
    """decide_mix_pages: both output sets from ONE pallas_call — the
    mixed half must match the mix-only kernel bit-for-bit and the decide
    half must carry kernel routing (st/tr populated)."""
    rng = np.random.default_rng(17)
    P, TP, K, SP, N = 16, 8, 4, 8, 64
    live = np.array([2, 3, 7, 11], np.int32)
    pdims = plane.PlaneDims(P, TP, K, SP)
    st = plane.init_state(pdims)
    z = lambda sh, dt=np.int32: jnp.zeros(sh, dt)
    inp = plane.TickInputs(
        sn=z((P, TP, K)), ts=z((P, TP, K)), layer=z((P, TP, K)),
        temporal=z((P, TP, K)), keyframe=z((P, TP, K), bool),
        layer_sync=z((P, TP, K), bool), begin_pic=z((P, TP, K), bool),
        end_frame=z((P, TP, K), bool), pid=z((P, TP, K)),
        tl0=z((P, TP, K)), keyidx=z((P, TP, K)), size=z((P, TP, K)),
        frame_ms=z((P, TP, K)), audio_level=z((P, TP, K)),
        arrival_rtp=z((P, TP, K)), ts_jump=z((P, TP, K)),
        valid=z((P, TP, K), bool),
        estimate=z((P, SP), np.float32), estimate_valid=z((P, SP), bool),
        nacks=z((P, SP), np.float32), pub_rtt_ms=z((P, TP), np.float32),
        fb_delay_ms=z((P, SP), np.float32),
        fb_recv_bps=z((P, SP), np.float32), fb_valid=z((P, SP), bool),
        fb_enabled=z((P, SP), bool), sub_reset=z((P, SP), bool),
        pad_num=z((P, SP)), pad_track=jnp.full((P, SP), -1, jnp.int32),
        tick_ms=jnp.asarray(10, jnp.int32),
        roll_quality=jnp.asarray(0, jnp.int32),
    )
    base = st.ctrl.subscribed & ~st.ctrl.sub_muted & (
        st.meta.published & ~st.meta.pub_muted)[:, :, None]
    pcm = rng.standard_normal((P, TP, N)).astype(np.float32) * 0.3
    level = rng.random((P, TP)).astype(np.float32)
    active = rng.random((P, TP)) < 0.7
    sub_track = rng.integers(-1, TP, (P, SP)).astype(np.int32)
    gain = rng.uniform(0.5, 1.5, (P, TP)).astype(np.float32)
    only_mix = paged_kernel.mix_pages(
        pcm, level, active, sub_track, gain, live,
        interpret=True, use_pallas=False)
    dec, mixed = paged_kernel.decide_mix_pages(
        st.sel, st.meta.is_svc, st.meta.is_video, base, inp,
        pcm, level, active, sub_track, gain, live,
        wire_overhead=42, interpret=True, use_pallas=False)
    assert np.array_equal(np.asarray(only_mix), np.asarray(mixed))
    assert dec.st is not None and dec.tr is not None
    assert dec.send_bits.shape == (4, TP, K, 1)


# -- runtime acceptance gate -------------------------------------------------

ROOMS = [("a", 1, 2), ("b", 4, 8), ("c", 2, 5)]


def _setup_rooms(rt):
    handles = {}
    for name, tr, sb in ROOMS:
        s = rt.slots.alloc_room(name)
        handles[name] = s
        for i in range(tr):
            s.alloc_track(f"t{i}")
        for i in range(sb):
            s.alloc_sub(f"p{i}")
    rt.set_track(0, 0, published=True, is_video=True)
    rt.set_subscription(0, 0, 1, subscribed=True)
    rt.set_track(1, 0, published=True, is_video=True)
    rt.set_track(1, 3, published=True, is_video=False)
    for sub in range(8):
        rt.set_subscription(1, 0, sub, subscribed=True)
    rt.set_subscription(1, 3, 2, subscribed=True)
    rt.set_track(2, 1, published=True, is_video=False)
    rt.set_subscription(2, 1, 4, subscribed=True)
    return handles


def _push(rt, tick):
    for room, track, base in [(0, 0, 100), (1, 0, 500), (1, 3, 900),
                              (2, 1, 1300)]:
        for j in range(2):
            sn = base + tick * 2 + j
            rt.ingest.push(PacketIn(
                room=room, track=track, sn=sn & 0xFFFF,
                ts=(960 * (tick * 2 + j)) & 0xFFFFFFFF,
                size=120, payload=b"x" * 120,
                keyframe=(tick == 0 and j == 0),
                audio_level=-(30 + (sn % 20)),
            ))


def _capture(rt, log):
    orig = rt._unpack_outputs

    def wrapped(buf):
        out = orig(buf)
        log.append(out)
        return out

    rt._unpack_outputs = wrapped


async def test_runtime_parity_interpret_vs_stock():
    """The acceptance gate: paged_kernel="interpret" (live-extent tick,
    Pallas kernels in interpret mode) against paged_kernel="off" (stock
    jit pooled tick) on the mixed-size fixture — identical logical
    TickOutputs every tick AND identical post-run state, through a
    grow-on-join across a page boundary at tick 3 and a compaction move
    at tick 5."""
    off = PagedPlaneRuntime(PD, tick_ms=10, paged_kernel="off")
    ik = PagedPlaneRuntime(PD, tick_ms=10, paged_kernel="interpret")
    lo, li = [], []
    _capture(off, lo)
    _capture(ik, li)
    h_off = _setup_rooms(off)
    h_ik = _setup_rooms(ik)
    for t in range(8):
        for rt in (off, ik):
            _push(rt, t)
            await rt.step_once()
        assert _trees_equal(lo[-1], li[-1]), t
        if t == 3:      # grow room "a" across its spage=4 boundary
            for rt, hs in ((off, h_off), (ik, h_ik)):
                for i in range(2, 6):
                    hs["a"].alloc_sub(f"p{i}")
                rt.set_subscription(0, 0, 5, subscribed=True)
        if t == 5:      # free room "c", compact: pages of "b" relocate
            for rt in (off, ik):
                rt.slots.release_room("c")
                rt.compact()
    assert off.encode_snapshot(off.snapshot()) == \
        ik.encode_snapshot(ik.snapshot())
    assert ik.stats["paged_kernel_ticks"] == 8
    assert ik.stats["paged_kernel_steps"] > 0
    # rooms "a" (1x2 pages after the grow) and "b" (2x2): 6 mapped pages
    assert ik.recent_ticks[-1]["live_pages"] == ik.pager.pages_mapped == 6
    assert ik.recent_ticks[-1]["page_live_fraction"] == round(6 / 16, 4)
    assert "live_pages" not in off.recent_ticks[-1]
    assert off.stats["paged_kernel_ticks"] == 0


async def test_grid_steps_track_live_pages():
    """Scheduled work ∝ live pages: with one-page rooms, halving the
    room count halves the per-tick kernel grid steps at FIXED pool size
    — dead pages are never scheduled, not masked."""
    dims = paged.PagedDims(rooms=8, tracks=2, pkts=2, subs=4,
                           tpage=2, spage=4, pool_pages=8)

    async def run(n_rooms):
        rt = PagedPlaneRuntime(dims, tick_ms=10, paged_kernel="on")
        for r in range(n_rooms):
            s = rt.slots.alloc_room(f"r{r}")
            s.alloc_track("t0")
            s.alloc_sub("p0")
            rt.set_track(r, 0, published=True, is_video=False)
            rt.set_subscription(r, 0, 0, subscribed=True)
        for t in range(3):
            for r in range(n_rooms):
                rt.ingest.push(PacketIn(room=r, track=0, sn=100 + t,
                                        ts=960 * t, size=50, payload=b"a"))
            await rt.step_once()
        return rt.stats["paged_kernel_steps"], rt.stats["paged_kernel_ticks"]

    steps4, ticks4 = await run(4)
    steps2, ticks2 = await run(2)
    assert ticks4 == ticks2 == 3
    assert steps4 == 2 * steps2 > 0


async def test_zero_live_pages_tick():
    """NL == 0: no grid to schedule — the tick returns the representative
    dead page's outputs broadcast pool-wide, leaves state untouched, and
    records zero kernel steps."""
    rt = PagedPlaneRuntime(PD, tick_ms=10, paged_kernel="interpret")
    res = await rt.step_once()
    assert res.fwd_packets == 0
    assert rt.stats["paged_kernel_steps"] == 0
    assert rt.stats["paged_kernel_ticks"] == 1
    assert rt.pager_stats()["page_live_fraction"] == 0.0


# -- config knob -------------------------------------------------------------

def test_paged_kernel_config_validation():
    cfg = load_config(yaml_text="""
development: true
plane:
  pager_enabled: true
  paged_kernel: interpret
""")
    assert cfg.plane.paged_kernel == "interpret"
    with pytest.raises(ConfigError, match="paged_kernel"):
        load_config(yaml_text="development: true\nplane:\n"
                              "  pager_enabled: true\n"
                              "  paged_kernel: fast")
    # inert while the pager is off
    cfg = load_config(yaml_text="development: true\nplane:\n"
                                "  paged_kernel: fast")
    assert not cfg.plane.pager_enabled

    with pytest.raises(ValueError, match="paged_kernel"):
        PagedPlaneRuntime(PD, tick_ms=10, paged_kernel="bogus")
