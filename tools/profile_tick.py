"""Per-block attribution of the media-plane tick at a given shape.

Times each sub-block of `_room_tick` standalone (vmapped over rooms, jitted,
donated where possible), a chained loop ending in block_until_ready. Run:

    python tools/profile_tick.py --shape cfg4
    python tools/profile_tick.py --shape northstar
"""

from __future__ import annotations

import argparse
import functools
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

from livekit_server_tpu.models import plane, synth
from livekit_server_tpu.ops import (
    allocation,
    audio,
    bwe,
    pacer,
    red,
    rtpmunger,
    rtpstats,
    selector,
    streamtracker,
    vp8,
)

SHAPES = {
    "cfg4": (
        plane.PlaneDims(1024, 10, 8, 10),
        synth.TrafficSpec(video_tracks=2, audio_tracks=8, tick_ms=20,
                          video_kbps=1500, svc=True),
    ),
    "northstar": (
        plane.PlaneDims(10240, 8, 16, 50),
        synth.TrafficSpec(video_tracks=2, audio_tracks=6, tick_ms=20,
                          video_kbps=1500, svc=True),
    ),
    "default": (
        plane.PlaneDims(128, 8, 16, 16),
        synth.TrafficSpec(video_tracks=4, audio_tracks=4, tick_ms=20,
                          video_kbps=3000),
    ),
}


def timeit(fn, args, n=8, label=""):
    """Mean over 3n chained calls after one warm call, ending in
    block_until_ready."""
    jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    o = None
    for _ in range(3 * n):
        o = fn(*args)
    jax.block_until_ready(o)
    ms = (time.perf_counter() - t0) / (3 * n) * 1000.0
    print(f"{label:42s} {ms:9.3f} ms")
    return ms


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--shape", default="cfg4", choices=list(SHAPES))
    ap.add_argument("--n", type=int, default=8)
    args = ap.parse_args()

    from livekit_server_tpu.utils.compile_cache import setup_compile_cache
    setup_compile_cache()

    dims, spec = SHAPES[args.shape]
    R, T, K, S = dims
    L = plane.MAX_LAYERS
    n = args.n

    state = synth.make_state(dims, spec)
    traffic = synth.init_traffic(dims, spec)
    traffic, inp = synth.next_tick(traffic, dims, spec, tick_index=7)
    inp = jax.tree.map(jnp.asarray, inp)
    print(f"shape={args.shape} dims={dims}")

    # ---- full tick (the reference number) --------------------------------
    pkt, fb, tf, tick_ms, roll = plane.pack_tick_inputs(inp)

    @functools.partial(jax.jit, donate_argnums=(0,))
    def full(state, pkt, fb, tf, tick_ms, roll):
        i = plane.unpack_tick_inputs(pkt, fb, tf, tick_ms, roll)
        state, out = plane.media_plane_tick(state, i)
        return state, plane.pack_tick_outputs(out).astype(jnp.int64).sum()

    st = state
    def full_call(pkt, fb, tf):
        nonlocal st
        st, chk = full(st, pkt, fb, tf, tick_ms, roll)
        return chk
    timeit(full_call, (pkt, fb, tf), n, "FULL tick (packed, donated)")

    state = synth.make_state(dims, spec)

    # ---- 1. rtpstats -----------------------------------------------------
    eff_layer = jnp.where(state.meta.is_svc[..., None],
                          0, jnp.clip(inp.layer, 0, L - 1))

    @jax.jit
    def stats_block(stats, sn, ts, size, arr, valid, eff_layer):
        lanes = jnp.arange(L, dtype=jnp.int32)[None, None, None, :]
        def to_streams(x, fill):
            routed = jnp.where(eff_layer[..., None] == lanes, x[..., None],
                               jnp.asarray(fill, x.dtype))
            return routed.transpose(0, 1, 3, 2).reshape(R, T * L, K)
        out = jax.vmap(rtpstats.update_tick)(
            stats, to_streams(sn, 0), to_streams(ts, 0),
            to_streams(size, 0), to_streams(arr, 0),
            to_streams(valid, False))
        return out
    timeit(lambda *a: stats_block(*a),
           (state.stats, inp.sn, inp.ts, inp.size, inp.arrival_rtp,
            inp.valid, eff_layer), n, "1. rtpstats.update_tick (+routing)")

    # ---- 2. streamtracker ------------------------------------------------
    @jax.jit
    def tracker_block(tracker, layer, valid, size, begin_pic, tick_ms):
        true_layer = jnp.clip(layer, 0, L - 1)
        lanes = jnp.arange(L, dtype=jnp.int32)[None, None, None, :]
        t_lane = true_layer[..., None] == lanes
        def to_tracker(x, pred):
            routed = jnp.where(t_lane & pred[..., None], x[..., None], 0)
            return jnp.sum(routed, axis=2).reshape(R, T * L)
        ones_k = jnp.ones((R, T, K), jnp.int32)
        st_pkts = to_tracker(ones_k, valid)
        st_bytes = to_tracker(size, valid)
        st_frames = to_tracker(ones_k, valid & begin_pic)
        return jax.vmap(
            lambda tr, p, b, f: streamtracker.update_tick(
                tr, streamtracker.TrackerParams(), p, b, tick_ms, frames=f)
        )(tracker, st_pkts, st_bytes, st_frames)
    timeit(lambda *a: tracker_block(*a),
           (state.tracker, inp.layer, inp.valid, inp.size, inp.begin_pic,
            inp.tick_ms), n, "2. streamtracker (+routing)")

    # ---- 3. fused forward-decision kernel (production phase 0) -----------
    base_m = (np.asarray(state.ctrl.subscribed)
              & ~np.asarray(state.ctrl.sub_muted)
              & (np.asarray(state.meta.published)
                 & ~np.asarray(state.meta.pub_muted))[:, :, None])

    @jax.jit
    def sel_block(sel, is_svc, is_video, base, layer, temporal, kf, sync, eof,
                  valid, size):
        return selector.decide_rooms(
            sel, is_svc, is_video, base, layer, temporal, kf, sync, eof,
            valid, size, wire_overhead=pacer.WIRE_OVERHEAD_BYTES)
    timeit(lambda *a: sel_block(*a),
           (state.sel, state.meta.is_svc, state.meta.is_video,
            jnp.asarray(base_m), inp.layer, inp.temporal,
            inp.keyframe, inp.layer_sync, inp.end_frame, inp.valid, inp.size),
           n, "3. selector.decide_rooms (fused kernel)")

    # ---- 4. munger + vp8 -------------------------------------------------
    fwd = jnp.ones((R, T, K, S), bool)
    drop = jnp.zeros((R, T, K, S), bool)
    switch = jnp.zeros((R, T, K, S), bool)

    tile_ts = lambda tree: jax.tree.map(  # noqa: E731
        lambda x: jnp.broadcast_to(x, (R, T) + x.shape).copy(), tree)
    munger_st = tile_ts(rtpmunger.init_state(S))
    vp8_st = tile_ts(vp8.init_state(S))

    @jax.jit
    def munger_block(munger, sn, ts, valid, fwd, drop, switch, ts_jump):
        return jax.vmap(jax.vmap(rtpmunger.munge_tick))(
            munger, sn, ts, valid, fwd, drop, switch, ts_jump)
    timeit(lambda *a: munger_block(*a),
           (munger_st, inp.sn, inp.ts, inp.valid, fwd, drop, switch,
            inp.ts_jump), n, "4. rtpmunger.munge_tick (retired from tick)")

    @jax.jit
    def vp8_block(vst, pid, tl0, keyidx, begin, valid, fwd, drop, switch):
        return jax.vmap(jax.vmap(vp8.munge_tick))(
            vst, pid, tl0, keyidx, begin, valid, fwd, drop, switch)
    timeit(lambda *a: vp8_block(*a),
           (vp8_st, inp.pid, inp.tl0, inp.keyidx, inp.begin_pic,
            inp.valid, fwd, drop, switch), n, "5. vp8.munge_tick (retired from tick)")

    # ---- 6. allocation (pallas, vmapped) ---------------------------------
    bitrates = jnp.ones((R, T, 4, 4), jnp.float32) * 1e5
    budget = jnp.ones((R, S), jnp.float32) * 5e6

    @jax.jit
    def alloc_block(bitrates, ms, mt, muted, budget):
        return allocation.allocate_budget_rooms(bitrates, ms, mt, muted, budget)
    timeit(lambda *a: alloc_block(*a),
           (bitrates, state.ctrl.max_spatial.transpose(0, 2, 1),
            state.ctrl.max_temporal.transpose(0, 2, 1),
            jnp.zeros((R, S, T), bool), budget),
           n, "6. allocation.allocate_budget_batch")

    # ---- 7. bwe + pacer --------------------------------------------------
    @jax.jit
    def bwe_block(bst, dst, pst, est, estv, nacks, fbd, fbr, fbv, fbe, tick_ms):
        pkts = jnp.ones((R, S), jnp.float32)
        b2, cong, trend, budget = jax.vmap(
            lambda a, b, c, d, e: bwe.update_tick(
                a, bwe.BWEParams(), b, c, d, e)
        )(bst, est, estv, pkts, nacks)
        d2, rate, over, act = jax.vmap(
            lambda a, b, c, d, e, f: bwe.delay_update_tick(
                a, bwe.DelayBWEParams(), b, c, d, e, f, tick_ms)
        )(dst, fbd, fbr, fbv, fbe, pkts)
        p2, allowed, backlog = jax.vmap(
            lambda a, b, c: pacer.update_tick(
                a, pacer.PacerParams(), b, c, tick_ms)
        )(pst, pkts * 100, budget)
        return b2, d2, p2, cong, budget, allowed
    timeit(lambda *a: bwe_block(*a),
           (state.bwe_state, state.delay_bwe, state.pacer_state,
            inp.estimate, inp.estimate_valid, inp.nacks, inp.fb_delay_ms,
            inp.fb_recv_bps, inp.fb_valid, inp.fb_enabled, inp.tick_ms),
           n, "7. bwe+delay+pacer")

    # ---- 8. RED plan -----------------------------------------------------
    @jax.jit
    def red_block(rst, sn, ts, size, audio_valid):
        return jax.vmap(red.encode_plan_tick)(rst, sn, ts, size, audio_valid)
    timeit(lambda *a: red_block(*a),
           (state.red_state, inp.sn, inp.ts, inp.size,
            inp.valid & ~state.meta.is_video[..., None]),
           n, "8. red.encode_plan_tick")

    # ---- 9. audio --------------------------------------------------------
    @jax.jit
    def audio_block(ast, level, frame_ms, valid, tick_ms):
        a2, linear, act = jax.vmap(
            lambda a, b, c, d: audio.observe_tick(
                a, audio.AudioLevelParams(), b, c, d, tick_ms)
        )(ast, level, frame_ms, valid)
        lv, tr = jax.vmap(lambda lin, a: audio.top_speakers(
            jnp.where(a, lin, 0.0), min(plane.SPEAKER_TOP_K, T)))(linear, act)
        return a2, lv, tr
    timeit(lambda *a: audio_block(*a),
           (state.audio_state, inp.audio_level, inp.frame_ms,
            inp.valid & ~state.meta.is_video[..., None], inp.tick_ms),
           n, "9. audio levels + top-k")

    # ---- 10. egress compaction (RETIRED from the tick: these two blocks
    # measure the r1-r4 device-side compaction designs for the record) ----
    send = fwd & (jnp.arange(S)[None, None, None, :] < 4)
    cap = min(T * K * S, max(128, T * K * 4))

    @jax.jit
    def compact_block(send, sn, ts):
        flat = send.reshape(R, -1)
        def one(fs, osn, ots):
            (idx,) = jnp.nonzero(fs, size=cap, fill_value=-1)
            safe = jnp.maximum(idx, 0)
            hit = idx >= 0
            return (idx.astype(jnp.int32),
                    jnp.where(hit, osn.reshape(-1)[safe], 0),
                    jnp.where(hit, ots.reshape(-1)[safe], 0))
        osn = jnp.broadcast_to(sn[..., None], (R, T, K, S))
        return jax.vmap(one)(flat, osn, jnp.broadcast_to(ts[..., None], (R, T, K, S)))
    timeit(lambda *a: compact_block(*a), (send, inp.sn, inp.ts),
           n, "10. egress compaction (nonzero+gather)")

    # ---- 11. compaction via cumsum+searchsorted (candidate) --------------
    @jax.jit
    def compact2_block(send, sn, ts):
        flat = send.reshape(R, -1).astype(jnp.int32)
        csum = jnp.cumsum(flat, axis=1)                      # [R, N]
        want = jnp.arange(1, cap + 1, dtype=jnp.int32)[None, :]
        idx = jax.vmap(lambda c, w: jnp.searchsorted(c, w, side="left"))(
            csum, jnp.broadcast_to(want, (R, cap)))
        total = csum[:, -1]
        hit = want[0][None, :] <= total[:, None]
        idx = jnp.where(hit, idx, -1).astype(jnp.int32)
        safe = jnp.maximum(idx, 0)
        osn = jnp.broadcast_to(sn[..., None], (R, T, K, S)).reshape(R, -1)
        ots = jnp.broadcast_to(ts[..., None], (R, T, K, S)).reshape(R, -1)
        g = lambda x: jnp.where(hit, jnp.take_along_axis(x, safe, axis=1), 0)
        return idx, g(osn), g(ots)
    timeit(lambda *a: compact2_block(*a), (send, inp.sn, inp.ts),
           n, "11. compaction (cumsum+searchsorted)")

    # ---- 12. output packing (concatenate) --------------------------------
    state2 = synth.make_state(dims, spec)
    pkt2, fb2, tf2, _, _ = plane.pack_tick_inputs(inp)

    @jax.jit
    def outputs_only(state, pkt, fb, tf):
        i = plane.unpack_tick_inputs(pkt, fb, tf, tick_ms, roll)
        _, out = plane.media_plane_tick(state, i)
        return out

    @jax.jit
    def outputs_packed(state, pkt, fb, tf):
        i = plane.unpack_tick_inputs(pkt, fb, tf, tick_ms, roll)
        _, out = plane.media_plane_tick(state, i)
        return plane.pack_tick_outputs(out)

    timeit(lambda *a: outputs_only(*a), (state2, pkt2, fb2, tf2),
           n, "12a. tick, outputs UNPACKED (no donate)")
    timeit(lambda *a: outputs_packed(*a), (state2, pkt2, fb2, tf2),
           n, "12b. tick, outputs packed (no donate)")

    # ---- 13. mask merges + padding + quality (leftover algebra) ----------
    @jax.jit
    def merge_block(is_video, valid, base, v_fwd, v_drop):
        a_fwd = valid[..., None] & base[:, :, None, :]
        fwd = jnp.where(is_video[..., None, None], v_fwd & base[:, :, None, :], a_fwd)
        drop = jnp.where(is_video[..., None, None], v_drop & base[:, :, None, :], False)
        ev = jnp.sum(fwd, dtype=jnp.int32)
        return fwd, drop, ev
    base = jnp.ones((R, T, S), bool)
    timeit(lambda *a: merge_block(*a),
           (state.meta.is_video, inp.valid, base, fwd, drop),
           n, "13. mask merges")
    print("done")


if __name__ == "__main__":
    main()
