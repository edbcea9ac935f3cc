"""Batched simulcast / temporal video-layer selection.

Reference parity: pkg/sfu/videolayerselector/simulcast.go:42 (key-frame-gated
spatial switching), temporallayerselector/ (VP8 layer-sync-gated temporal
upgrades), and the selector interface videolayerselector.go:31. SVC/
dependency-descriptor selection (vp9.go, dependencydescriptor.go) builds on
the same mask algebra and lands in ops.svc.

TPU-first re-design: per-(track, subscriber) selector state lives in [S]
int32 tensors; each tick a `lax.scan` over the (small, static) packet axis
produces forward/drop/switch masks consumed by ops.rtpmunger / ops.vp8 —
the decision half of the reference's DownTrack.WriteRTP hot path
(downtrack.go:680 → forwarder.go GetTranslationParams :1436).

Layer encoding: spatial/temporal are small ints; INVALID_LAYER (-1) means
"not forwarding" (reference buffer.InvalidLayer{-1,-1}).
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from livekit_server_tpu.ops.backend import want_pallas

INVALID_LAYER = -1  # plain int: module import must not init a jax backend


class SelectorState(NamedTuple):
    """Per-(track, subscriber) selection state; fields are [..., S] int32.

    current_*: layer currently forwarded (reference `currentLayer`)
    target_*:  layer the allocator wants (reference `targetLayer`, set by
               the stream allocator / forwarder allocation algebra)
    """

    current_spatial: jax.Array
    current_temporal: jax.Array
    target_spatial: jax.Array
    target_temporal: jax.Array


def init_state(num_subscribers: int, target_spatial: int = 2, target_temporal: int = 3) -> SelectorState:
    s = jnp.full((num_subscribers,), INVALID_LAYER, jnp.int32)
    return SelectorState(
        current_spatial=s,
        current_temporal=s,
        target_spatial=jnp.full((num_subscribers,), target_spatial, jnp.int32),
        target_temporal=jnp.full((num_subscribers,), target_temporal, jnp.int32),
    )


def select_tick(
    state: SelectorState,
    pkt_spatial: jax.Array,    # [P] int32 — simulcast layer of the packet
    pkt_temporal: jax.Array,   # [P] int32 — temporal id (0 if none)
    pkt_keyframe: jax.Array,   # [P] bool
    pkt_layer_sync: jax.Array, # [P] bool — VP8 Y bit / temporal upswitch point
    pkt_valid: jax.Array,      # [P] bool
):
    """One tick of layer selection for one video track.

    Returns (new_state, forward [P,S], drop [P,S], switch [P,S],
    need_keyframe [S]). `drop` marks current-stream packets filtered by the
    temporal selector (they compact the SN space); `switch` marks the packet
    where a subscriber changes spatial source; `need_keyframe` asks the host
    to send a PLI upstream when a subscriber waits on a spatial switch
    (reference Simulcast.Select key-frame gating + downtrack key-frame
    requester downtrack.go:608).
    """

    def step(carry: SelectorState, xs):
        sp, tp, kf, sync, valid = xs

        # Spatial switch: only at a key frame of the target layer; also the
        # initial lock-on when nothing is forwarding yet. A downgrade request
        # (target < current) also waits for a target-layer key frame.
        want_switch = (carry.target_spatial != carry.current_spatial) & (
            carry.target_spatial >= 0
        )
        sw = valid & kf & want_switch & (sp == carry.target_spatial)
        cur_sp = jnp.where(sw, carry.target_spatial, carry.current_spatial)
        # Reset temporal on spatial switch: start from target temporal.
        cur_tp = jnp.where(sw, carry.target_temporal, carry.current_temporal)

        on_current = valid & (sp == cur_sp) & (cur_sp >= 0)

        # Temporal selection (temporallayerselector/simple.go semantics):
        # upgrade only at a layer-sync point, downgrade immediately.
        can_up = on_current & sync & (tp <= carry.target_temporal)
        cur_tp = jnp.where(can_up & (tp > cur_tp), tp, cur_tp)
        cur_tp = jnp.where(
            on_current & (carry.target_temporal < cur_tp), carry.target_temporal, cur_tp
        )

        fwd = on_current & (tp <= cur_tp)
        drp = on_current & ~fwd
        # Pause: target invalid ⇒ stop forwarding entirely.
        paused = carry.target_spatial < 0
        fwd = fwd & ~paused
        drp = (drp | (on_current & paused))

        new_carry = SelectorState(
            current_spatial=jnp.where(paused, INVALID_LAYER, cur_sp),
            current_temporal=cur_tp,
            target_spatial=carry.target_spatial,
            target_temporal=carry.target_temporal,
        )
        return new_carry, (fwd, drp, sw)

    xs = (pkt_spatial, pkt_temporal, pkt_keyframe, pkt_layer_sync, pkt_valid)
    new_state, (fwd, drp, sw) = jax.lax.scan(step, state, xs, unroll=True)
    need_keyframe = (new_state.target_spatial >= 0) & (
        new_state.target_spatial != new_state.current_spatial
    )
    return new_state, fwd, drp, sw, need_keyframe


def select_both_tick(state: SelectorState, is_svc, pkt_spatial, pkt_temporal,
                     pkt_keyframe, pkt_layer_sync, pkt_end_frame, pkt_valid):
    """Merged simulcast + SVC selection for one room's [T] tracks — the
    SCAN formulation (the spec): both selector variants over shared state,
    picked per track by `is_svc` [T]. The production TPU path is the fused
    room-batched `decide_rooms` kernel, pinned bit-identical to this
    composition by tests/test_selector.py.

    Returns (state', fwd [T,K,S] bool, drop, switch, need_kf [T,S] bool).
    """
    from livekit_server_tpu.ops import svc as svc_mod

    sel_state, v_fwd, v_drop, v_switch, nk_sim = jax.vmap(select_tick)(
        state, pkt_spatial, pkt_temporal, pkt_keyframe, pkt_layer_sync,
        pkt_valid,
    )
    svc_state, s_fwd, s_drop, _s_up, nk_svc = jax.vmap(svc_mod.select_tick)(
        svc_mod.SVCSelectorState(*state), pkt_spatial, pkt_temporal,
        pkt_keyframe, pkt_layer_sync, pkt_end_frame, pkt_valid,
    )
    merged = jax.tree.map(
        lambda sim, sv: jnp.where(is_svc[:, None], sv, sim),
        sel_state, SelectorState(*svc_state),
    )
    m = is_svc[:, None, None]
    fwd = jnp.where(m, s_fwd, v_fwd)
    drop = jnp.where(m, s_drop, v_drop)
    switch = jnp.where(m, False, v_switch)
    need_kf = jnp.where(is_svc[:, None], nk_svc, nk_sim)
    return merged, fwd, drop, switch, need_kf


def set_target(state: SelectorState, target_spatial: jax.Array, target_temporal: jax.Array) -> SelectorState:
    """Apply allocator-decided target layers (reference Forwarder.SetTargetLayer)."""
    return state._replace(
        target_spatial=jnp.asarray(target_spatial, jnp.int32),
        target_temporal=jnp.asarray(target_temporal, jnp.int32),
    )


# ---------------------------------------------------------------------------
# Room-batched kernels: rooms on the vector lanes.
#
# A per-room kernel under vmap runs as a grid with ONE room per step;
# per-step fixed costs (DMA setup, tiny [T,S] vregs at ~8% lane occupancy)
# measured ~0.8 ms/tick at cfg4 and ~8 ms at the 10k-room north-star
# shape. These kernels block a room batch onto the 128-wide lane axis
# ([T, K|S, RB] layout), so every vector op is fully packed and the grid
# shrinks by RB.
# ---------------------------------------------------------------------------


def pick_room_block(R: int, per_room_bytes: int) -> int:
    """Room-block size for the lane axis: a multiple of 128 (Mosaic
    requires lane-dim blocks divisible by 128) whose single-buffered VMEM
    working set stays under ~4 MB (Mosaic double-buffers blocks and keeps
    unrolled-loop live ranges in scoped VMEM, so actual use runs a small
    multiple of this against the raised per-kernel limit), or the whole
    array when R has no suitable 128-multiple divisor."""
    from livekit_server_tpu.utils.logger import log

    cap = max(1, (4 << 20) // max(per_room_bytes, 1))
    for cand in (512, 256, 128):
        if cand <= cap and R % cand == 0:
            return cand
    if R % 128 == 0:
        # Over-budget but lane-valid: 128 is the SMALLEST legal block, so
        # it is the best effort when even that exceeds the cap (returning
        # R here would request the largest block exactly when the budget
        # is tightest). The per-kernel vmem_limit gives real headroom.
        # Trace-time only: block sizing runs while jit traces, never in
        # the compiled graph — warning once per compile is the intent.
        log.warn(  # graftcheck: disable=GC02
            "pick_room_block over VMEM budget: smallest legal block "
            "exceeds the ~4MB working-set cap; relying on the raised "
            "per-kernel vmem_limit",
            R=R, per_room_bytes=per_room_bytes, block=128, cap_rooms=cap,
        )
        return 128
    # No 128-multiple divisor (small or odd R): whole array. Legal only
    # because Mosaic pads a sub-128 lane dim; a LARGE R landing here means
    # a dims misconfiguration (e.g. R=384+1) and a likely OOM, not a
    # deliberate small-plane shape.
    if R > 128:
        # Trace-time only, as above: fires once per compile, not per tick.
        log.warn(  # graftcheck: disable=GC02
            "pick_room_block whole-array fallback for large R: no "
            "128-multiple divisor; check plane dims",
            R=R, per_room_bytes=per_room_bytes,
        )
    assert R % 128 != 0, "divisible R must take a 128-multiple block above"
    return R


def _decide_rooms_kernel(sp_ref, tp_ref, kf_ref, sync_ref, eof_ref, valid_ref,
                         size_ref, cur_sp_ref, cur_tp_ref, tgt_sp_ref,
                         tgt_tp_ref, svc_ref, vid_ref, base_ref,
                         send_ref, drop_ref, sw_ref, out_sp_ref, out_tp_ref,
                         nkf_ref, pkts_ref, bytes_ref, fp_ref, fb_ref,
                         *, wire_overhead: int):
    """Pallas TPU kernel: the ENTIRE per-packet forward decision for a
    room block — simulcast+SVC selection, subscription/mute base merge,
    audio path, egress-mask BIT PACKING, and the per-subscriber send
    sums — with nothing dense ever leaving VMEM.

    Packet refs [T, K, RB]; state/base refs [T, S, RB]; svc/vid
    [T, 1, RB]; outputs: masks [T, K, W, RB] int32 bit words,
    selector state + need_kf [T, S, RB], pkts/bytes [1, S, RB],
    fwd totals [1, 1, RB].
    """
    T, K, RB = sp_ref.shape
    S = cur_sp_ref.shape[1]
    W = (S + 31) // 32
    is_svc = svc_ref[:, :, :] != 0                                  # [T,1,RB]
    is_vid = vid_ref[:, :, :] != 0                                  # [T,1,RB]
    base = base_ref[:, :, :] != 0                                   # [T,S,RB]
    tgt_sp = tgt_sp_ref[:, :, :]
    tgt_tp = tgt_tp_ref[:, :, :]
    sim_sp, sim_tp = cur_sp_ref[:, :, :], cur_tp_ref[:, :, :]
    svc_sp, svc_tp = cur_sp_ref[:, :, :], cur_tp_ref[:, :, :]
    paused = tgt_sp < 0

    pkts_acc = jnp.zeros((S, RB), jnp.int32)
    bytes_acc = jnp.zeros((S, RB), jnp.int32)
    fp_acc = jnp.zeros((1, RB), jnp.int32)
    fb_acc = jnp.zeros((1, RB), jnp.int32)

    for k in range(K):
        sp_k = sp_ref[:, k, :][:, None, :]                          # [T,1,RB]
        tp_k = tp_ref[:, k, :][:, None, :]
        kf_k = kf_ref[:, k, :][:, None, :] != 0
        sync_k = sync_ref[:, k, :][:, None, :] != 0
        eof_k = eof_ref[:, k, :][:, None, :] != 0
        val_k = valid_ref[:, k, :][:, None, :] != 0
        size_k = size_ref[:, k, :][:, None, :]                      # [T,1,RB]

        # -- simulcast path ----------------------------------------------
        want = (tgt_sp != sim_sp) & (tgt_sp >= 0)
        sw = val_k & kf_k & want & (sp_k == tgt_sp)
        c_sp = jnp.where(sw, tgt_sp, sim_sp)
        c_tp = jnp.where(sw, tgt_tp, sim_tp)
        on_cur = val_k & (sp_k == c_sp) & (c_sp >= 0)
        can_up = on_cur & sync_k & (tp_k <= tgt_tp)
        c_tp = jnp.where(can_up & (tp_k > c_tp), tp_k, c_tp)
        c_tp = jnp.where(on_cur & (tgt_tp < c_tp), tgt_tp, c_tp)
        fwd_sim = on_cur & (tp_k <= c_tp) & ~paused
        drp_sim = (on_cur & ~(on_cur & (tp_k <= c_tp))) | (on_cur & paused)
        sim_sp = jnp.where(paused, -1, c_sp)
        sim_tp = c_tp

        # -- SVC onion path ----------------------------------------------
        up = val_k & kf_k & (tgt_sp > svc_sp) & (sp_k <= tgt_sp)
        s_sp = jnp.where(up, tgt_sp, svc_sp)
        down = val_k & eof_k & (tgt_sp >= 0) & (tgt_sp < s_sp)
        s_sp_next = jnp.where(down, tgt_sp, s_sp)
        on_stream = val_k & (s_sp >= 0)
        s_tp = jnp.where(up, tgt_tp, svc_tp)
        can_up2 = on_stream & sync_k & (tp_k <= tgt_tp) & (tp_k > s_tp)
        s_tp = jnp.where(can_up2, tp_k, s_tp)
        s_tp = jnp.where(on_stream & (tgt_tp < s_tp), tgt_tp, s_tp)
        fwd_svc = on_stream & (sp_k <= s_sp) & (tp_k <= s_tp) & ~paused
        drp_svc = on_stream & ~fwd_svc
        svc_sp = jnp.where(paused, -1, s_sp_next)
        svc_tp = s_tp

        # -- merge: video selection × base; audio = valid × base ---------
        # (int domain for the select chain — Mosaic cannot lower i1
        # vector truncations.)
        fwd_sel = jnp.where(is_svc, jnp.where(fwd_svc, 1, 0),
                            jnp.where(fwd_sim, 1, 0))
        drp_sel = jnp.where(is_svc, jnp.where(drp_svc, 1, 0),
                            jnp.where(drp_sim, 1, 0))
        sw_sel = jnp.where(sw & ~is_svc, 1, 0)
        base_i = jnp.where(base, 1, 0)
        a_fwd = jnp.where(val_k, base_i, 0)
        fwd_i = jnp.where(is_vid, fwd_sel * base_i, a_fwd)          # [T,S,RB]
        drp_i = jnp.where(is_vid, drp_sel * base_i, 0)
        sw_i = jnp.where(is_vid, sw_sel * base_i, 0)

        # -- send sums ---------------------------------------------------
        pkts_acc = pkts_acc + jnp.sum(fwd_i, axis=0)                # [S,RB]
        bytes_acc = bytes_acc + jnp.sum(
            fwd_i * (size_k + wire_overhead), axis=0
        )
        fp_acc = fp_acc + jnp.sum(fwd_i, axis=(0, 1))[None, :]
        fb_acc = fb_acc + jnp.sum(fwd_i * size_k, axis=(0, 1))[None, :]

        # -- bit packing over the subscriber axis ------------------------
        for w in range(W):
            hi = min(S, (w + 1) * 32)
            send_w = jnp.zeros((T, RB), jnp.int32)
            drop_w = jnp.zeros((T, RB), jnp.int32)
            sw_w = jnp.zeros((T, RB), jnp.int32)
            for s in range(w * 32, hi):
                sh = s - w * 32
                send_w = send_w | jnp.left_shift(fwd_i[:, s, :], sh)
                drop_w = drop_w | jnp.left_shift(drp_i[:, s, :], sh)
                sw_w = sw_w | jnp.left_shift(sw_i[:, s, :], sh)
            send_ref[:, k, w, :] = send_w
            drop_ref[:, k, w, :] = drop_w
            sw_ref[:, k, w, :] = sw_w

    out_sp = jnp.where(is_svc, svc_sp, sim_sp)
    out_tp = jnp.where(is_svc, svc_tp, sim_tp)
    out_sp_ref[:, :, :] = out_sp
    out_tp_ref[:, :, :] = out_tp
    nkf_sim = (tgt_sp >= 0) & (tgt_sp != out_sp)
    nkf_svc = (tgt_sp >= 0) & (tgt_sp > out_sp)
    nkf = jnp.where(is_svc, jnp.where(nkf_svc, 1, 0),
                    jnp.where(nkf_sim, 1, 0))
    nkf_ref[:, :, :] = nkf * jnp.where(base & is_vid, 1, 0)
    pkts_ref[0, :, :] = pkts_acc
    bytes_ref[0, :, :] = bytes_acc
    fp_ref[0, 0, :] = fp_acc[0]
    fb_ref[0, 0, :] = fb_acc[0]


def decide_rooms(state: SelectorState, is_svc, is_video, base, pkt_spatial,
                 pkt_temporal, pkt_keyframe, pkt_layer_sync, pkt_end_frame,
                 pkt_valid, pkt_size, wire_overhead: int,
                 use_pallas: bool | None = None, interpret: bool = False):
    """The full forward decision for ALL rooms: selection + base merge +
    audio path + bit packing + send sums, as ONE kernel.

    Args: state fields [R,T,S]; is_svc/is_video [R,T]; base [R,T,S] bool
    (subscribed & ~sub_muted & publisher live); packets [R,T,K].

    Returns (state', send_bits [R,T,K,W] i32, drop_bits, switch_bits,
    need_kf [R,T,S] bool (base-merged), pkts_sent [R,S] i32,
    sent_bytes [R,S] i32 (wire_overhead included), fwd_packets [R] i32,
    fwd_bytes [R] i32).

    The dense [R,T,K,S] masks NEVER materialize in HBM on this path —
    they measured as both the XLA-fusion VMEM blow-up and several
    hundred MB of traffic per tick at the 10k-room shape. CPU
    (tests/dryrun) composes the same result from the per-room pieces.
    """
    from livekit_server_tpu.ops import bits

    use_pallas = want_pallas(use_pallas, interpret, "selector.decide_rooms")
    S = state.current_spatial.shape[-1]
    if not (use_pallas or interpret):
        sel_state, v_fwd, v_drop, v_switch, nkf_sel = select_both_rooms(
            state, is_svc, pkt_spatial, pkt_temporal, pkt_keyframe,
            pkt_layer_sync, pkt_end_frame, pkt_valid,
        )
        is_vid = jnp.asarray(is_video, bool)[:, :, None, None]
        base_b = jnp.asarray(base, bool)[:, :, None, :]
        a_fwd = jnp.asarray(pkt_valid, bool)[:, :, :, None] & base_b
        fwd = jnp.where(is_vid, v_fwd & base_b, a_fwd)
        drop = jnp.where(is_vid, v_drop & base_b, False)
        switch = jnp.where(is_vid, v_switch & base_b, False)
        need_kf = (
            nkf_sel & jnp.asarray(base, bool)
            & jnp.asarray(is_video, bool)[:, :, None]
        )
        pkts_sent = jnp.sum(fwd, axis=(1, 2)).astype(jnp.int32)
        size_b = jnp.asarray(pkt_size, jnp.int32)[:, :, :, None]
        sent_bytes = jnp.sum(
            jnp.where(fwd, size_b + wire_overhead, 0), axis=(1, 2)
        ).astype(jnp.int32)
        fwd_packets = jnp.sum(fwd, axis=(1, 2, 3)).astype(jnp.int32)
        fwd_bytes = jnp.sum(
            jnp.where(fwd, size_b, 0), axis=(1, 2, 3)
        ).astype(jnp.int32)
        return (sel_state, bits.pack_bits(fwd), bits.pack_bits(drop),
                bits.pack_bits(switch), need_kf, pkts_sent, sent_bytes,
                fwd_packets, fwd_bytes)

    import functools as _functools

    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    # Renamed upstream: TPUCompilerParams (<=0.4.x) -> CompilerParams.
    _CompilerParams = getattr(pltpu, "CompilerParams", None) or (
        pltpu.TPUCompilerParams
    )

    R, T, K = pkt_spatial.shape
    W = bits.mask_words(S)
    # Word-sized outputs keep this kernel's block footprint ~32× smaller
    # than select_both_rooms', so blocks scale by the input/state set.
    RB = pick_room_block(
        R, 4 * (T * (7 * K + 9 * S + 3 * K * W) + 2 * S + 2)
    )
    i32 = lambda x: jnp.asarray(x, jnp.int32)  # noqa: E731
    tkr = lambda x: i32(x).transpose(1, 2, 0)   # noqa: E731
    tsr = lambda x: i32(x).transpose(1, 2, 0)   # noqa: E731
    t1r = lambda x: i32(x).transpose(1, 0)[:, None, :]  # noqa: E731

    pkt_spec = pl.BlockSpec((T, K, RB), lambda i: (0, 0, i),
                            memory_space=pltpu.VMEM)
    st_spec = pl.BlockSpec((T, S, RB), lambda i: (0, 0, i),
                           memory_space=pltpu.VMEM)
    t1_spec = pl.BlockSpec((T, 1, RB), lambda i: (0, 0, i),
                           memory_space=pltpu.VMEM)
    word_spec = pl.BlockSpec((T, K, W, RB), lambda i: (0, 0, 0, i),
                             memory_space=pltpu.VMEM)
    sub_spec = pl.BlockSpec((1, S, RB), lambda i: (0, 0, i),
                            memory_space=pltpu.VMEM)
    tot_spec = pl.BlockSpec((1, 1, RB), lambda i: (0, 0, i),
                            memory_space=pltpu.VMEM)
    (send_w, drop_w, sw_w, out_sp, out_tp, nkf, pkts, byts, fp, fb) = (
        pl.pallas_call(
            _functools.partial(
                _decide_rooms_kernel, wire_overhead=wire_overhead
            ),
            grid=(R // RB,),
            out_shape=(
                jax.ShapeDtypeStruct((T, K, W, R), jnp.int32),
                jax.ShapeDtypeStruct((T, K, W, R), jnp.int32),
                jax.ShapeDtypeStruct((T, K, W, R), jnp.int32),
                jax.ShapeDtypeStruct((T, S, R), jnp.int32),
                jax.ShapeDtypeStruct((T, S, R), jnp.int32),
                jax.ShapeDtypeStruct((T, S, R), jnp.int32),
                jax.ShapeDtypeStruct((1, S, R), jnp.int32),
                jax.ShapeDtypeStruct((1, S, R), jnp.int32),
                jax.ShapeDtypeStruct((1, 1, R), jnp.int32),
                jax.ShapeDtypeStruct((1, 1, R), jnp.int32),
            ),
            in_specs=[pkt_spec] * 7 + [st_spec] * 4 + [t1_spec] * 2
            + [st_spec],
            out_specs=(word_spec,) * 3 + (st_spec,) * 3
            + (sub_spec,) * 2 + (tot_spec,) * 2,
            # v5e has 128 MB of VMEM; Mosaic's default 16 MB scoped limit
            # under-counts this kernel's unrolled-loop live ranges.
            compiler_params=_CompilerParams(
                vmem_limit_bytes=64 * 1024 * 1024
            ),
            interpret=interpret,
        )(
            tkr(pkt_spatial), tkr(pkt_temporal), tkr(pkt_keyframe),
            tkr(pkt_layer_sync), tkr(pkt_end_frame), tkr(pkt_valid),
            tkr(pkt_size),
            tsr(state.current_spatial), tsr(state.current_temporal),
            tsr(state.target_spatial), tsr(state.target_temporal),
            t1r(is_svc), t1r(is_video), tsr(base),
        )
    )
    new_state = SelectorState(
        current_spatial=out_sp.transpose(2, 0, 1),
        current_temporal=out_tp.transpose(2, 0, 1),
        target_spatial=state.target_spatial,
        target_temporal=state.target_temporal,
    )
    wb = lambda m: m.transpose(3, 0, 1, 2)  # noqa: E731 — [T,K,W,R]→[R,T,K,W]
    return (
        new_state, wb(send_w), wb(drop_w), wb(sw_w),
        nkf.transpose(2, 0, 1).astype(bool),
        pkts[0].transpose(1, 0), byts[0].transpose(1, 0),
        fp[0, 0], fb[0, 0],
    )


def select_both_rooms(state: SelectorState, is_svc, pkt_spatial, pkt_temporal,
                      pkt_keyframe, pkt_layer_sync, pkt_end_frame, pkt_valid):
    """Plane-level merged selection, composed from the per-room scan spec
    (state fields [R, T, S], packets [R, T, K], is_svc [R, T]). Used by
    `decide_rooms`'s CPU fallback and tests; the production TPU path is
    the fused `decide_rooms` kernel.

    Returns (state', fwd [R,T,K,S] bool, drop, switch, need_kf [R,T,S]).
    """
    return jax.vmap(select_both_tick)(
        state, is_svc, pkt_spatial, pkt_temporal, pkt_keyframe,
        pkt_layer_sync, pkt_end_frame, pkt_valid,
    )

