"""Ragged-aware pooled-tick Pallas kernel: one grid step per LIVE page.

The stock pooled tick computes every pool row at `[P, TP, K, SP]` and
masks the dead ones — after PR 9 the paged plane wins on memory but
still pays full-pool compute. This kernel consumes the device page
table's live extents as a SCALAR-PREFETCH operand (`live_rows`, the
mapped pool ids): the grid is `(NL,)`, each step's input index maps
select pool block `live_rows[i]`, and outputs land compact at block `i`.
Dead and unmapped pages are never *scheduled* — there is no grid step
that could touch them — rather than computed-and-masked, so kernel work
is proportional to occupancy, not pool size.

Each grid step fuses, for one live page:

  * the ENTIRE forward decision (`ops/selector.py` `_decide_rooms_kernel`
    algebra at page shapes): simulcast + SVC selection, base merge,
    audio path, egress bit packing, per-sub send sums;
  * the stats/tracker ROUTING selects from the phase-1 core (the
    stacked `[5, T, K, L]` one-hot routing; models/plane.py `_room_tick`
    accepts them precomputed via `routed_stats`);
  * optionally the `ops/mix.py` active-speaker mix for the page's
    subscribers — the first time decide and mix ride one kernel. The
    page-local top-K speaker gate equals the room-level gate exactly
    when the room's tracks fit one track page (MT == 1 — the MCU
    1000-room shape); multi-track-page rooms would need a cross-page
    level reduction and keep the XLA mix.

Accumulator/output layout keeps the pool dimension leading on every
array, so `parallel/mesh.py page_sharding` still shards the pool axis of
the scattered results. Layout note: page blocks put SP (≤ 32 by config)
or K on the lane axis — fine in interpret mode (CPU CI) and correct on
TPU, but sub-128 lanes under-occupy the VPU; lane-packing multiple
pages per step is recorded future work (ARCHITECTURE.md).

CPU fallback (`use_pallas=False`, `interpret=False`): the same compact
live-row computation composed from `selector.decide_rooms`'s fallback —
still live-only compute, no Pallas — with the routing left to
`_room_tick` (`st`/`tr` returned as None).
"""

from __future__ import annotations

import functools
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp

from livekit_server_tpu.analysis.registry import device_entry
from livekit_server_tpu.ops import selector
from livekit_server_tpu.ops.backend import want_pallas

NUM_LAYERS = 3   # spatial routing lanes (models/plane.py MAX_LAYERS)


class LiveDecide(NamedTuple):
    """Phase-0 products for the live pages only (leading axis [NL]).

    `st`/`tr` are the precomputed stats/tracker routings
    (`[NL, 5, TP*L, K]` / `[NL, 3, TP*L]`) on the kernel path, None on
    the CPU fallback (the phase-1 core then computes them in place).
    """

    sel: Any                 # selector.SelectorState, leaves [NL, TP, SP]
    send_bits: jax.Array     # [NL, TP, K, W] int32
    drop_bits: jax.Array     # [NL, TP, K, W] int32
    switch_bits: jax.Array   # [NL, TP, K, W] int32
    need_kf: jax.Array       # [NL, TP, SP] bool
    pkts_sent: jax.Array     # [NL, SP] int32
    sent_bytes: jax.Array    # [NL, SP] int32
    fwd_packets: jax.Array   # [NL] int32
    fwd_bytes: jax.Array     # [NL] int32
    st: Any                  # [NL, 5, TP*L, K] int32 | None
    tr: Any                  # [NL, 3, TP*L] int32 | None


def _page_kernel(*refs, TP: int, K: int, SP: int, L: int,
                 wire_overhead: int, top_k: int,
                 with_decide: bool, with_mix: bool):
    """One live page per grid step. Ref order (after the prefetched
    live_rows ref): decide inputs, mix inputs, decide outputs, mix
    output — each present only when its flag is set."""
    it = iter(refs)
    _ = next(it)  # live_rows scalar-prefetch ref: consumed by index maps
    if with_decide:
        (cur_sp_ref, cur_tp_ref, tgt_sp_ref, tgt_tp_ref, svc_ref, vid_ref,
         base_ref, layer_ref, temporal_ref, kf_ref, sync_ref, eof_ref,
         valid_ref, size_ref, sn_ref, ts_ref, arr_ref, bpic_ref) = (
            next(it) for _ in range(18)
        )
    if with_mix:
        pcm_ref, level_ref, active_ref, gain_ref, subtrack_ref = (
            next(it) for _ in range(5)
        )
    if with_decide:
        (send_ref, drop_ref, sw_ref, out_sp_ref, out_tp_ref, nkf_ref,
         pkts_ref, bytes_ref, fp_ref, fb_ref, st_ref, tr_ref) = (
            next(it) for _ in range(12)
        )
    if with_mix:
        mixed_ref = next(it)

    if with_decide:
        # ---- forward decision: ops/selector.py `_decide_rooms_kernel`
        # algebra with the room-block lane axis replaced by this page's
        # [TP, SP] plane (int domain throughout — Mosaic cannot lower i1
        # vector truncations).
        is_svc = svc_ref[0, 0][:, None] != 0                    # [TP, 1]
        is_vid = vid_ref[0, 0][:, None] != 0                    # [TP, 1]
        base = base_ref[0] != 0                                 # [TP, SP]
        tgt_sp = tgt_sp_ref[0]                                  # [TP, SP]
        tgt_tp = tgt_tp_ref[0]
        sim_sp, sim_tp = cur_sp_ref[0], cur_tp_ref[0]
        svc_sp, svc_tp = cur_sp_ref[0], cur_tp_ref[0]
        paused = tgt_sp < 0

        sh = jnp.arange(SP, dtype=jnp.int32)[None, :]           # [1, SP]
        pkts_acc = jnp.zeros((SP,), jnp.int32)
        bytes_acc = jnp.zeros((SP,), jnp.int32)
        fp_acc = jnp.zeros((), jnp.int32)
        fb_acc = jnp.zeros((), jnp.int32)

        for k in range(K):
            sp_k = layer_ref[0][:, k][:, None]                  # [TP, 1]
            tp_k = temporal_ref[0][:, k][:, None]
            kf_k = kf_ref[0][:, k][:, None] != 0
            sync_k = sync_ref[0][:, k][:, None] != 0
            eof_k = eof_ref[0][:, k][:, None] != 0
            val_k = valid_ref[0][:, k][:, None] != 0
            size_k = size_ref[0][:, k][:, None]                 # [TP, 1]

            # -- simulcast path ------------------------------------------
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

            # -- SVC onion path ------------------------------------------
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

            # -- merge: video selection × base; audio = valid × base -----
            fwd_sel = jnp.where(is_svc, jnp.where(fwd_svc, 1, 0),
                                jnp.where(fwd_sim, 1, 0))
            drp_sel = jnp.where(is_svc, jnp.where(drp_svc, 1, 0),
                                jnp.where(drp_sim, 1, 0))
            sw_sel = jnp.where(sw & ~is_svc, 1, 0)
            base_i = jnp.where(base, 1, 0)
            a_fwd = jnp.where(val_k, base_i, 0)
            fwd_i = jnp.where(is_vid, fwd_sel * base_i, a_fwd)  # [TP, SP]
            drp_i = jnp.where(is_vid, drp_sel * base_i, 0)
            sw_i = jnp.where(is_vid, sw_sel * base_i, 0)

            # -- send sums -----------------------------------------------
            pkts_acc = pkts_acc + jnp.sum(fwd_i, axis=0)        # [SP]
            bytes_acc = bytes_acc + jnp.sum(
                fwd_i * (size_k + wire_overhead), axis=0
            )
            fp_acc = fp_acc + jnp.sum(fwd_i)
            fb_acc = fb_acc + jnp.sum(fwd_i * size_k)

            # -- bit packing over the sub axis (SP ≤ 32 ⇒ one word):
            # disjoint-bit shift-SUM over lanes == OR, exact incl. the
            # two's-complement bit 31.
            send_ref[0, :, k] = jnp.sum(jnp.left_shift(fwd_i, sh), axis=1)
            drop_ref[0, :, k] = jnp.sum(jnp.left_shift(drp_i, sh), axis=1)
            sw_ref[0, :, k] = jnp.sum(jnp.left_shift(sw_i, sh), axis=1)

        out_sp = jnp.where(is_svc, svc_sp, sim_sp)
        out_tp = jnp.where(is_svc, svc_tp, sim_tp)
        out_sp_ref[0] = out_sp
        out_tp_ref[0] = out_tp
        nkf_sim = (tgt_sp >= 0) & (tgt_sp != out_sp)
        nkf_svc = (tgt_sp >= 0) & (tgt_sp > out_sp)
        nkf = jnp.where(is_svc, jnp.where(nkf_svc, 1, 0),
                        jnp.where(nkf_sim, 1, 0))
        nkf_ref[0] = nkf * jnp.where(base & is_vid, 1, 0)
        pkts_ref[0, 0] = pkts_acc
        bytes_ref[0, 0] = bytes_acc
        fp_ref[0] = jnp.full((1, 1), fp_acc, jnp.int32)
        fb_ref[0] = jnp.full((1, 1), fb_acc, jnp.int32)

        # ---- stats/tracker routing (models/plane.py `_room_tick`
        # sections 1–2, verbatim int algebra at page shapes) -------------
        lanes = jnp.arange(L, dtype=jnp.int32)[None, None, :]   # [1,1,L]
        layer = layer_ref[0]                                    # [TP, K]
        size = size_ref[0]
        valid_i = valid_ref[0]
        eff_layer = jnp.where(
            is_svc, 0, jnp.clip(layer, 0, L - 1)
        )
        st_vals = jnp.stack(
            [sn_ref[0], ts_ref[0], size, arr_ref[0], valid_i]
        )                                                       # [5,TP,K]
        st_routed = jnp.where(
            (eff_layer[:, :, None] == lanes)[None], st_vals[:, :, :, None], 0
        )                                                       # [5,TP,K,L]
        st_ref[0] = st_routed
        true_layer = jnp.clip(layer, 0, L - 1)
        t_lane = true_layer[:, :, None] == lanes                # [TP,K,L]
        ones_k = jnp.ones((TP, K), jnp.int32)
        tr_vals = jnp.stack([ones_k, size, ones_k])             # [3,TP,K]
        tr_pred = jnp.stack(
            [valid_i, valid_i, valid_i * bpic_ref[0]]
        )                                                       # [3,TP,K]
        routed = jnp.where(
            t_lane[None] & (tr_pred[:, :, :, None] != 0),
            tr_vals[:, :, :, None], 0,
        )                                                       # [3,TP,K,L]
        tr_ref[0] = jnp.sum(routed, axis=2)                     # [3,TP,L]

    if with_mix:
        # ---- page-local active-speaker mix (ops/mix.py mix_tick math;
        # exact vs the room-level gate when MT == 1 — module doc). The
        # top-K threshold is the multiset k-th largest via pairwise
        # compares (no sort in-kernel): min{v : #{v' > v} < k}, which
        # equals sort(lv)[TP - k] including tie semantics.
        level = level_ref[0, 0]                                 # [TP] f32
        act = active_ref[0, 0] != 0                             # [TP]
        lv = jnp.where(act, level, -1.0)
        k_eff = min(top_k, TP)
        cnt_gt = jnp.sum(
            (lv[None, :] > lv[:, None]).astype(jnp.int32), axis=1
        )                                                       # [TP]
        thr = jnp.min(jnp.where(cnt_gt < k_eff, lv, jnp.inf))
        speak = act & (lv >= jnp.maximum(thr, 0.0))             # [TP]
        sub_tr = subtrack_ref[0, 0]                             # [SP]
        w = speak[None, :] & (
            jnp.arange(TP, dtype=jnp.int32)[None, :] != sub_tr[:, None]
        )                                                       # [SP, TP]
        weights = w.astype(jnp.float32) * gain_ref[0, 0][None, :]
        mixed_ref[0] = jnp.dot(weights, pcm_ref[0])             # [SP, N]


def _pallas_live_call(live_rows, decide_ops, mix_ops, *, TP, K, SP, N, L,
                      wire_overhead, top_k, interpret):
    """Assemble and run the live-page pallas_call. `decide_ops` /
    `mix_ops` are the input tuples (or None to skip that half)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    # Renamed upstream: TPUCompilerParams (<=0.4.x) -> CompilerParams.
    _CompilerParams = getattr(pltpu, "CompilerParams", None) or (
        pltpu.TPUCompilerParams
    )
    NL = live_rows.shape[0]
    with_decide = decide_ops is not None
    with_mix = mix_ops is not None

    def live(i, lr):
        return lr[i]

    vm = pltpu.VMEM
    st3 = pl.BlockSpec((1, TP, SP), lambda i, lr: (live(i, lr), 0, 0),
                       memory_space=vm)
    # Per-page vectors carry a unit axis ([P, 1, x] blocked (1, 1, x)): the
    # last two block dims must equal the array's, and P stays leading so
    # `page_sharding` still holds.
    t2 = pl.BlockSpec((1, 1, TP), lambda i, lr: (live(i, lr), 0, 0),
                      memory_space=vm)
    pk = pl.BlockSpec((1, TP, K), lambda i, lr: (live(i, lr), 0, 0),
                      memory_space=vm)
    in_specs: list = []
    inputs: list = []
    if with_decide:
        in_specs += [st3] * 4 + [t2] * 2 + [st3] + [pk] * 11
        d = list(decide_ops)
        inputs += d[:4] + [x[:, None, :] for x in d[4:6]] + d[6:]
    if with_mix:
        pcm_spec = pl.BlockSpec((1, TP, N), lambda i, lr: (live(i, lr), 0, 0),
                                memory_space=vm)
        s2 = pl.BlockSpec((1, 1, SP), lambda i, lr: (live(i, lr), 0, 0),
                          memory_space=vm)
        in_specs += [pcm_spec, t2, t2, t2, s2]
        inputs += [mix_ops[0]] + [x[:, None, :] for x in mix_ops[1:]]

    # Compact outputs: block i of the [NL]-leading result arrays.
    c3 = pl.BlockSpec((1, TP, SP), lambda i, lr: (i, 0, 0), memory_space=vm)
    cw = pl.BlockSpec((1, TP, K), lambda i, lr: (i, 0, 0), memory_space=vm)
    cs = pl.BlockSpec((1, 1, SP), lambda i, lr: (i, 0, 0), memory_space=vm)
    ct = pl.BlockSpec((1, 1, 1), lambda i, lr: (i, 0, 0), memory_space=vm)
    # Routed stats leave the kernel as computed ([5,TP,K,L] / [3,TP,L]);
    # the [.., TP*L, ..] row form the core wants is a reshape of the
    # result outside (Mosaic has no such shape cast in-kernel).
    cst = pl.BlockSpec((1, 5, TP, K, L), lambda i, lr: (i, 0, 0, 0, 0),
                       memory_space=vm)
    ctr = pl.BlockSpec((1, 3, TP, L), lambda i, lr: (i, 0, 0, 0),
                       memory_space=vm)
    out_specs: list = []
    out_shape: list = []
    if with_decide:
        i32 = jnp.int32
        out_specs += [cw] * 3 + [c3] * 3 + [cs] * 2 + [ct] * 2 + [cst, ctr]
        out_shape += [
            jax.ShapeDtypeStruct((NL, TP, K), i32),      # send words
            jax.ShapeDtypeStruct((NL, TP, K), i32),      # drop words
            jax.ShapeDtypeStruct((NL, TP, K), i32),      # switch words
            jax.ShapeDtypeStruct((NL, TP, SP), i32),     # out_sp
            jax.ShapeDtypeStruct((NL, TP, SP), i32),     # out_tp
            jax.ShapeDtypeStruct((NL, TP, SP), i32),     # need_kf
            jax.ShapeDtypeStruct((NL, 1, SP), i32),      # pkts_sent
            jax.ShapeDtypeStruct((NL, 1, SP), i32),      # sent_bytes
            jax.ShapeDtypeStruct((NL, 1, 1), i32),       # fwd_packets
            jax.ShapeDtypeStruct((NL, 1, 1), i32),       # fwd_bytes
            jax.ShapeDtypeStruct((NL, 5, TP, K, L), i32),
            jax.ShapeDtypeStruct((NL, 3, TP, L), i32),
        ]
    if with_mix:
        cm = pl.BlockSpec((1, SP, N), lambda i, lr: (i, 0, 0),
                          memory_space=vm)
        out_specs += [cm]
        out_shape += [jax.ShapeDtypeStruct((NL, SP, N), jnp.float32)]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(NL,),
        in_specs=in_specs,
        out_specs=tuple(out_specs),
    )
    return pl.pallas_call(
        functools.partial(
            _page_kernel, TP=TP, K=K, SP=SP, L=L,
            wire_overhead=wire_overhead, top_k=top_k,
            with_decide=with_decide, with_mix=with_mix,
        ),
        out_shape=tuple(out_shape),
        grid_spec=grid_spec,
        # v5e has 128 MB of VMEM; page blocks are small but the unrolled
        # K loop keeps many live ranges (cf. ops/selector.py).
        compiler_params=_CompilerParams(vmem_limit_bytes=64 * 1024 * 1024),
        interpret=interpret,
        # for whoever opens the .xplane.pb: the Mosaic call's own name
        name="paged_decide",
    )(jnp.asarray(live_rows, jnp.int32), *inputs)


def _decide_inputs(sel_state, is_svc, is_video, base, inp):
    i32 = lambda x: jnp.asarray(x, jnp.int32)  # noqa: E731
    return (
        i32(sel_state.current_spatial), i32(sel_state.current_temporal),
        i32(sel_state.target_spatial), i32(sel_state.target_temporal),
        i32(is_svc), i32(is_video), i32(base),
        i32(inp.layer), i32(inp.temporal), i32(inp.keyframe),
        i32(inp.layer_sync), i32(inp.end_frame), i32(inp.valid),
        i32(inp.size), i32(inp.sn), i32(inp.ts), i32(inp.arrival_rtp),
        i32(inp.begin_pic),
    )


def _decide_from_call(res, sel_state, live_rows):
    (send_w, drop_w, sw_w, out_sp, out_tp, nkf, pkts, byts, fp, fb,
     st, tr) = res[:12]
    NL, _, TP, K, L = st.shape
    sel_new = selector.SelectorState(
        current_spatial=out_sp,
        current_temporal=out_tp,
        target_spatial=sel_state.target_spatial[live_rows],
        target_temporal=sel_state.target_temporal[live_rows],
    )
    return LiveDecide(
        sel=sel_new,
        send_bits=send_w[:, :, :, None],
        drop_bits=drop_w[:, :, :, None],
        switch_bits=sw_w[:, :, :, None],
        need_kf=nkf.astype(bool),
        pkts_sent=pkts[:, 0], sent_bytes=byts[:, 0],
        fwd_packets=fp[:, 0, 0], fwd_bytes=fb[:, 0, 0],
        st=st.transpose(0, 1, 2, 4, 3).reshape(NL, 5, TP * L, K),
        tr=tr.reshape(NL, 3, TP * L),
    )


def _decide_fallback(sel_state, is_svc, is_video, base, inp, live_rows,
                     wire_overhead):
    """Compact live-row decide without Pallas: the stock fallback algebra
    over gathered rows (bit-identical per row). Routing is left to the
    phase-1 core (st/tr None)."""
    def g(a):
        return a[live_rows]

    sel_c = jax.tree.map(g, sel_state)
    (sel_new, send, drop, sw, nkf, pkts, byts, fp, fb) = selector.decide_rooms(
        sel_c, g(is_svc), g(is_video), g(base),
        g(inp.layer), g(inp.temporal), g(inp.keyframe),
        g(inp.layer_sync), g(inp.end_frame), g(inp.valid), g(inp.size),
        wire_overhead=wire_overhead, use_pallas=False,
    )
    return LiveDecide(sel_new, send, drop, sw, nkf, pkts, byts, fp, fb,
                      None, None)


@device_entry("paged_kernel.decide_pages")
def decide_pages(sel_state, is_svc, is_video, base, inp, live_rows, *,
                 wire_overhead: int, num_layers: int = NUM_LAYERS,
                 use_pallas: bool | None = None, interpret: bool = False):
    """Phase 0 of the live-extent tick: the fused forward decision +
    routing for the live pages named by `live_rows` (pow2-padded pool
    ids). Operands stay at POOLED shapes — the kernel's index maps read
    only the live blocks; the fallback gathers them. Returns LiveDecide
    (leading axis NL = live_rows.shape[0])."""
    if not (want_pallas(use_pallas, interpret, "paged_kernel") or interpret):
        return _decide_fallback(sel_state, is_svc, is_video, base, inp,
                                live_rows, wire_overhead)
    P, TP, SP = base.shape
    K = inp.layer.shape[2]
    if SP > 32:
        raise ValueError(f"sub page must fit one mask word, got SP={SP}")
    res = _pallas_live_call(
        live_rows, _decide_inputs(sel_state, is_svc, is_video, base, inp),
        None, TP=TP, K=K, SP=SP, N=0, L=num_layers,
        wire_overhead=wire_overhead, top_k=0, interpret=interpret,
    )
    return _decide_from_call(res, sel_state, live_rows)


def mix_pages(pcm, level, active, sub_track, gain, live_rows, *,
              top_k: int = 3, use_pallas: bool | None = None,
              interpret: bool = False):
    """Active-speaker mix for the live pages only: [NL, SP, N] PCM.
    Page-local speaker gate — exact vs ops/mix.mix_tick when a room's
    tracks fit one track page (module doc)."""
    if not (want_pallas(use_pallas, interpret, "paged_kernel") or interpret):
        from livekit_server_tpu.ops import mix

        def g(a):
            return a[live_rows]

        return mix.mix_tick(g(pcm), g(level), g(active), g(sub_track),
                            g(gain), top_k=top_k)
    P, TP, N = pcm.shape
    SP = sub_track.shape[1]
    (mixed,) = _pallas_live_call(
        live_rows, None,
        (jnp.asarray(pcm, jnp.float32), jnp.asarray(level, jnp.float32),
         jnp.asarray(active, jnp.int32), jnp.asarray(gain, jnp.float32),
         jnp.asarray(sub_track, jnp.int32)),
        TP=TP, K=0, SP=SP, N=N, L=NUM_LAYERS,
        wire_overhead=0, top_k=top_k, interpret=interpret,
    )
    # Soft clip outside the kernel: same jnp.tanh op as mix_tick's.
    return jnp.tanh(mixed)


def decide_mix_pages(sel_state, is_svc, is_video, base, inp,
                     pcm, level, active, sub_track, gain, live_rows, *,
                     wire_overhead: int, top_k: int = 3,
                     num_layers: int = NUM_LAYERS,
                     use_pallas: bool | None = None,
                     interpret: bool = False):
    """Decide AND mix in a single pass per live page — one pallas_call,
    one grid, both output sets. Returns (LiveDecide, mixed [NL, SP, N])."""
    if not (want_pallas(use_pallas, interpret, "paged_kernel") or interpret):
        dec = _decide_fallback(sel_state, is_svc, is_video, base, inp,
                               live_rows, wire_overhead)
        mixed = mix_pages(pcm, level, active, sub_track, gain, live_rows,
                          top_k=top_k, use_pallas=False, interpret=False)
        return dec, mixed
    P, TP, SP = base.shape
    K = inp.layer.shape[2]
    N = pcm.shape[2]
    if SP > 32:
        raise ValueError(f"sub page must fit one mask word, got SP={SP}")
    res = _pallas_live_call(
        live_rows, _decide_inputs(sel_state, is_svc, is_video, base, inp),
        (jnp.asarray(pcm, jnp.float32), jnp.asarray(level, jnp.float32),
         jnp.asarray(active, jnp.int32), jnp.asarray(gain, jnp.float32),
         jnp.asarray(sub_track, jnp.int32)),
        TP=TP, K=K, SP=SP, N=N, L=num_layers,
        wire_overhead=wire_overhead, top_k=top_k, interpret=interpret,
    )
    return _decide_from_call(res, sel_state, live_rows), jnp.tanh(res[12])
