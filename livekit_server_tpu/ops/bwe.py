"""Batched bandwidth estimation: channel observation + trend detection.

Reference parity: pkg/sfu/streamallocator — ChannelObserver
(channelobserver.go:77-170), TrendDetector (trenddetector.go:73-200),
NackTracker (nacktracker.go), RateMonitor, and the congestion-state
machine of the StreamAllocator event loop (streamallocator.go:563-720,
100 ms tick :575).

TPU-first re-design: one state row per subscriber peer connection; the
estimate history is a fixed ring [W]; the trend statistic is a dot product
of the (time-ordered) history with a centered linear-regression weight
vector — the whole per-tick update over all subscribers is one fused
elementwise + matvec kernel (the "BWE per-tick batched matmul" of the north
star). Probe *scheduling* stays host-side (probe_controller timing), fed by
the `probe_good` / congestion outputs here.

Congestion states (streamallocator.go State): 0 = clear, 1 = congested.
Trend directions (trenddetector.go): -1 lowering, 0 neutral, +1 upgrading.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

WINDOW = 8  # estimate samples per trend window (trenddetector RequiredSamples)


class BWEParams(NamedTuple):
    """Mirrors config congestion-control tuning (config.go CongestionControlConfig)."""

    nack_ratio_threshold: float = 0.08   # nacktracker.go ratio threshold
    nack_window_min_packets: int = 10
    estimate_required_downgrades: int = 3  # lowering samples to call a downtrend
    congested_min_estimate: float = 100_000.0  # floor on usable estimate
    stale_ticks: int = 50  # a downtrend older than this many sample-less
                           # ticks no longer holds the channel congested
                           # (channelobserver windows age out; without this
                           # a client that stops reporting would freeze the
                           # congested state and starve the probe controller)
    allow_pause: bool = True  # config allow_pause; False = the allocator keeps
                              # every video at its lowest layer at least
                              # (ops/allocation.allocate_budget)


class BWEState(NamedTuple):
    """Per-subscriber-PC state; fields are [..., S]."""

    estimate_ring: jax.Array   # [..., S, W] float32 — recent estimate samples
    ring_pos: jax.Array        # [..., S] int32 — next write slot
    last_estimate: jax.Array   # [..., S] float32 — latest committed estimate
    nack_packets: jax.Array    # [..., S] float32 — window packet count
    nack_count: jax.Array      # [..., S] float32 — window nack count
    congested: jax.Array       # [..., S] bool
    committed_channel_capacity: jax.Array  # [..., S] float32 — allocator budget
    ticks_since_sample: jax.Array  # [..., S] int32 — staleness counter


def init_state(num_subscribers: int, initial_estimate: float = 7_000_000.0) -> BWEState:
    s = (num_subscribers,)
    return BWEState(
        estimate_ring=jnp.full(s + (WINDOW,), initial_estimate, jnp.float32),
        ring_pos=jnp.zeros(s, jnp.int32),
        last_estimate=jnp.full(s, initial_estimate, jnp.float32),
        nack_packets=jnp.zeros(s, jnp.float32),
        nack_count=jnp.zeros(s, jnp.float32),
        congested=jnp.zeros(s, jnp.bool_),
        committed_channel_capacity=jnp.full(s, initial_estimate, jnp.float32),
        ticks_since_sample=jnp.zeros(s, jnp.int32),
    )


def _trend_weights() -> jax.Array:
    """Centered linear-regression slope weights over the window."""
    x = jnp.arange(WINDOW, dtype=jnp.float32)
    xc = x - jnp.mean(x)
    return xc / jnp.sum(xc * xc)


def update_tick(
    state: BWEState,
    params: BWEParams,
    estimate: jax.Array,        # [S] float32 — new TWCC/REMB estimate sample
    estimate_valid: jax.Array,  # [S] bool — a sample arrived this tick
    pkts_sent: jax.Array,       # [S] float32 — packets sent this tick
    nacks: jax.Array,           # [S] float32 — NACKs received this tick
):
    """One BWE tick over all subscribers.

    Returns (new_state, congested [S] bool, trend [S] int32,
    available_capacity [S] float32). `available_capacity` is the committed
    channel capacity the allocator should budget against
    (streamallocator.go handleSignalEstimate → allocateAllTracks).
    """
    # --- estimate ring update (only where a sample arrived) ---
    pos = state.ring_pos % WINDOW
    ring = jnp.where(
        estimate_valid[..., None],
        _scatter_ring(state.estimate_ring, pos, estimate),
        state.estimate_ring,
    )
    ring_pos = jnp.where(estimate_valid, state.ring_pos + 1, state.ring_pos)
    last_estimate = jnp.where(estimate_valid, estimate, state.last_estimate)

    # --- trend: slope of time-ordered ring ---
    # Rotation moved onto the WEIGHTS instead of the data: gathering the
    # ring per subscriber (take_along_axis) lowered to a TPU gather that
    # measured ~0.8 ms/tick at cfg4; rotating the constant 8-tap weight
    # vector via one-hot keeps everything elementwise and fused. The mean
    # is rotation-invariant.
    ranks = (
        jnp.arange(WINDOW, dtype=jnp.int32) - pos[..., None] - 1
    ) % WINDOW                                                   # [S, W]
    w_rot = jnp.sum(
        jax.nn.one_hot(ranks, WINDOW, dtype=jnp.float32)
        * _trend_weights()[None, :],
        axis=-1,
    )                                                            # [S, W]
    slope = jnp.sum(ring * w_rot, axis=-1)  # [S]
    mean = jnp.mean(ring, axis=-1)
    rel_slope = slope / jnp.maximum(mean, 1.0)
    trend = jnp.where(rel_slope < -0.02, -1, jnp.where(rel_slope > 0.02, 1, 0)).astype(jnp.int32)

    # --- nack ratio window ---
    nack_packets = state.nack_packets + pkts_sent
    nack_count = state.nack_count + nacks
    ratio = nack_count / jnp.maximum(nack_packets, 1.0)
    nack_bad = (nack_packets >= params.nack_window_min_packets) & (
        ratio > params.nack_ratio_threshold
    )

    # --- congestion state machine (channelobserver GetTrend semantics:
    # lowering estimate or high nack ratio ⇒ congested). A downtrend only
    # counts while samples are fresh: with no reports the window is stale
    # and must not pin the channel congested forever.
    ticks_since = jnp.where(estimate_valid, 0, state.ticks_since_sample + 1)
    congested = ((trend < 0) & (ticks_since < params.stale_ticks)) | nack_bad
    # Commit capacity on congestion onset; recover to estimate when clear.
    committed = jnp.where(
        congested,
        jnp.maximum(
            jnp.minimum(state.committed_channel_capacity, last_estimate),
            params.congested_min_estimate,
        ),
        last_estimate,
    )

    # Decay the nack window each tick (rolling window approximation).
    new_state = BWEState(
        estimate_ring=ring,
        ring_pos=ring_pos,
        last_estimate=last_estimate,
        nack_packets=nack_packets * 0.5,
        nack_count=nack_count * 0.5,
        congested=congested,
        committed_channel_capacity=committed,
        ticks_since_sample=ticks_since,
    )
    return new_state, congested, trend, committed


def _scatter_ring(ring: jax.Array, pos: jax.Array, value: jax.Array) -> jax.Array:
    """ring[..., pos] = value without dynamic slicing (one-hot mask)."""
    oh = jax.nn.one_hot(pos, ring.shape[-1], dtype=ring.dtype)
    return ring * (1.0 - oh) + oh * value[..., None]


# ---------------------------------------------------------------------------
# Send-side delay-based estimation (TWCC seat).
#
# Reference parity: the reference wires pion's cc.BandwidthEstimator (GCC)
# fed by transport-wide-cc feedback (pkg/rtc/transport.go:253-374) into the
# StreamAllocator (streamallocator.go:304-391 OnREMB/onTargetBitrateChange).
# Here the transport-wide sequence number is the sealed-frame counter the
# egress already stamps on every datagram (runtime/crypto.py layout); the
# host matches client feedback (runtime/udp.py TWCC frames) against its
# send-time ring and reduces each tick's feedback to THREE per-subscriber
# samples: mean delay-variation, acked receive rate, and validity. The
# estimator itself — an EMA'd queuing-delay gradient driving an AIMD rate,
# GCC's shape without the Kalman filter — then updates every subscriber in
# one elementwise pass per tick.
#
# Trust model (the reason this exists): allocation must not depend on
# client-volunteered REMB estimates. A client that sends no feedback at all
# while sealed sends are outstanding decays toward the floor (safe), and a
# client that acks honestly converges the budget to the real channel rate
# with no estimate samples ever sent.
# ---------------------------------------------------------------------------


class DelayBWEParams(NamedTuple):
    overuse_ms: float = 1.5        # EMA'd delay-variation above ⇒ overuse
    underuse_ms: float = -1.5      # below ⇒ draining; hold rate
    ema_alpha: float = 0.3
    beta: float = 0.85             # overuse: rate = beta × acked receive rate
    increase_per_s: float = 0.08   # multiplicative increase while clear
    min_rate_bps: float = 64_000.0
    max_rate_bps: float = 50e6
    fb_timeout_ticks: int = 50     # outstanding sends, no feedback ⇒ decay
    starve_decay: float = 0.97     # per-tick rate factor once starved


class DelayBWEState(NamedTuple):
    """Per-subscriber delay-estimator state; fields [..., S]."""

    slope_ema: jax.Array     # float32 — EMA of mean delay-variation (ms)
    rate_bps: jax.Array      # float32 — delay-based target rate
    ticks_no_fb: jax.Array   # int32 — ticks with sends but no feedback
    ever_fb: jax.Array       # bool — any feedback seen (activates the cap)


def delay_init_state(num_subscribers: int, initial_rate: float = 7_000_000.0) -> DelayBWEState:
    s = (num_subscribers,)
    return DelayBWEState(
        slope_ema=jnp.zeros(s, jnp.float32),
        rate_bps=jnp.full(s, initial_rate, jnp.float32),
        ticks_no_fb=jnp.zeros(s, jnp.int32),
        ever_fb=jnp.zeros(s, jnp.bool_),
    )


def delay_update_tick(
    state: DelayBWEState,
    params: DelayBWEParams,
    fb_delay_ms: jax.Array,   # [S] float32 — mean delay-variation this tick
    fb_recv_bps: jax.Array,   # [S] float32 — acked receive rate sample
    fb_valid: jax.Array,      # [S] bool — feedback arrived this tick
    fb_enabled: jax.Array,    # [S] bool — sub rides the sealed UDP path
    pkts_sent: jax.Array,     # [S] float32 — sends this tick
    tick_ms: jax.Array,       # scalar int32
):
    """Returns (new_state, rate_bps [S], overuse [S] bool, active [S] bool).

    `active` marks subscribers whose budget the delay rate should cap
    (sealed-path subscribers that have ever acked). WS-only subscribers
    never activate and keep the estimate-driven budget path.
    """
    ema = jnp.where(
        fb_valid,
        (1.0 - params.ema_alpha) * state.slope_ema + params.ema_alpha * fb_delay_ms,
        state.slope_ema,
    )
    overuse = ema > params.overuse_ms
    underuse = ema < params.underuse_ms
    tick_s = jnp.maximum(tick_ms.astype(jnp.float32), 1.0) / 1000.0
    rate_up = state.rate_bps * (1.0 + params.increase_per_s * tick_s)
    rate_down = params.beta * jnp.maximum(fb_recv_bps, params.min_rate_bps)
    rate = jnp.where(
        fb_valid,
        jnp.where(
            overuse,
            jnp.minimum(state.rate_bps, rate_down),
            jnp.where(underuse, state.rate_bps, rate_up),
        ),
        state.rate_bps,
    )
    # Silent-client guard: sealed sends outstanding but nothing acked.
    ticks_no_fb = jnp.where(
        fb_valid | ~fb_enabled,
        0,
        state.ticks_no_fb + (pkts_sent > 0).astype(jnp.int32),
    )
    starved = ticks_no_fb > params.fb_timeout_ticks
    rate = jnp.where(starved, rate * params.starve_decay, rate)
    rate = jnp.clip(rate, params.min_rate_bps, params.max_rate_bps)
    ever_fb = state.ever_fb | (fb_valid & fb_enabled)
    active = fb_enabled & (ever_fb | starved)
    new_state = DelayBWEState(
        slope_ema=ema,
        rate_bps=rate,
        ticks_no_fb=ticks_no_fb,
        ever_fb=ever_fb,
    )
    return new_state, rate, overuse & fb_enabled, active
