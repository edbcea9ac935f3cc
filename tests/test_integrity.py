"""State-integrity plane: checksum codec units, audit-kernel rules,
seeded end-to-end bitflip chaos (detect → quarantine → row repair),
repair-storm escalation to a supervisor restart, and checkpoint
generation fallback on corruption."""

from __future__ import annotations

import asyncio

import jax.numpy as jnp
import numpy as np
import pytest

from livekit_server_tpu.models import plane
from livekit_server_tpu.runtime import (
    FaultInjector,
    PlaneRuntime,
    PlaneSupervisor,
)
from livekit_server_tpu.runtime.faultinject import FaultSpec, _replace_leaf
from livekit_server_tpu.runtime.ingest import PacketIn
from livekit_server_tpu.runtime.integrity import (
    AUDIT_RULES,
    BIT_BOUNDS,
    BIT_CTRL,
    BIT_CURSOR,
    BIT_NONFINITE,
    BIT_RANGE,
    IntegrityMonitor,
    audit_plane,
    init_mirror,
)
from livekit_server_tpu.utils import checksum
from livekit_server_tpu.utils.backoff import BackoffPolicy
from livekit_server_tpu.utils.checksum import ChecksumError


def make_rt(rooms: int = 3) -> PlaneRuntime:
    """Small plane with one published audio track + one subscriber per
    room (audio-only keeps selector rows inert, so injected corruption
    there persists until the audit sees it)."""
    dims = plane.PlaneDims(rooms=rooms, tracks=4, pkts=4, subs=4)
    rt = PlaneRuntime(dims, tick_ms=10)
    for room in range(rooms):
        rt.set_track(room, 0, published=True, is_video=False)
        rt.set_subscription(room, 0, 1, subscribed=True)
    return rt


def push_audio(rt: PlaneRuntime, rooms, i: int) -> None:
    for room in rooms:
        rt.ingest.push(PacketIn(room=room, track=0, sn=(1000 + i) & 0xFFFF,
                                ts=960 * i, size=50, payload=b"a"))


def poison(rt: PlaneRuntime, path: str, room: int, value) -> None:
    """Overwrite one room's row of a device-state leaf in place — the
    hand-rolled corruption the audit rules are unit-tested against."""
    leaf = rt.state
    for part in path.split("."):
        leaf = getattr(leaf, part)
    rt.state = _replace_leaf(rt.state, path, leaf.at[room].set(value))


def audit_once(rt: PlaneRuntime):
    mask, counts, _ = audit_plane(rt.state, init_mirror(rt.state))
    return np.asarray(mask), np.asarray(counts)


async def until(cond, timeout: float = 60.0, msg: str = "condition"):
    deadline = asyncio.get_running_loop().time() + timeout
    while not cond():
        assert asyncio.get_running_loop().time() < deadline, \
            f"timed out waiting for {msg}"
        await asyncio.sleep(0.01)


# -- checksum codec ----------------------------------------------------------

def test_frame_roundtrip():
    payload = b"media-plane checkpoint bytes" * 7
    frame = checksum.encode_frame(payload)
    assert frame[:4] == checksum.MAGIC
    assert len(frame) == checksum.HEADER_SIZE + len(payload)
    assert checksum.decode_frame(frame) == payload
    assert checksum.decode_frame_b64(checksum.encode_frame_b64(payload)) == payload


def test_frame_tamper_detected():
    fails0 = checksum.CodecStats.verify_failures
    flipped = bytearray(checksum.encode_frame(b"x" * 100))
    flipped[checksum.HEADER_SIZE + 11] ^= 0x01
    with pytest.raises(ChecksumError):
        checksum.decode_frame(bytes(flipped))          # CRC mismatch
    with pytest.raises(ChecksumError):
        checksum.decode_frame(checksum.encode_frame(b"abc")[:-1])  # short
    with pytest.raises(ChecksumError):
        checksum.decode_frame(b"NOPE" + checksum.encode_frame(b"abc")[4:])
    with pytest.raises(ChecksumError):
        checksum.decode_frame(b"\x00" * 5)             # truncated header
    with pytest.raises(ChecksumError):
        checksum.decode_frame_b64("!!! not base64 !!!")
    assert checksum.CodecStats.verify_failures == fails0 + 5


def test_frame_unknown_version_rejected():
    frame = checksum.encode_frame(b"abc")
    bad = frame[:4] + b"\x00\x63" + frame[6:]
    with pytest.raises(ChecksumError):
        checksum.decode_frame(bad)


def test_full_snapshot_codec_roundtrip():
    rt = make_rt(rooms=2)
    snap = rt.snapshot()
    blob = rt.encode_snapshot(snap)
    back = rt.decode_snapshot(blob)
    assert back["tick_index"] == snap["tick_index"]
    assert len(back["arrays"]) == len(snap["arrays"])
    assert len(back["munger"]) == len(snap["munger"])
    for a, b in zip(snap["arrays"], back["arrays"]):
        np.testing.assert_array_equal(np.asarray(a), b)
    # One flipped payload byte fails verification BEFORE np.load runs.
    tampered = bytearray(blob)
    tampered[checksum.HEADER_SIZE + 7] ^= 0xFF
    with pytest.raises(ChecksumError):
        rt.decode_snapshot(bytes(tampered))


def test_room_snapshot_codec_rejects_tamper():
    rt = make_rt(rooms=2)
    payload = rt.encode_room_snapshot(rt.snapshot_room(0))
    pos = 40
    repl = "A" if payload[pos] != "A" else "B"
    with pytest.raises(ChecksumError):
        rt.decode_room_snapshot(payload[:pos] + repl + payload[pos + 1:])


# -- audit kernel rules ------------------------------------------------------

def test_audit_clean_state():
    mask, counts = audit_once(make_rt())
    assert not mask.any()
    assert not counts.any()


def test_audit_rules_flag_expected_bits():
    rt = make_rt(rooms=5)
    poison(rt, "audio_state.smoothed_level", 0, jnp.nan)
    poison(rt, "temporal_bytes", 1, 1e35)          # finite but absurd
    poison(rt, "ctrl.max_spatial", 2, 7)
    poison(rt, "sel.current_spatial", 3, 99)
    poison(rt, "bwe_state.ring_pos", 4, -3)
    mask, counts = audit_once(rt)
    assert mask[0] & BIT_NONFINITE
    assert mask[1] & BIT_RANGE
    assert mask[2] & BIT_CTRL
    assert mask[3] & BIT_BOUNDS
    assert mask[4] & BIT_BOUNDS
    assert int(counts[AUDIT_RULES.index("nonfinite")]) == 1
    assert int(counts[AUDIT_RULES.index("bounds")]) == 2


def test_audit_cursor_regression_vs_legit_reset():
    rt = make_rt(rooms=2)
    s = rt.state.stats
    rt.state = rt.state._replace(stats=s._replace(
        started=s.started.at[0, 0].set(True),
        first_sn=s.first_sn.at[0, 0].set(17),
        highest_sn=s.highest_sn.at[0, 0].set(100),
    ))
    mirror = init_mirror(rt.state)
    regressed = mirror._replace(
        started=mirror.started.at[0, 0].set(True),
        first_sn=mirror.first_sn.at[0, 0].set(17),
        ext_sn=mirror.ext_sn.at[0, 0].set(200),    # cursor went backwards
    )
    mask, _, _ = audit_plane(rt.state, regressed)
    assert np.asarray(mask)[0] & BIT_CURSOR
    # Same regression but the stream identity changed (new first_sn):
    # that is a legitimate reset, not corruption.
    reset = regressed._replace(first_sn=regressed.first_sn.at[0, 0].set(18))
    mask, _, _ = audit_plane(rt.state, reset)
    assert not np.asarray(mask).any()


def test_audit_sn_wrap_is_monotonic():
    rt = make_rt(rooms=1)
    s = rt.state.stats
    # Post-wrap: highest_sn rewound 65530 -> 5 but sn_cycles advanced.
    rt.state = rt.state._replace(stats=s._replace(
        started=s.started.at[0, 0].set(True),
        first_sn=s.first_sn.at[0, 0].set(3),
        highest_sn=s.highest_sn.at[0, 0].set(5),
        sn_cycles=s.sn_cycles.at[0, 0].set(1),
    ))
    mirror = init_mirror(rt.state)._replace(
        started=rt.state.stats.started,
        first_sn=rt.state.stats.first_sn,
        ext_sn=jnp.zeros_like(rt.state.stats.highest_sn).at[0, 0].set(65530),
    )
    mask, _, _ = audit_plane(rt.state, mirror)
    assert not np.asarray(mask).any()


# -- end-to-end bitflip chaos ------------------------------------------------

async def _bitflip_scenario() -> dict:
    """Seeded silent-data-corruption drill: a bitflip lands in room 0's
    selector row at tick 5; the audit (cadence 4) must catch it at tick
    8, quarantine the room, and row-repair it from the checksummed
    checkpoint — while rooms 1 and 2 never drop an audio tick."""
    rt = make_rt(rooms=3)
    for i in range(2):
        push_audio(rt, range(3), i)
        await rt.step_once()
    async with rt.state_lock:
        snap = rt.snapshot()
    blob = rt.encode_snapshot(snap)   # checksummed at rest, like the sup ring
    mon = IntegrityMonitor(rt, audit_every_ticks=4, max_row_repairs=3,
                           storm_threshold=4)
    mon.snapshot_provider = lambda: rt.decode_snapshot(blob)
    escalations: list[str] = []
    mon.escalate_cb = escalations.append
    rt.integrity = mon
    # Target the BWE ring cursor: the tick only advances it on estimate
    # samples (none in an audio-only room), so the corruption persists
    # until the audit sees it, and ANY bit-30 flip lands out of bounds.
    rt.fault = FaultInjector(FaultSpec(
        seed=7, bitflip_tick=5, bitflip_room=0,
        bitflip_leaf="bwe_state.ring_pos", bitflip_bit=30, bitflip_count=2,
    ))
    witness_ok = True
    detection_tick = None
    repair_tick = None
    quarantined_seen = False
    for i in range(2, 14):
        push_audio(rt, range(3), i)
        res = await rt.step_once()
        if {p.room for p in res.egress} < {1, 2}:
            witness_ok = False                 # a witness room dropped a tick
        # Same-tick repair releases quarantine before step_once returns;
        # the monotonic counter proves the victim passed through it.
        quarantined_seen = quarantined_seen or mon.rows_quarantined > 0
        if detection_tick is None and mon.violations_total:
            detection_tick = mon.last_audit_tick
        if repair_tick is None and mon.rows_repaired:
            repair_tick = res.tick_index
    return {
        "bitflips": rt.fault.stats.bitflips,
        "detection_tick": detection_tick,
        "repair_tick": repair_tick,
        "quarantined_seen": quarantined_seen,
        "repaired": mon.rows_repaired,
        "escalations": len(escalations),
        "quarantined_now": sorted(mon.quarantined),
        "witness_ok": witness_ok,
        "ring_max": int(np.asarray(rt.state.bwe_state.ring_pos).max()),
        "rule_hits": dict(mon.rule_violations),
    }


async def test_bitflip_detected_quarantined_and_row_repaired():
    r = await _bitflip_scenario()
    assert r["bitflips"] == 2
    # Flip at tick 5, audit cadence 4: caught at tick 8 — within one window.
    assert r["detection_tick"] == 8
    assert r["quarantined_seen"]
    assert r["repaired"] == 1 and r["repair_tick"] == 8
    assert r["escalations"] == 0              # row repair, no full restart
    assert r["quarantined_now"] == []         # victim released after repair
    assert r["witness_ok"]                    # zero dropped witness ticks
    from livekit_server_tpu.ops import bwe
    assert r["ring_max"] < bwe.WINDOW         # corruption actually gone
    assert r["rule_hits"]["bounds"] >= 1


async def test_bitflip_chaos_is_deterministic():
    """Same seed → identical detection tick and repair path, twice."""
    assert await _bitflip_scenario() == await _bitflip_scenario()


# -- repair ladder escalation ------------------------------------------------

async def test_unrepairable_row_escalates_exactly_once():
    rt = make_rt(rooms=3)
    mon = IntegrityMonitor(rt, audit_every_ticks=1, max_row_repairs=2,
                           storm_threshold=4)
    reasons: list[str] = []
    mon.escalate_cb = reasons.append
    mon.snapshot_provider = lambda: None      # no verified checkpoint at all
    rt.integrity = mon
    poison(rt, "bwe_state.ring_pos", 1, 77)
    for i in range(4):
        push_audio(rt, range(3), i)
        await rt.step_once()
    assert mon.repair_failures >= 1
    assert len(reasons) == 1                  # epoch guard: one escalation
    assert 1 in mon.quarantined               # stays muted while suspect


async def test_repair_storm_escalates_to_supervisor_restart_once():
    rt = make_rt(rooms=6)
    for i in range(2):
        push_audio(rt, range(6), i)
        await rt.step_once()
    sup = PlaneSupervisor(
        rt, tick_deadline_s=5.0, check_interval_s=0.02,
        checkpoint_interval_s=60.0, max_restarts=5,
        backoff=BackoffPolicy(base=0.01, max_delay=0.05),
    )
    await sup.checkpoint_now()                # the (clean) restart seed
    mon = IntegrityMonitor(rt, audit_every_ticks=1, storm_threshold=2)
    mon.snapshot_provider = sup.last_good_snapshot
    mon.escalate_cb = sup.request_restart
    rt.integrity = mon
    for room in range(4):                     # 4 rooms > storm threshold 2
        poison(rt, "bwe_state.ring_pos", room, 77)
    rt.start()
    sup.start()
    try:
        await until(lambda: sup.restart_causes.get("integrity", 0) >= 1,
                    msg="integrity restart")
        base = rt.stats["ticks"]
        await until(lambda: rt.stats["ticks"] >= base + 5,
                    msg="post-restart ticks")
        assert sup.restart_causes["integrity"] == 1
        assert mon.escalations == 1
        assert not mon.quarantined            # on_full_restore cleared it
        from livekit_server_tpu.ops import bwe
        async with rt.state_lock:             # the running tick donates it
            ring_max = int(np.asarray(rt.state.bwe_state.ring_pos).max())
        assert ring_max < bwe.WINDOW          # restored state is clean
        assert not sup.gave_up
    finally:
        await sup.stop()
        await rt.stop()


# -- checkpoint generations --------------------------------------------------

async def test_corrupt_checkpoint_falls_back_one_generation():
    rt = make_rt(rooms=2)
    push_audio(rt, range(2), 0)
    await rt.step_once()
    sup = PlaneSupervisor(rt, checkpoint_interval_s=60.0)
    await sup.checkpoint_now()                        # older, clean
    older_tick = sup.last_snapshot["tick_index"]
    for i in range(1, 3):
        push_audio(rt, range(2), i)
        await rt.step_once()
    await sup.checkpoint_now()                        # newest
    assert sup.last_snapshot["tick_index"] > older_tick
    flipped = bytearray(sup._gens[0])
    flipped[checksum.HEADER_SIZE + 5] ^= 0xFF         # rot the newest gen
    sup._gens[0] = bytes(flipped)
    snap = sup.last_good_snapshot()
    assert snap is not None
    assert snap["tick_index"] == older_tick           # fell back exactly one
    assert sup.ckpt_fallbacks == 1
    # Restore-from-checkpoint walks the same ladder.
    assert await sup._restore_from_checkpoint()
    assert rt.tick_index == older_tick
    assert sup.ckpt_fallbacks == 2


async def test_corrupt_ckpt_fault_seam():
    rt = make_rt(rooms=2)
    push_audio(rt, range(2), 0)
    await rt.step_once()
    sup = PlaneSupervisor(rt, checkpoint_interval_s=60.0)
    await sup.checkpoint_now()                        # clean (no fault yet)
    clean_tick = sup.last_snapshot["tick_index"]
    rt.fault = FaultInjector(FaultSpec(corrupt_ckpt_every=1))
    push_audio(rt, range(2), 1)
    await rt.step_once()
    await sup.checkpoint_now()                        # damaged at the seam
    assert rt.fault.stats.ckpt_corrupted == 1
    snap = sup.last_good_snapshot()
    assert snap is not None and snap["tick_index"] == clean_tick
    assert sup.ckpt_fallbacks == 1


async def test_generation_ring_keeps_k_checkpoints():
    rt = make_rt(rooms=2)
    sup = PlaneSupervisor(rt, checkpoint_interval_s=60.0, ckpt_generations=3)
    for _ in range(5):
        await sup.checkpoint_now()
    assert len(sup._gens) == 3


# -- restore-path hardening --------------------------------------------------

async def test_repair_rejects_mismatched_snapshot():
    rt = make_rt(rooms=2)
    push_audio(rt, range(2), 0)
    await rt.step_once()
    async with rt.state_lock:
        snap = rt.snapshot()
    row = rt.row_snapshot_from_full(snap, 0)
    async with rt.state_lock:
        with pytest.raises(ValueError, match="plane versions differ"):
            rt.repair_room_row(0, {"arrays": row["arrays"][:-1]})
        bad_shape = [np.zeros((9, 9, 9), np.float32)] + row["arrays"][1:]
        with pytest.raises(ValueError, match="row shape"):
            rt.repair_room_row(0, {"arrays": bad_shape})
        bad_dtype = list(row["arrays"])
        bad_dtype[0] = np.asarray(bad_dtype[0]).astype(np.complex64)
        with pytest.raises(ValueError, match="dtype"):
            rt.repair_room_row(0, {"arrays": bad_dtype})
    # A good row snapshot is still accepted after the rejections.
    async with rt.state_lock:
        rt.repair_room_row(0, row)


async def test_full_restore_rejects_wrong_plane():
    rt = make_rt(rooms=2)
    other = make_rt(rooms=3)                  # different [R] leading axis
    snap = other.snapshot()
    async with rt.state_lock:
        with pytest.raises(ValueError):
            rt.restore(snap)


# -- audit overhead ----------------------------------------------------------

@pytest.mark.slow
async def test_audit_overhead_under_5_percent():
    """At bench-ish dims on the default cadence, the audit's share of
    total tick wall time stays under 5%."""
    dims = plane.PlaneDims(rooms=64, tracks=8, pkts=8, subs=16)
    rt = PlaneRuntime(dims, tick_ms=10)
    for room in range(dims.rooms):
        rt.set_track(room, 0, published=True, is_video=False)
        rt.set_subscription(room, 0, 1, subscribed=True)
    mon = IntegrityMonitor(rt, audit_every_ticks=16)
    rt.integrity = mon
    for i in range(3):                        # compile tick
        push_audio(rt, range(dims.rooms), i)
        await rt.step_once()
    # Compile + warm the audit kernel off the clock too.
    mon.maybe_audit(0)
    mon.audit_s = 0.0
    t_base = rt.stats["stage_s"] + rt.stats["device_s"] + rt.stats["fanout_s"]
    for i in range(3, 67):
        push_audio(rt, range(dims.rooms), i)
        await rt.step_once()
    total = (rt.stats["stage_s"] + rt.stats["device_s"]
             + rt.stats["fanout_s"]) - t_base
    assert mon.audits >= 4
    assert mon.audit_s < 0.05 * total, \
        f"audit {mon.audit_s:.4f}s is >=5% of {total:.4f}s tick time"
