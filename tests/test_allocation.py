"""Allocation algebra tests (reference: pkg/sfu/forwarder_test.go allocation cases)."""

import jax
import jax.numpy as jnp
import numpy as np

from livekit_server_tpu.ops import allocation as al


def _bitrates():
    # 2 tracks × 4 spatial × 4 temporal; only 3×2 layers populated for track0,
    # track1 is audio-like single layer.
    b = np.zeros((2, 4, 4), np.float32)
    b[0, 0, 0], b[0, 0, 1] = 150e3, 200e3
    b[0, 1, 0], b[0, 1, 1] = 500e3, 700e3
    b[0, 2, 0], b[0, 2, 1] = 1.5e6, 2.5e6
    b[1, 0, 0] = 32e3
    return jnp.asarray(b)


def test_optimal_layer_respects_caps():
    b = _bitrates()
    opt = al.optimal_layer(b, jnp.array([2, 3]), jnp.array([3, 3]))
    assert int(al.spatial_of(opt)[0]) == 2 and int(al.temporal_of(opt)[0]) == 1
    assert int(al.spatial_of(opt)[1]) == 0 and int(al.temporal_of(opt)[1]) == 0
    opt = al.optimal_layer(b, jnp.array([1, 3]), jnp.array([0, 3]))
    assert int(al.spatial_of(opt)[0]) == 1 and int(al.temporal_of(opt)[0]) == 0


def test_optimal_layer_none_available():
    b = jnp.zeros((1, 4, 4))
    opt = al.optimal_layer(b, jnp.array([3]), jnp.array([3]))
    assert int(opt[0]) == -1


def test_allocate_budget_rich_channel_gets_optimal():
    b = _bitrates()
    target, used, deficient = al.allocate_budget(
        b, jnp.array([3, 3]), jnp.array([3, 3]), jnp.array([False, False]), 10e6
    )
    assert int(al.spatial_of(target)[0]) == 2 and int(al.temporal_of(target)[0]) == 1
    assert int(target[1]) == 0
    assert not bool(deficient.any())
    assert abs(float(used) - (2.5e6 + 32e3)) < 1


def test_allocate_budget_constrained_downgrades():
    b = _bitrates()
    target, used, deficient = al.allocate_budget(
        b, jnp.array([3, 3]), jnp.array([3, 3]), jnp.array([False, False]), 800e3
    )
    # Track0 should land on a sub-optimal layer; track1 audio fits.
    assert bool(deficient[0])
    assert float(used) <= 800e3 + 1
    assert int(target[0]) >= 0  # minimal allocation guaranteed
    assert int(target[1]) == 0


def test_allocate_budget_starvation_pauses():
    b = _bitrates()
    target, used, deficient = al.allocate_budget(
        b, jnp.array([3, 3]), jnp.array([3, 3]), jnp.array([False, False]), 10e3
    )
    assert int(target[0]) == -1  # cannot afford even minimal video
    assert bool(deficient[0])


def test_allocate_budget_without_pause_keeps_the_lowest_layer():
    """`allow_pause=False` (config rtc.congestion_control.allow_pause, the
    reference's default): a budget that cannot pay for the minimal layer
    degrades the video to it and does not pause it; a muted track is still
    skipped, and a rich channel allocates as before."""
    b = _bitrates()
    caps, live = jnp.array([3, 3]), jnp.array([False, False])
    target, used, deficient = al.allocate_budget(
        b, caps, caps, live, 10e3, allow_pause=False)
    assert [int(t) for t in target] == [0, 0]       # lowest layer of each
    assert float(used) == 150e3 + 32e3              # over the 10 kbit/s budget
    assert bool(deficient[0]) and not bool(deficient[1])
    target, _, _ = al.allocate_budget(
        b, caps, caps, jnp.array([True, False]), 10e3, allow_pause=False)
    assert [int(t) for t in target] == [-1, 0]
    rich = al.allocate_budget(b, caps, caps, live, 10e6, allow_pause=False)
    want = al.allocate_budget(b, caps, caps, live, 10e6)
    assert all(np.array_equal(np.asarray(x), np.asarray(y))
               for x, y in zip(rich, want))


def test_allocate_budget_mute_skips():
    b = _bitrates()
    target, used, deficient = al.allocate_budget(
        b, jnp.array([3, 3]), jnp.array([3, 3]), jnp.array([True, False]), 10e6
    )
    assert int(target[0]) == -1
    assert not bool(deficient[0])
    assert abs(float(used) - 32e3) < 1


def test_next_higher():
    b = _bitrates()
    cur = jnp.array([al.flat_layer(0, 1), 0], jnp.int32)
    nxt, delta = al.next_higher(b, jnp.array([3, 3]), jnp.array([3, 3]), cur)
    assert int(al.spatial_of(nxt)[0]) == 1 and int(al.temporal_of(nxt)[0]) == 0
    assert abs(float(delta[0]) - (500e3 - 200e3)) < 1
    assert int(nxt[1]) == 0 and float(delta[1]) == 0  # no higher layer


def test_vmap_over_subscribers():
    b = _bitrates()
    budgets = jnp.array([10e6, 300e3], jnp.float32)
    f = jax.vmap(lambda bud: al.allocate_budget(
        b, jnp.array([3, 3]), jnp.array([3, 3]), jnp.array([False, False]), bud
    ))
    target, used, deficient = f(budgets)
    assert target.shape == (2, 2)
    assert not bool(deficient[0, 0]) and bool(deficient[1, 0])


def test_pallas_rooms_budget_matches_per_room():
    """The room-batched allocation kernel (production TPU path since the
    phase-2 hoist) is bit-equivalent to the per-room fallback."""
    rng = np.random.default_rng(13)
    for R, T, S in ((4, 5, 7), (6, 4, 33)):
        bit = (rng.random((R, T, 4, 4)) * 2e6
               * (rng.random((R, T, 4, 4)) > 0.3)).astype(np.float32)
        ms = rng.integers(-1, 4, (R, S, T)).astype(np.int32)
        mt = rng.integers(-1, 4, (R, S, T)).astype(np.int32)
        mu = rng.random((R, S, T)) < 0.2
        bud = (rng.random((R, S)) * 8e6).astype(np.float32)
        args = tuple(jnp.asarray(x) for x in (bit, ms, mt, mu, bud))
        for allow_pause in (True, False):
            t0, u0, d0 = al.allocate_budget_rooms(
                *args, use_pallas=False, allow_pause=allow_pause)
            t1, u1, d1 = al.allocate_budget_rooms(
                *args, interpret=True, allow_pause=allow_pause)
            assert np.array_equal(np.asarray(t0), np.asarray(t1))
            assert np.allclose(np.asarray(u0), np.asarray(u1), rtol=1e-5)
            assert np.array_equal(np.asarray(d0), np.asarray(d1))


def test_pallas_rooms_budget_edge_cases_match():
    """Kernel/fallback parity at the boundary conditions the random
    sweep rarely lands on: zero budget, every track muted, and a budget
    large enough to admit every top layer. These are the branches that
    drift silently when the two-pass greedy is edited in one place."""
    rng = np.random.default_rng(29)
    R, T, S = 3, 4, 8
    bit = (rng.random((R, T, 4, 4)) * 2e6).astype(np.float32)
    ms = np.full((R, S, T), 3, np.int32)
    mt = np.full((R, S, T), 3, np.int32)
    cases = [
        (np.zeros((R, S, T), bool), np.zeros((R, S), np.float32)),
        (np.ones((R, S, T), bool),
         (rng.random((R, S)) * 5e6).astype(np.float32)),
        (np.zeros((R, S, T), bool), np.full((R, S), 1e9, np.float32)),
    ]
    for mu, bud in cases:
        args = tuple(jnp.asarray(x) for x in (bit, ms, mt, mu, bud))
        for allow_pause in (True, False):
            t0, u0, d0 = al.allocate_budget_rooms(
                *args, use_pallas=False, allow_pause=allow_pause)
            t1, u1, d1 = al.allocate_budget_rooms(
                *args, interpret=True, allow_pause=allow_pause)
            assert np.array_equal(np.asarray(t0), np.asarray(t1))
            assert np.allclose(np.asarray(u0), np.asarray(u1), rtol=1e-5)
            assert np.array_equal(np.asarray(d0), np.asarray(d1))
