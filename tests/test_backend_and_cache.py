"""The two rules every TPU process of this repo follows: no kernel entry
drops to its CPU reference on a TPU without raising (`ops/backend.py`),
and the persistent compile cache lives where JAX_COMPILATION_CACHE_DIR
says or else in one fixed directory of the checkout
(`utils/compile_cache.py`). The TPU is stood in for by answering
`jax.default_backend()`; nothing is compiled here."""

from __future__ import annotations

import subprocess
from pathlib import Path

import jax
import pytest

from livekit_server_tpu.ops.backend import want_pallas
from livekit_server_tpu.utils import compile_cache

REPO = Path(__file__).resolve().parents[1]


@pytest.fixture
def as_tpu(monkeypatch):
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")


@pytest.fixture
def cache_config(monkeypatch):
    """Record what `setup_compile_cache` sets instead of setting it."""
    updates = {}
    monkeypatch.setattr(jax.config, "update", updates.__setitem__)
    return updates


def test_want_pallas_off_the_tpu_leaves_the_choice_to_the_caller():
    assert want_pallas(None, False, "t") is False
    assert want_pallas(False, False, "t") is False
    assert want_pallas(None, True, "t") is False      # interpret: caller's `or`
    assert want_pallas(True, False, "t") is True


@pytest.mark.parametrize("use_pallas,interpret", [
    (False, False), (None, True), (True, True), (False, True),
])
def test_want_pallas_on_a_tpu_refuses_the_cpu_reference(as_tpu, use_pallas, interpret):
    with pytest.raises(RuntimeError, match="default backend is a TPU"):
        want_pallas(use_pallas, interpret, "selector.decide_rooms")


def test_want_pallas_on_a_tpu_means_the_kernel(as_tpu):
    assert want_pallas(None, False, "t") is True
    assert want_pallas(True, False, "t") is True


def test_kernel_entries_raise_on_a_tpu_when_asked_for_the_reference(as_tpu):
    import jax.numpy as jnp

    from livekit_server_tpu.ops import allocation

    z = jnp.zeros
    with pytest.raises(RuntimeError, match="allocate_budget_rooms"):
        allocation.allocate_budget_rooms(
            z((2, 2, 4, 4)), z((2, 2, 2), jnp.int32), z((2, 2, 2), jnp.int32),
            z((2, 2, 2), bool), z((2, 2)), use_pallas=False)


def test_cache_dir_from_the_environment_is_used_and_nothing_is_set(
        monkeypatch, cache_config, as_tpu):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/somewhere/else")
    assert compile_cache.setup_compile_cache() == "/somewhere/else"
    assert cache_config == {}


def test_cache_dir_unset_on_a_tpu_is_one_fixed_ignored_directory(
        monkeypatch, cache_config, as_tpu):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    want = str(REPO / ".jax_cache")
    assert compile_cache.setup_compile_cache() == want
    assert compile_cache.setup_compile_cache() == want     # no pid, time, hash
    assert cache_config == {"jax_compilation_cache_dir": want}
    ignored = subprocess.run(
        ["git", "check-ignore", "-q", ".jax_cache/x"], cwd=REPO, check=False)
    if (REPO / ".git").exists():
        assert ignored.returncode == 0
    else:
        assert ".jax_cache/" in (REPO / ".gitignore").read_text().split()


def test_no_cache_on_xla_cpu(monkeypatch, cache_config):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    assert compile_cache.setup_compile_cache() is None
    assert cache_config == {}
