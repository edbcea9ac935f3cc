"""Test harness configuration.

Tests run on the CPU, on eight virtual devices, so multi-chip sharding is
exercised without TPU hardware: JAX_PLATFORMS and XLA_FLAGS are set here,
before JAX is imported. The chip is reached through `chip_smoke.py`, never
through the tests; `tests/test_tpu_lowering.py` compiles for a described
chip from inside its own fixture.
"""

import os

flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

os.environ.setdefault("JAX_PLATFORMS", "cpu")

# The JAX persistent compilation cache is deliberately NOT enabled here.
# It was, once, to amortize the media-plane tick's compile across runs —
# and it produced the suite's nastiest flake family: on XLA:CPU, a cache
# entry written by a clean PASSING run could deserialize into a silently
# miscompiled executable on the next run. The bad executable scribbled
# rate-like garbage into state.ctrl tensors (constants like max_spatial
# read back as 163816.0) of rooms the test never touched; the cross-room
# allocator reads those rows, so forwarding wedged for tens to hundreds
# of ticks with bit-identical inputs, differently on every run. A cold
# compile in each process is slower but correct. If someone re-enables
# the cache (JAX_COMPILATION_CACHE_DIR), unexplained forwarding wedges
# mean: delete the cache dir before debugging the model.

# Minimal async-test support (pytest-asyncio isn't in this image): any
# `async def test_*` runs under asyncio.run, `@pytest.mark.asyncio` or not.
import asyncio  # noqa: E402
import inspect  # noqa: E402

# Per-test ceiling (seconds) for async tests. The whole tier-1 suite must
# fit one wall-clock budget, so a single wedged await must surface as ONE
# failing test, not eat the entire run: asyncio.wait_for cancels the test
# coroutine (its finally blocks still run teardown) and asyncio.run then
# reaps whatever tasks the test leaked. No timing-sensitive test should
# come anywhere near this — it is a hang backstop, not a perf budget.
ASYNC_TEST_TIMEOUT_S = float(os.environ.get("LK_TEST_TIMEOUT_S", "180"))


def pytest_pyfunc_call(pyfuncitem):
    fn = pyfuncitem.obj
    if inspect.iscoroutinefunction(fn):
        kwargs = {
            name: pyfuncitem.funcargs[name]
            for name in pyfuncitem._fixtureinfo.argnames
        }
        asyncio.run(asyncio.wait_for(fn(**kwargs), ASYNC_TEST_TIMEOUT_S))
        return True
    return None


def pytest_configure(config):
    config.addinivalue_line("markers", "asyncio: run test under asyncio.run")


def free_port(kind=None) -> int:
    """One-shot ephemeral port (the shared bind-port-0 idiom). Pass
    socket.SOCK_DGRAM when the port will be bound for UDP — a TCP-probed
    port can still be busy on the UDP side."""
    import socket

    s = socket.socket(socket.AF_INET, kind or socket.SOCK_STREAM)
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port
