"""The client processes and the benchmark's parent never import JAX or the
server's runtime: a process that has touched JAX holds the chip."""

import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]


@pytest.mark.parametrize("module", ["benchmarks.client.worker", "benchmarks.client.wire",
                                    "benchmarks.run", "benchmarks.reference",
                                    "benchmarks.traffic"])
def test_imports_neither_jax_nor_the_server(module):
    code = (f"import sys, {module}\n"
            "bad = [m for m in sys.modules if m == 'jax' or m.startswith('jax.') "
            "or m.startswith('livekit_server_tpu')]\n"
            "print(bad); sys.exit(1 if bad else 0)")
    done = subprocess.run([sys.executable, "-c", code], cwd=ROOT, capture_output=True,
                          text=True, timeout=60)
    assert done.returncode == 0, done.stdout + done.stderr
