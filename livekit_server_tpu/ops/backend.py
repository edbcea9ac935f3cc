"""Which form of a kernel a call takes: the Pallas kernel on a TPU, the
plain composition elsewhere — and never the latter on a TPU."""

from __future__ import annotations

import jax


def want_pallas(use_pallas: bool | None, interpret: bool, what: str) -> bool:
    """Resolve a kernel entry's `use_pallas` argument.

    None means "the kernel where there is a TPU". The plain composition
    and Pallas interpret mode are the CPU reference the tests compare
    the kernels with; asking for either while the default backend is a
    TPU raises, so nothing served from a chip runs them unnoticed.
    """
    on_tpu = jax.default_backend() == "tpu"
    if use_pallas is None:
        use_pallas = on_tpu
    if on_tpu and (interpret or not use_pallas):
        raise RuntimeError(
            f"{what}: the CPU composition / Pallas interpret mode was "
            f"requested (use_pallas={use_pallas}, interpret={interpret}) "
            "while the default backend is a TPU"
        )
    return use_pallas
