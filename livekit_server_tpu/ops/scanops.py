"""Small-axis prefix ops that avoid TPU's pathological scan lowerings.

`jnp.cumsum` lowers to `reduce-window` on TPU, and shift-add prefix sums
(via jnp.pad or concatenate) lower to pad/dynamic-update-slice chains —
at the media plane's tiny static axes (4 spatial layers, K ≤ 16 packet
slots) each measured milliseconds per tick for microseconds of work.
A contraction against an n×n triangular matrix fuses cleanly instead.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def cumsum_small(x: jnp.ndarray, axis: int) -> jnp.ndarray:
    """Inclusive prefix sum along a SMALL static axis as a triangular-
    matrix contraction: out_i = Σ_{j≤i} x_j.

    Precision: integer inputs contract in their own dtype — exact, and
    that covers the byte-count/packet-count sums this serves. Float
    inputs use Precision.HIGHEST (TPU's default matmul precision
    truncates float32 operands to bfloat16, which would visibly corrupt
    these sums) — but a matmul accumulates each prefix in one reduction
    order while `jnp.cumsum` folds sequentially, so general float
    results only match a sequential cumsum to within a few ulps, not
    bit-exactly. Float values exactly representable with headroom (e.g.
    byte counts cast to f32 below 2^24) still come out exact.
    """
    n = x.shape[axis]
    axis = axis % x.ndim
    if n == 1:
        return x
    xm = jnp.moveaxis(x, axis, -1)
    tri = jnp.tril(jnp.ones((n, n), x.dtype))          # [i, j≤i]
    if jnp.issubdtype(x.dtype, jnp.integer):
        ym = jnp.einsum("...j,ij->...i", xm, tri)
    else:
        ym = jnp.einsum(
            "...j,ij->...i", xm, tri,
            precision=jax.lax.Precision.HIGHEST,
        )
    return jnp.moveaxis(ym, -1, axis)

