"""Transportation-cost matrix: pairwise euclidean distance between embeddings.

The paper's hotspot #2 (Table I / Fig. 7): ``M = cdist(vecs[sel], vecs)``.
On Xeon this vectorizes to AVX-512 FMA; on PIUMA it dominates (scalar cores).
On TPU the natural form is the matmul expansion
``|a - b|^2 = |a|^2 + |b|^2 - 2 a.b`` which routes the O(v_r * V * w) work
through the MXU instead of the VPU -- that is the hardware adaptation.
`repro.kernels.cdist` provides the Pallas-tiled version; this module is the
jnp implementation used as both the production fallback and the oracle's base.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def cdist_direct(a: jax.Array, b: jax.Array, *, squared: bool = False) -> jax.Array:
    """O(n*m*w) elementwise form: sqrt(sum((a_i - b_j)^2)). VPU-bound; oracle."""
    d2 = jnp.sum((a[:, None, :] - b[None, :, :]) ** 2, axis=-1)
    return d2 if squared else jnp.sqrt(d2)


def cdist_matmul(a: jax.Array, b: jax.Array, *, squared: bool = False) -> jax.Array:
    """MXU form: |a|^2 + |b|^2 - 2ab, clamped at 0 for fp round-off."""
    a2 = jnp.sum(a * a, axis=-1)[:, None]
    b2 = jnp.sum(b * b, axis=-1)[None, :]
    # HIGHEST: a TPU's default single bf16 pass loses the cancellation in
    # |a|^2 + |b|^2 - 2 a.b (f32 on the CPU either way)
    ab = jnp.matmul(a, b.T, precision=jax.lax.Precision.HIGHEST)
    d2 = jnp.maximum(a2 + b2 - 2.0 * ab, 0.0)
    return d2 if squared else jnp.sqrt(d2)


def cdist(a: jax.Array, b: jax.Array, *, squared: bool = False,
          method: str = "matmul") -> jax.Array:
    """Pairwise euclidean distance. a: (n, w), b: (m, w) -> (n, m)."""
    if method == "matmul":
        return cdist_matmul(a, b, squared=squared)
    if method == "direct":
        return cdist_direct(a, b, squared=squared)
    raise ValueError(f"unknown cdist method: {method!r}")
