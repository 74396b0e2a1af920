"""Pure-jnp oracles for the Pallas kernels (the ``ref`` side of every
kernel-vs-reference allclose test), at full f32 matmul precision."""
from __future__ import annotations

import jax
import jax.numpy as jnp


def _sq(x):
    return jnp.sum(x * x, axis=-1)


def _mm(x, y):
    return jnp.matmul(x, y.T, precision=jax.lax.Precision.HIGHEST)


def pairwise_ref(x, y, mode: str = "sqeuclidean"):
    """Distance matrix (m, n).

    modes: sqeuclidean | euclidean | dot (similarity, negated so that larger
    = farther is monotone with distance) | cosine (arccos of cosine sim —
    inputs are expected pre-normalized by the ops wrapper).
    """
    if mode in ("sqeuclidean", "euclidean"):
        d2 = _sq(x)[:, None] + _sq(y)[None, :] - 2.0 * _mm(x, y)
        d2 = jnp.maximum(d2, 0.0)
        return jnp.sqrt(d2) if mode == "euclidean" else d2
    if mode == "dot":
        return -_mm(x, y)
    if mode == "cosine":
        sim = jnp.clip(_mm(x, y), -1.0, 1.0)
        return jnp.arccos(sim)
    raise ValueError(mode)


def gmm_update_select_ref(points, centers, min_in, mask, mode: str = "euclidean"):
    """Fused GMM round: distance of every point to the (block of) new center(s),
    running min against ``min_in``, and the masked global max + argmax.

    Returns (min_out (n,), argmax () int32, max ()).
    """
    d = pairwise_ref(points, centers, mode)          # (n, b)
    d = jnp.min(d, axis=1)                           # (n,)
    min_out = jnp.minimum(min_in, d)
    masked = jnp.where(mask, min_out, -jnp.inf)
    return min_out, jnp.argmax(masked).astype(jnp.int32), jnp.max(masked)
