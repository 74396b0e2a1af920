"""Fused batched-GMM round: distance block + running min in a single VMEM
pass (the shared fold kernel of ``gmm_update``), then the TOP-P candidates
of the updated field.

This is the production-TPU form of the chunk-fused sweep in
``core/gmm.gmm_batched(chunk=...)``: per grid step, one (bn, d) point tile
meets a (b, d) center block on the MXU and the running min-distance update
happens in VMEM, so the (n, b) distance matrix never exists in HBM — one
point-set read per b centers.  Mosaic cannot lower ``lax.top_k``, so the
top-p runs in the jit wrapper, tile by tile over the (n,) field (4n bytes
re-read plus the n-byte mask; 1/d of the point sweep) and then merged —
exact, like the chunk-local top-k of the jnp sweep.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .gmm_update import fold_pallas, top_p


@functools.partial(jax.jit, static_argnames=("mode", "bn", "p", "interpret"))
def gmm_topb_pallas(points, centers, min_in, mask, *, mode: str = "euclidean",
                    bn: int = 1024, p: int = None, interpret=None):
    """Fused batched round.  points (n, d) [n % bn == 0], centers (b, d),
    min_in (n,), mask (n,) -> (min_out (n,), cand_val (p,), cand_idx (p,)).

    cand_* are the exact global top-p of the updated masked min-distance
    field.  ``p`` defaults to the center-block size b; the
    adaptive/oversampled engines pass p=2b to pull a candidate pool wider
    than the block from the same sweep.  ``interpret=None`` auto-selects per
    backend (see ``gmm_update.resolve_interpret``)."""
    n = points.shape[0]
    p = centers.shape[0] if p is None else p
    assert n % bn == 0 and n >= p, (n, bn, p)
    min_out = fold_pallas(points, centers, min_in, mode=mode, bn=bn,
                          interpret=interpret)
    vals, idxs = top_p(jnp.where(mask, min_out, -jnp.inf), p, bn)
    return min_out, vals, idxs


def gmm_topb_ref(points, centers, min_in, mask, mode: str = "euclidean",
                 p: int = None):
    """Pure-jnp oracle."""
    from .ref import pairwise_ref
    p = p if p is not None else centers.shape[0]
    d = pairwise_ref(points, centers, mode)
    new_min = jnp.minimum(min_in, jnp.min(d, axis=1))
    masked = jnp.where(mask, new_min, -jnp.inf)
    vals, idxs = jax.lax.top_k(masked, p)
    return new_min, vals, idxs.astype(jnp.int32)
