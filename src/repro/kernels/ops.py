"""jit'd public wrappers around the Pallas kernels.

Handle padding to tile boundaries and metric-name -> kernel-mode
translation (cosine pre-normalizes once so the kernel is a pure
dot+arccos).  The kernels compile to Mosaic on a TPU and run under the
Pallas interpreter on the CPU backend (``gmm_update.resolve_interpret``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .gmm_topb import gmm_topb_pallas
from .gmm_update import gmm_grouped_topb_pallas, gmm_update_select_pallas
from .pairwise import pairwise_pallas


def _round_up(x, m):
    return (x + m - 1) // m * m


def _metric_to_mode(metric_name: str):
    """-> (mode, needs_normalize)."""
    if metric_name in ("euclidean", "sqeuclidean", "dot"):
        return metric_name, False
    if metric_name == "cosine":
        return "cosine", True
    raise ValueError(f"no Pallas path for metric {metric_name!r}")


def _normalize(x):
    return x / jnp.maximum(jnp.linalg.norm(x, axis=-1, keepdims=True), 1e-30)


@functools.partial(jax.jit, static_argnames=("metric_name", "bm", "bn"))
def pairwise(x, y, metric_name: str = "sqeuclidean", bm: int = 256,
             bn: int = 256):
    """Distance matrix (m, n) with padding handled."""
    mode, norm = _metric_to_mode(metric_name)
    x = jnp.asarray(x, jnp.float32)
    y = jnp.asarray(y, jnp.float32)
    if norm:
        x, y = _normalize(x), _normalize(y)
    m, d = x.shape
    n, _ = y.shape
    bm_ = min(bm, _round_up(m, 8))
    bn_ = min(bn, _round_up(n, 8))
    mp, np_ = _round_up(m, bm_), _round_up(n, bn_)
    xp = jnp.pad(x, ((0, mp - m), (0, 0)))
    yp = jnp.pad(y, ((0, np_ - n), (0, 0)))
    out = pairwise_pallas(xp, yp, mode=mode, bm=bm_, bn=bn_)
    return out[:m, :n]


@functools.partial(jax.jit, static_argnames=("metric_name", "bn"))
def gmm_update_select(points, centers, min_in, mask, metric_name: str,
                      bn: int = 1024):
    """Fused GMM round on (n, d) points vs (b, d) centers.

    Returns (min_out (n,), argmax () int32, max ()).  Padded rows are masked
    out, so argmax/max are exact over the original n points.
    """
    mode, norm = _metric_to_mode(metric_name)
    points = jnp.asarray(points, jnp.float32)
    centers = jnp.atleast_2d(jnp.asarray(centers, jnp.float32))
    if norm:
        points, centers = _normalize(points), _normalize(centers)
    n, d = points.shape
    bn_ = min(bn, _round_up(n, 8))
    npad = _round_up(n, bn_)
    pp = jnp.pad(points, ((0, npad - n), (0, 0)))
    mi = jnp.pad(min_in, (0, npad - n), constant_values=jnp.inf)
    mk = jnp.pad(mask, (0, npad - n), constant_values=False)
    min_out, arg, mx = gmm_update_select_pallas(pp, centers, mi, mk,
                                                mode=mode, bn=bn_)
    return min_out[:n], arg, mx


@functools.partial(jax.jit, static_argnames=("metric_name", "p", "bn"))
def gmm_topb(points, centers, min_in, mask, metric_name: str,
             p: int = None, bn: int = 1024):
    """Fused batched GMM round on (n, d) points vs (b, d) centers.

    Returns (min_out (n,), cand_val (p,), cand_idx (p,)) — the exact global
    top-p of the updated masked min-distance field (``p`` defaults to b; the
    oversampled engines pass p=2b).  Padded rows are masked out, so the
    candidates always index the original n points.
    """
    mode, norm = _metric_to_mode(metric_name)
    points = jnp.asarray(points, jnp.float32)
    centers = jnp.atleast_2d(jnp.asarray(centers, jnp.float32))
    if norm:
        points, centers = _normalize(points), _normalize(centers)
    n, d = points.shape
    p = centers.shape[0] if p is None else p
    bn_ = max(min(bn, _round_up(n, 8)), p)
    npad = _round_up(n, bn_)
    pp = jnp.pad(points, ((0, npad - n), (0, 0)))
    mi = jnp.pad(min_in, (0, npad - n), constant_values=jnp.inf)
    mk = jnp.pad(mask, (0, npad - n), constant_values=False)
    min_out, vals, idxs = gmm_topb_pallas(pp, centers, mi, mk, mode=mode,
                                          bn=bn_, p=p)
    return min_out[:n], vals, jnp.minimum(idxs, n - 1)


@functools.partial(jax.jit, static_argnames=("metric_name", "b", "bn"))
def grouped_gmm_topb(points, centers, min_in, labels, metric_name: str,
                     b: int, bn: int = 1024):
    """Fused group-blocked batched GMM round (constrained subsystem).

    points (n, d), centers (m, bc, d), min_in (n,) (own-group running min),
    labels (n,) int32 in [0, m) -> (min_out (n,), cand_val (m, b),
    cand_idx (m, b)): one sweep serves all m per-group masks (see
    ``gmm_grouped_topb_pallas``).  Padded rows carry label -1, matching no
    group, so per-group candidates are exact over the original n points.
    """
    mode, norm = _metric_to_mode(metric_name)
    points = jnp.asarray(points, jnp.float32)
    centers = jnp.asarray(centers, jnp.float32)
    if norm:
        points, centers = _normalize(points), _normalize(centers)
    n, d = points.shape
    bn_ = max(min(bn, _round_up(n, 8)), b)
    npad = _round_up(n, bn_)
    pp = jnp.pad(points, ((0, npad - n), (0, 0)))
    mi = jnp.pad(min_in, (0, npad - n), constant_values=jnp.inf)
    lb = jnp.pad(jnp.asarray(labels, jnp.int32), (0, npad - n),
                 constant_values=-1)
    min_out, vals, idxs = gmm_grouped_topb_pallas(pp, centers, mi, lb,
                                                  mode=mode, bn=bn_, b=b)
    return min_out[:n], vals, jnp.minimum(idxs, n - 1)


def gmm_update(points, center, min_in, metric_name: str):
    """Running-min only (compat wrapper used by the lax GMM path)."""
    n = points.shape[0]
    mask = jnp.ones((n,), bool)
    min_out, _, _ = gmm_update_select(points, center, min_in, mask, metric_name)
    return min_out
