"""Metric registry.

Every metric exposes three operations used throughout the core library:

* ``pairwise(x, y) -> (m, n)``  true distance matrix
* ``point_to_set(x, c) -> (n,)`` distances from every row of ``x`` to point ``c``
* ``prep(x)`` optional per-pointset precomputation (e.g. squared norms) that the
  fused GMM update reuses across iterations.

All distances are *true metric* distances (triangle inequality holds), which the
SMM threshold logic relies on.  ``sqeuclidean`` is exposed for callers that only
need ordering (GMM selection) — it is not a metric and must not be fed to SMM.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Callable, Optional

import jax
import jax.numpy as jnp


@dataclass(frozen=True)
class Metric:
    name: str
    pairwise: Callable[[jnp.ndarray, jnp.ndarray], jnp.ndarray]
    point_to_set: Callable[[jnp.ndarray, jnp.ndarray], jnp.ndarray]
    # True when pairwise obeys the triangle inequality (SMM requirement).
    is_metric: bool = True


def _sq_norms(x):
    return jnp.sum(x * x, axis=-1)


def _mm(a, b):
    # Full f32 cross terms: at the TPU's default precision an f32 matmul is
    # rounded through bf16, and the norm expansion below turns that rounding
    # into distance error.
    return jnp.matmul(a, b, precision=jax.lax.Precision.HIGHEST)


def _sqeuclidean_pairwise(x, y):
    # ||x-y||^2 = ||x||^2 + ||y||^2 - 2 x.y   (MXU-friendly form)
    xx = _sq_norms(x)[:, None]
    yy = _sq_norms(y)[None, :]
    xy = _mm(x, y.T)
    return jnp.maximum(xx + yy - 2.0 * xy, 0.0)


def _euclidean_pairwise(x, y):
    return jnp.sqrt(_sqeuclidean_pairwise(x, y))


def _euclidean_p2s(x, c):
    d2 = _sq_norms(x) + jnp.sum(c * c) - 2.0 * _mm(x, c)
    return jnp.sqrt(jnp.maximum(d2, 0.0))


def _sqeuclidean_p2s(x, c):
    d2 = _sq_norms(x) + jnp.sum(c * c) - 2.0 * _mm(x, c)
    return jnp.maximum(d2, 0.0)


def _cosine_pairwise(x, y):
    # arccos of cosine similarity -- the paper's distance for musiXmatch (§7).
    xn = x / jnp.maximum(jnp.linalg.norm(x, axis=-1, keepdims=True), 1e-30)
    yn = y / jnp.maximum(jnp.linalg.norm(y, axis=-1, keepdims=True), 1e-30)
    sim = jnp.clip(_mm(xn, yn.T), -1.0, 1.0)
    return jnp.arccos(sim)


def _cosine_p2s(x, c):
    xn = x / jnp.maximum(jnp.linalg.norm(x, axis=-1, keepdims=True), 1e-30)
    cn = c / jnp.maximum(jnp.linalg.norm(c), 1e-30)
    sim = jnp.clip(_mm(xn, cn), -1.0, 1.0)
    return jnp.arccos(sim)


def _manhattan_pairwise(x, y):
    return jnp.sum(jnp.abs(x[:, None, :] - y[None, :, :]), axis=-1)


def _manhattan_p2s(x, c):
    return jnp.sum(jnp.abs(x - c[None, :]), axis=-1)


_REGISTRY = {
    "euclidean": Metric("euclidean", _euclidean_pairwise, _euclidean_p2s),
    "sqeuclidean": Metric(
        "sqeuclidean", _sqeuclidean_pairwise, _sqeuclidean_p2s, is_metric=False
    ),
    "cosine": Metric("cosine", _cosine_pairwise, _cosine_p2s),
    "manhattan": Metric("manhattan", _manhattan_pairwise, _manhattan_p2s),
}


def get_metric(name) -> Metric:
    if isinstance(name, Metric):
        return name
    try:
        return _REGISTRY[name]
    except KeyError:
        raise KeyError(f"unknown metric {name!r}; have {sorted(_REGISTRY)}")


def register_metric(metric: Metric) -> None:
    _REGISTRY[metric.name] = metric
