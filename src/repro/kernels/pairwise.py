"""Tiled pairwise-distance Pallas kernel.

The workhorse of the core-set stack: the SMM chunk filter, the final
sequential solvers and the measure evaluations all consume an ``(m, n)``
distance matrix.  On TPU the ``x @ yᵀ`` term is an MXU matmul (at
``Precision.HIGHEST``: the default rounds f32 through bf16); the norm
corrections and the elementwise transform (clamp/sqrt) are fused into the
same VMEM tile so the matrix is written to HBM exactly once (cosine's
arccos has no Mosaic lowering and runs as one elementwise pass after).  Both
norms are computed in-kernel — ``‖x‖²`` as a (bm, 1) column, ``‖y‖²`` as a
row sum transposed to (1, bn) — so no rank-1 block crosses the kernel
boundary.

Tiling: grid over (m/bm, n/bn); both point tiles keep the full feature dim
``d`` resident (embeddings here are 3–8192 wide — at bm=bn=256 and d=1024
fp32 that is 2×1 MB in + 0.25 MB out, comfortably inside the ~16 MB VMEM
budget of a v5e core).  MXU alignment wants bm, bn multiples of 128 and d a
multiple of 8; the ops wrapper pads.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .gmm_update import _HIGHEST, staged_interpret


def _transform(dot, x, y, mode):
    if mode in ("sqeuclidean", "euclidean"):
        xsq = jnp.sum(x * x, axis=1, keepdims=True)          # (bm, 1)
        ysq = jnp.sum(y * y, axis=1, keepdims=True).T        # (1, bn)
        d2 = jnp.maximum(xsq + ysq - 2.0 * dot, 0.0)
        return jnp.sqrt(d2) if mode == "euclidean" else d2
    if mode == "dot":
        return -dot
    if mode == "cosine":
        return dot            # Mosaic has no arccos: applied after the kernel
    raise ValueError(mode)


def _pairwise_kernel(x_ref, y_ref, o_ref, *, mode):
    x = x_ref[...]                       # (bm, d)
    y = y_ref[...]                       # (bn, d)
    dot = jax.lax.dot_general(
        x, y, (((1,), (1,)), ((), ())), precision=_HIGHEST,
        preferred_element_type=jnp.float32)          # (bm, bn) on the MXU
    o_ref[...] = _transform(dot, x, y, mode)


@functools.partial(jax.jit, static_argnames=("mode", "bm", "bn", "interpret"))
def pairwise_pallas(x, y, *, mode: str = "sqeuclidean", bm: int = 256,
                    bn: int = 256, interpret=None):
    """Distance matrix via pl.pallas_call.  Inputs must be pre-padded so that
    m % bm == 0 and n % bn == 0 (ops.py handles padding + unpadding).
    ``interpret=None`` auto-selects per backend (``resolve_interpret``)."""
    m, d = x.shape
    n, _ = y.shape
    assert m % bm == 0 and n % bn == 0, (m, n, bm, bn)
    out = pl.pallas_call(
        functools.partial(_pairwise_kernel, mode=mode),
        grid=(m // bm, n // bn),
        in_specs=[
            pl.BlockSpec((bm, d), lambda i, j: (i, 0)),
            pl.BlockSpec((bn, d), lambda i, j: (j, 0)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.float32),
        interpret=staged_interpret(interpret),
    )(x, y)
    return jnp.arccos(jnp.clip(out, -1.0, 1.0)) if mode == "cosine" else out
