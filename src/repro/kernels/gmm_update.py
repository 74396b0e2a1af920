"""Fused GMM-round Pallas kernels.

One GMM round = "distance from every point to the newest center block,
running min with the incumbent distances, then a selection over the updated
field".  The kernels here perform the distance + running-min fold in a single
sweep: each grid step loads one (bn, d) point tile, hits the MXU for
``c @ xᵀ`` against a *block* of ``b`` centers, reduces over centers and
writes the tile's running min.  The selection over the (n,) field — an
argmax, a top-p, or a per-group top-b — runs in the jit'd wrapper.

TPU layout: every per-point vector crosses the kernel boundary as a
lane-dense ``(1, n)`` row (XLA keeps it ``T(1,128)``, no padding) and the
distance tile is transposed to ``(b, bn)`` so that the min over centers
lands on that row directly; norms are computed in-kernel instead of
arriving as rank-1 blocks.  Mosaic has no
lowering for ``lax.top_k`` (nor ``arccos``), so the selection cannot live in
the kernel body: the wrapper's tile-wise top-p (``top_p``) reads the (n,)
field once more (4n bytes, plus n mask bytes), which is 1/d of the point
sweep.  The grouped wrapper takes one top-b per group: each of the m passes
reads the field and the labels and writes a masked copy, about 12·m·n
bytes, which at m=16, d=3 is 16x the 12n-byte point read.

All distance matmuls run at ``Precision.HIGHEST``: at the TPU's default
precision an f32 matmul is rounded through bf16, and the
``‖x‖² + ‖c‖² − 2x·c`` expansion turns that rounding into distance error.

Arithmetic intensity of a round is ~2·b·d FLOPs per 4·d bytes of point row,
i.e. memory-bound at small b — why the single-sweep fusion and the ``b>1``
center blocking of the batched engine are the right TPU shape for the
paper's hot loop.
"""
from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.obs.trace import count as _count

# The one backend with a compiled Pallas lowering (Mosaic) in this
# installation.  Elsewhere (the CPU test backend) the kernel bodies run under
# the Pallas interpreter, which is exact but slow.
_COMPILED_BACKENDS = ("tpu",)

_HIGHEST = jax.lax.Precision.HIGHEST


def resolve_interpret(interpret=None) -> bool:
    """Resolve the ``interpret`` knob for a Pallas kernel.

    ``None`` (the default everywhere) compiles on a TPU and interprets
    elsewhere.  The environment variable ``REPRO_PALLAS_INTERPRET`` (``1`` /
    ``0``) overrides the auto-selection; ``0`` forces compilation (for
    ahead-of-time compiles against a described TPU from a CPU process).  An
    explicit boolean wins over both.  The interpreter never runs on a TPU:
    asking for it there raises.  Resolution happens at trace time.
    """
    if interpret is None:
        env = os.environ.get("REPRO_PALLAS_INTERPRET")
        if env is not None:
            interpret = env.strip().lower() not in ("0", "false", "no", "off")
    on_chip = jax.default_backend() in _COMPILED_BACKENDS
    if interpret is None:
        return not on_chip
    if interpret and on_chip:
        raise ValueError(
            "the Pallas interpreter does not run on a TPU: unset "
            "REPRO_PALLAS_INTERPRET (or pass interpret=None/False) so the "
            "kernels compile")
    return bool(interpret)


def staged_interpret(interpret=None) -> bool:
    """``resolve_interpret`` for a kernel being staged into a program: one
    that is compiled for the device, not interpreted, bumps the active
    trace's ``pallas_compiled`` counter."""
    interpret = resolve_interpret(interpret)
    if not interpret:
        _count("pallas_compiled")
    return interpret


def _distance(dot, x, c, mode):
    """Distance tile (bn, b) from the cross term ``dot = x @ cᵀ``.

    ``cosine`` (inputs pre-normalized) yields the negated similarity: Mosaic
    has no ``arccos`` lowering, so ``_angle`` applies it after the kernel —
    arccos is decreasing, so a min over −similarity is a min over angles."""
    if mode in ("sqeuclidean", "euclidean"):
        xsq = jnp.sum(x * x, axis=1, keepdims=True)      # (bn, 1)
        csq = jnp.sum(c * c, axis=1, keepdims=True).T    # (1, b)
        d2 = jnp.maximum(xsq + csq - 2.0 * dot, 0.0)
        return jnp.sqrt(d2) if mode == "euclidean" else d2
    if mode in ("dot", "cosine"):
        return -dot
    raise ValueError(mode)


def _angle(neg_sim):
    return jnp.arccos(jnp.clip(-neg_sim, -1.0, 1.0))


def _center_dist(x_ref, c_ref, mode):
    """(b, bn) distances of a point tile to the center block: computed as
    (bn, b) — the orientation of the jnp sweep's ``x @ cᵀ``, so both paths
    round alike — then transposed so the min over centers is a lane row."""
    x = x_ref[...]                                       # (bn, d)
    c = c_ref[...]                                       # (b, d)
    dot = jax.lax.dot_general(
        x, c, (((1,), (1,)), ((), ())), precision=_HIGHEST,
        preferred_element_type=jnp.float32)              # (bn, b)
    return _distance(dot, x, c, mode).T


def _fold_kernel(x_ref, c_ref, *refs, mode):
    *min_ref, out_ref = refs
    near = jnp.min(_center_dist(x_ref, c_ref, mode), axis=0, keepdims=True)
    out_ref[...] = jnp.minimum(min_ref[0][...], near) if min_ref else near


def _fold_call(kernel, points, centers, rows, *, bn: int, interpret):
    """Run a fold kernel over (bn, d) point tiles with the whole center
    block resident; ``rows`` are (n,) per-point inputs, passed as (1, n)."""
    n, d = points.shape
    assert n % bn == 0, (n, bn)
    return pl.pallas_call(
        kernel,
        grid=(n // bn,),
        in_specs=[pl.BlockSpec((bn, d), lambda i: (i, 0)),
                  pl.BlockSpec(centers.shape, lambda i: (0, 0))]
        + [pl.BlockSpec((1, bn), lambda i: (0, i)) for _ in rows],
        out_specs=pl.BlockSpec((1, bn), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, n), jnp.float32),
        interpret=staged_interpret(interpret),
    )(points, centers, *(r.reshape(1, n) for r in rows)).reshape(n)


def fold_pallas(points, centers, min_in, *, mode: str, bn: int, interpret):
    """Running-min fold: points (n, d) [n % bn == 0], centers (b, d),
    min_in (n,) -> min(min_in, distance to the nearest center) (n,)."""
    kernel = functools.partial(_fold_kernel, mode=mode)
    if mode == "cosine":
        near = _fold_call(kernel, points, centers, (), bn=bn,
                          interpret=interpret)
        return jnp.minimum(min_in, _angle(near))
    return _fold_call(kernel, points, centers, (min_in,), bn=bn,
                      interpret=interpret)


@functools.partial(jax.jit,
                   static_argnames=("mode", "bn", "interpret"))
def gmm_update_select_pallas(points, centers, min_in, mask, *,
                             mode: str = "euclidean", bn: int = 1024,
                             interpret=None):
    """Fused round.  points (n,d) [n % bn == 0], centers (b,d), min_in (n,),
    mask (n,) -> (min_out (n,), argmax (), max ()).

    ``interpret=None`` auto-selects per backend (see ``resolve_interpret``)."""
    min_out = fold_pallas(points, centers, min_in, mode=mode, bn=bn,
                          interpret=interpret)
    masked = jnp.where(mask, min_out, -jnp.inf)
    j = jnp.argmax(masked).astype(jnp.int32)
    return min_out, j, masked[j]


def top_p(field, p: int, bn: int):
    """Exact top-``p`` (values, int32 indices) of an (n,) field [n % bn ==
    0]: tile-local top-p over (n/bn, bn) rows, then a top-p merge of the tile
    winners.  Ties go to the lowest index, as in ``lax.top_k``.  XLA's TPU
    top-k compiles much faster on rows than over one long vector."""
    if p == 1:
        j = jnp.argmax(field).astype(jnp.int32)
        return field[j][None], j[None]
    n = field.shape[0]
    vals, idxs = jax.lax.top_k(field.reshape(n // bn, bn), min(p, bn))
    idxs = idxs + bn * jnp.arange(n // bn, dtype=jnp.int32)[:, None]
    vals, sel = jax.lax.top_k(vals.reshape(-1), p)
    return vals, idxs.reshape(-1)[sel]


def _grouped_fold_kernel(x_ref, c_ref, lab_ref, *refs, mode, bc):
    """Group-blocked fold: ONE (m·bc, d) × (bn, d) MXU matmul serves all
    ``m`` groups; each point folds only its OWN group's center rows into its
    running min (the per-group GMM runs are independent)."""
    *min_ref, out_ref = refs
    dist = _center_dist(x_ref, c_ref, mode)              # (m*bc, bn)
    lo = lab_ref[...] * bc                               # (1, bn)
    row = jax.lax.broadcasted_iota(jnp.int32, dist.shape, 0)
    own = (row >= lo) & (row < lo + bc)
    near = jnp.min(jnp.where(own, dist, jnp.inf), axis=0, keepdims=True)
    out_ref[...] = jnp.minimum(min_ref[0][...], near) if min_ref else near


@functools.partial(jax.jit, static_argnames=("mode", "bn", "b", "interpret"))
def gmm_grouped_topb_pallas(points, centers, min_in, labels, *,
                            mode: str = "euclidean", bn: int = 1024,
                            b: int = 8, interpret=None):
    """Fused group-blocked batched round for the constrained (partition-
    matroid) per-group GMM sweep.

    points (n, d) [n % bn == 0], centers (m, bc, d) — bc centers per group —
    min_in (n,) (each point's distance to its OWN group's selected centers),
    labels (n,) int32 in [0, m) (pad rows carry -1 so they match no group)
    -> (min_out (n,), cand_val (m, b), cand_idx (m, b)).

    One grid step loads one point tile, performs a single (m·bc, d) × (bn, d)
    matmul shared across the m groups and folds each point's own-group block
    into the shared running-min field; each group's top-b of the updated
    field is taken here in the jit wrapper, one group at a time so that no
    (m, n) array is materialized.
    """
    n, d = points.shape
    m, bc, _ = centers.shape
    assert n >= b, (n, b)
    kernel = functools.partial(_grouped_fold_kernel, mode=mode, bc=bc)
    cflat = centers.reshape(m * bc, d)
    if mode == "cosine":
        near = _fold_call(kernel, points, cflat, (labels,), bn=bn,
                          interpret=interpret)
        min_out = jnp.minimum(min_in, _angle(near))
    else:
        min_out = _fold_call(kernel, points, cflat, (labels, min_in), bn=bn,
                             interpret=interpret)

    vals, idxs = jax.lax.map(
        lambda g: top_p(jnp.where(labels == g, min_out, -jnp.inf), b, bn),
        jnp.arange(m, dtype=jnp.int32))
    return min_out, vals, idxs
