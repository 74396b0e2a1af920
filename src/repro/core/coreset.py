"""Core-set containers + the high-level single-machine driver API.

``Coreset``            — explicit point core-set (fixed-capacity + validity mask,
                         so every array is static-shape for jit).
``GeneralizedCoreset`` — kernel points + multiplicities (§6 of the paper).

The end-to-end sequential pipeline (paper §4/§5 final stage) lives here:
``diversity_maximize`` = build core-set → run the α-approx sequential solver.
"""
from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.obs.trace import count as _count


class Coreset(NamedTuple):
    points: jnp.ndarray      # (cap, d)
    valid: jnp.ndarray       # (cap,) bool
    weights: jnp.ndarray     # (cap,) int32  (1 for valid rows, 0 otherwise)
    radius: jnp.ndarray      # () — proxy-distance bound r_T (telemetry)
    cert: Optional[object] = None  # RadiusCertificate (adaptive/auto paths)

    def compact(self) -> np.ndarray:
        """Materialize valid rows (host-side, dynamic shape)."""
        v = np.asarray(self.valid)
        return np.asarray(self.points)[v]

    @property
    def size(self) -> int:
        return int(np.asarray(self.valid).sum())


class GeneralizedCoreset(NamedTuple):
    points: jnp.ndarray        # (kprime, d) kernel
    multiplicity: jnp.ndarray  # (kprime,) int32 (0 = invalid row)
    radius: jnp.ndarray        # () — delegate distance bound (Lemma 7's δ)
    cert: Optional[object] = None  # RadiusCertificate (adaptive/auto paths)

    def compact(self):
        m = np.asarray(self.multiplicity)
        keep = m > 0
        return np.asarray(self.points)[keep], m[keep]

    @property
    def expanded_size(self) -> int:
        return int(np.asarray(self.multiplicity).sum())


def coreset_from_points(points, weights=None) -> Coreset:
    points = jnp.asarray(points)
    n = points.shape[0]
    if weights is None:
        weights = jnp.ones((n,), jnp.int32)
    return Coreset(points=points, valid=jnp.ones((n,), bool),
                   weights=jnp.asarray(weights, jnp.int32),
                   radius=jnp.asarray(0.0, points.dtype))


def build_coreset(points, k: int, kprime, measure: str, *,
                  metric="euclidean", use_pallas: bool = False,
                  generalized: bool = False, b=1, chunk: int = 0,
                  eps: float = 0.1, schedule=None, tau=None, cliff=None,
                  sprint="auto"):
    """Sequential (single-partition) core-set per the paper's recipe:

    * remote-edge / remote-cycle  -> GMM(S, k')            (Thm 4)
    * the other four              -> GMM-EXT(S, k, k')     (Thm 5)
    * generalized=True            -> GMM-GEN(S, k, k')     (Thm 10)

    ``b``/``chunk`` select the batched lookahead-b engine (``gmm_batched``)
    instead of the one-center-per-sweep loop; ``b`` is snapped to a divisor
    of ``kprime``.  ``b="auto"`` runs the radius-certified adaptive
    controller and ``kprime="auto"`` grows k' until the measured radius
    certificate meets the ``eps`` accuracy target (``core.adaptive``); both
    attach the resulting ``RadiusCertificate`` as ``cs.cert``.
    ``tau``/``cliff`` override the adaptive controller's greedy-consistency
    bars (None = ``core.adaptive.DEFAULT_TAU`` / ``DEFAULT_CLIFF``) and
    ``sprint`` its device-paced segment runner (``"auto"`` = on whenever it
    is bit-identical; see ``core.adaptive.resolve_sprint``).

    >>> import numpy as np
    >>> rng = np.random.default_rng(0)
    >>> pts = rng.normal(size=(500, 4)).astype(np.float32)
    >>> cs = build_coreset(pts, k=4, kprime=16, measure="remote-edge")
    >>> cs.size                     # k' centers, all valid
    16
    >>> float(cs.radius) > 0.0      # anticover radius r_T (telemetry)
    True
    >>> cs = build_coreset(pts, k=4, kprime="auto", measure="remote-edge",
    ...                    eps=0.5)
    >>> cs.cert.meets_target        # certified: 2*r_T/scale_k <= eps
    True
    """
    from repro.core.gmm import (effective_block, gmm as _gmm, gmm_batched,
                                gmm_ext as _gmm_ext, gmm_gen as _gmm_gen)
    from .measures import NEEDS_INJECTIVE

    on_host = not isinstance(points, jax.Array)
    points = jnp.asarray(points)
    if on_host:
        _count("h2d_bytes", points.nbytes)
    auto = kprime == "auto" or b == "auto"
    cert = None
    if kprime == "auto":
        from .adaptive import auto_kprime
        res = auto_kprime(points, k, eps, measure, metric=metric, b=b,
                          chunk=chunk, use_pallas=use_pallas, tau=tau,
                          cliff=cliff, sprint=sprint)
        kprime, cert = int(res.idx.shape[0]), res.cert
        kernel = res
    elif b == "auto":
        from .adaptive import gmm_adaptive
        kernel = gmm_adaptive(points, kprime, metric=metric, chunk=chunk,
                              use_pallas=use_pallas, tau=tau, cliff=cliff,
                              scale_count=min(k, kprime), sprint=sprint)
        cert = kernel.cert
    if generalized:
        if auto:
            from repro.core.gmm import gmm_ext_from_kernel
            ext = gmm_ext_from_kernel(points, kernel.idx, kernel.radius, k,
                                      metric=metric, chunk=chunk)
            return GeneralizedCoreset(points=points[ext.kernel_idx],
                                      multiplicity=ext.multiplicity,
                                      radius=ext.radius, cert=cert)
        return _gmm_gen(points, k, kprime, metric=metric,
                        use_pallas=use_pallas, b=b, chunk=chunk,
                        schedule=schedule)
    if measure in NEEDS_INJECTIVE:
        if auto:
            from repro.core.gmm import gmm_ext_from_kernel
            ext = gmm_ext_from_kernel(points, kernel.idx, kernel.radius, k,
                                      metric=metric, chunk=chunk)
        else:
            ext = _gmm_ext(points, k, kprime, metric=metric,
                           use_pallas=use_pallas, b=b, chunk=chunk,
                           schedule=schedule)
        flat_idx = ext.delegate_idx.reshape(-1)
        flat_valid = ext.delegate_valid.reshape(-1)
        pts = points[flat_idx]
        return Coreset(points=pts, valid=flat_valid,
                       weights=flat_valid.astype(jnp.int32),
                       radius=ext.radius, cert=cert)
    if auto:
        pts = points[kernel.idx]
        n = pts.shape[0]
        return Coreset(points=pts, valid=jnp.ones((n,), bool),
                       weights=jnp.ones((n,), jnp.int32),
                       radius=kernel.radius, cert=cert)
    if schedule is None:
        b = effective_block(kprime, b)
    if schedule is not None or b > 1 or chunk:
        idx, radius, _ = gmm_batched(points, kprime, b=b, metric=metric,
                                     chunk=chunk, use_pallas=use_pallas,
                                     schedule=schedule)
    else:
        res = _gmm(points, kprime, metric=metric, use_pallas=use_pallas)
        idx, radius = res.idx, res.radius
    pts = points[idx]
    n = pts.shape[0]
    return Coreset(points=pts, valid=jnp.ones((n,), bool),
                   weights=jnp.ones((n,), jnp.int32), radius=radius)


def diversity_maximize(points, k: int, measure: str, *, kprime=None,
                       metric="euclidean", use_pallas: bool = False,
                       b=1, chunk: int = 0, eps: float = 0.1,
                       tau=None, cliff=None):
    """End-to-end: core-set + sequential α-approx solver.

    Legacy spelling of ``repro.diversify`` — prefer the facade for new code
    (this wrapper emits a ``DeprecationWarning`` and routes through it,
    bit-identically).  Returns (solution_points (k,d) ndarray, value,
    coreset).  ``b="auto"`` and ``kprime="auto"`` enable the
    radius-certified adaptive engine (``eps`` sets the auto-k' target; see
    ``build_coreset``), and the returned core-set then carries ``cs.cert``.

    >>> import numpy as np
    >>> rng = np.random.default_rng(0)
    >>> pts = rng.normal(size=(1000, 3)).astype(np.float32)
    >>> sol, value, cs = diversity_maximize(pts, k=5, measure="remote-edge")
    >>> sol.shape
    (5, 3)
    >>> bool(value > 0.0)
    True
    """
    from repro.api import (ExecutionSpec, ProblemSpec, _warn_legacy,
                           diversify)

    _warn_legacy("repro.core.diversity_maximize")
    res = diversify(
        ProblemSpec(points=points, k=k, measure=measure, metric=metric),
        ExecutionSpec(mode="batch", kprime=kprime, b=b, chunk=chunk,
                      eps=eps, use_pallas=use_pallas, tau=tau, cliff=cliff))
    return res.solution, res.value, res.coreset
