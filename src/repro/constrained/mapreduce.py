"""MapReduce matroid-constrained diversity on a jax device mesh.

The MR rounds are matroid-agnostic — they only see group labels; the matroid
oracle (``quotas=`` sugar or ``matroid=``) enters at the replicated
final-stage solve.

Mirrors ``repro.core.distributed`` (paper §5) with the matroid-coreset
composition layered on top:

  round 1 — every reducer runs the vmapped per-group core-set builder on its
            local (shard, labels) pair: ``m`` GMM/GMM-EXT runs batched into
            one vmap (see ``constrained.coreset``);
  round 2 — per-device unions are aggregated with the same single
            ``all_gather`` collective as the unconstrained path, and the
            feasible-greedy + local-search solver runs replicated on the
            union (host-side, core-set scale).

Composition is sound in both directions: the union over reducers of the union
over groups equals the union over groups of per-reducer core-sets, and
per-group core-sets compose across partitions exactly like the unconstrained
ones (composability of GMM core-sets + the matroid-coreset theorem).

``simulate_fair_mr`` is the single-device ℓ-reducer analogue of
``core.distributed.simulate_mr`` used by the CPU benchmark suite.
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from repro.core.measures import NEEDS_INJECTIVE
from repro.core.metrics import get_metric

from repro.core.gmm import effective_block
from repro.obs.trace import (active as _obs_active, count as _count,
                             counting as _counting,
                             reducer_detail as _reducer_detail, span as _span)

from .coreset import (_grouped_ext_blocked_impl, _grouped_select_impl,
                      pad_for_engine)
from .solver import solve_and_value


class FairCoreset(NamedTuple):
    """Union core-set tagged with group labels (points, not input indices —
    round 2 gathers rows across devices, so original indices are gone)."""
    points: jnp.ndarray      # (cap, d)
    labels: jnp.ndarray      # (cap,) int32 group ids
    valid: jnp.ndarray       # (cap,) bool
    radius: jnp.ndarray      # () max per-group, per-reducer proxy radius
    cert: Optional[object] = None  # probe RadiusCertificate (auto paths)

    def compact(self) -> Tuple[np.ndarray, np.ndarray]:
        v = np.asarray(self.valid)
        return np.asarray(self.points)[v], np.asarray(self.labels)[v]

    @property
    def size(self) -> int:
        return int(np.asarray(self.valid).sum())


def _round1(shard, lab, m: int, k: int, kprime: int, metric_name: str,
            mode: str, use_pallas: bool, b: int = 1, chunk: int = 0,
            schedule=None):
    """Per-reducer body: group-blocked per-group core-set of the local shard
    on the single-sweep engine (one fused sweep per round for all m groups;
    see ``constrained.coreset``).  ``schedule`` pins the static (block,
    rounds) plan a ``b="auto"`` probe resolved.  Returns (pts (m*s, d),
    labels (m*s,), valid (m*s,), radius ())."""
    if schedule is None:
        b = effective_block(kprime, b)
    shard_p, lab_p, chunk = pad_for_engine(shard, lab, chunk)
    if mode == "ext":
        idx, valid, radius, _ = _grouped_ext_blocked_impl(
            shard_p, lab_p, m, k, kprime, b, chunk, metric_name, use_pallas,
            schedule=schedule)
    else:
        idx, valid, radius, _, _ = _grouped_select_impl(
            shard_p, lab_p, m, kprime, b, chunk, metric_name, use_pallas,
            schedule=schedule)
    s = idx.shape[1]
    pts = shard[idx.reshape(-1)]
    glab = jnp.repeat(jnp.arange(m, dtype=jnp.int32), s)
    return pts, glab, valid.reshape(-1), jnp.max(radius)


def mr_grouped_coreset(points, labels, m: Optional[int] = None,
                       k: Optional[int] = None, kprime=32,
                       measure: str = "remote-edge",
                       mesh: Optional[Mesh] = None, *, matroid=None,
                       data_axes: Sequence[str] = ("data",),
                       metric="euclidean", use_pallas: bool = False,
                       b=1, chunk: int = 0,
                       eps: float = 0.1, tau=None,
                       cliff=None) -> FairCoreset:
    """2-round MR fair core-set on a mesh: ``points (n, d)`` and ``labels
    (n,)`` are sharded over ``data_axes``; returns the replicated union.
    ``matroid=`` derives ``m``/``k`` from an oracle (the construction itself
    is matroid-agnostic — it only sees group labels).  ``b="auto"`` /
    ``kprime="auto"`` probe the labelled input once on the host and compile
    the adaptive controller's decisions into every reducer as a static
    (block, rounds) schedule."""
    from jax import shard_map

    from repro.core.distributed import _resolve_reducer_plan

    from .matroid import derive_mk

    m, k = derive_mk(matroid, m, k, "mr_grouped_coreset")
    if mesh is None:
        raise ValueError("mr_grouped_coreset requires a mesh")

    axes = tuple(data_axes)
    nshards = int(np.prod([mesh.shape[a] for a in axes]))
    n, _ = points.shape
    if n % nshards:
        raise ValueError(f"n={n} not divisible by {nshards} reducers")
    kprime, schedule, b, cert = _resolve_reducer_plan(
        points, k, kprime, b, eps=eps, metric=metric, chunk=chunk,
        per_shard=n // nshards, labels=labels, m=m, tau=tau, cliff=cliff)
    metric_name = get_metric(metric).name
    mode = "ext" if measure in NEEDS_INJECTIVE else "plain"

    def body(shard, lab):
        pts, glab, valid, radius = _round1(shard, lab, m, k, kprime,
                                           metric_name, mode, use_pallas,
                                           b, chunk, schedule)
        g_pts = jax.lax.all_gather(pts, axes, tiled=True)
        g_lab = jax.lax.all_gather(glab, axes, tiled=True)
        g_valid = jax.lax.all_gather(valid, axes, tiled=True)
        g_rad = jax.lax.pmax(radius, axes)
        return g_pts, g_lab, g_valid, g_rad

    fn = shard_map(body, mesh=mesh, in_specs=(P(axes), P(axes)),
                   out_specs=(P(), P(), P(), P()), check_vma=False)
    with _span("mr.round1", reducers=nshards, kprime=kprime, groups=m):
        g_pts, g_lab, g_valid, g_rad = jax.jit(fn)(
            jnp.asarray(points), jnp.asarray(labels, jnp.int32))
        _count("device_dispatches")
        if _counting():
            from repro.core.distributed import _count_round1
            _count_round1(nshards, n // nshards, points.shape[1], kprime, b,
                          schedule, mode)
            jax.block_until_ready(g_rad)
    return FairCoreset(points=g_pts, labels=g_lab, valid=g_valid,
                       radius=g_rad, cert=cert)


def _mr_fair_diversity_impl(points, labels, quotas=None,
                            measure: str = "remote-edge",
                            mesh: Optional[Mesh] = None, *, matroid=None,
                            kprime: Optional[int] = None,
                            data_axes: Sequence[str] = ("data",),
                            metric="euclidean",
                            use_pallas: bool = False, swap_rounds: int = 10,
                            b=1, chunk: int = 0, eps: float = 0.1,
                            tau=None, cliff=None, resilience=None):
    """Execution body of the constrained mesh MR pipeline (no deprecation
    warning — the ``repro.diversify`` facade routes here).  Returns
    (sol, sol_labels, value, cert, report).  Like the unconstrained mesh
    path, a ``ResiliencePolicy`` retries the whole sharded round-1 dispatch
    (one collective: no per-reducer unit to degrade to)."""
    from .matroid import as_matroid

    if mesh is None:
        raise ValueError("mr_fair_diversity requires a mesh")
    mat = as_matroid(matroid, quotas)
    m, k = mat.m, mat.k
    if kprime is None:
        kprime = max(2 * k, 32)

    def round1():
        return mr_grouped_coreset(points, labels, m, k, kprime, measure,
                                  mesh, data_axes=data_axes, metric=metric,
                                  use_pallas=use_pallas, b=b, chunk=chunk,
                                  eps=eps, tau=tau, cliff=cliff)

    report = None
    if resilience is not None:
        from repro.distributed.fault_tolerance import retry_call
        cs, report = retry_call(lambda: jax.block_until_ready(round1()),
                                resilience, point="round:mr.round1")
    else:
        cs = round1()
    cand_pts, cand_lab = cs.compact()
    sel, value = solve_and_value(cand_pts, cand_lab, measure=measure,
                                 matroid=mat, metric=metric,
                                 swap_rounds=swap_rounds)
    return cand_pts[sel], cand_lab[sel], value, cs.cert, report


def mr_fair_diversity(points, labels, quotas=None, measure: str = "remote-edge",
                      mesh: Optional[Mesh] = None, *, matroid=None,
                      kprime: Optional[int] = None,
                      data_axes: Sequence[str] = ("data",), metric="euclidean",
                      use_pallas: bool = False, swap_rounds: int = 10,
                      b=1, chunk: int = 0, eps: float = 0.1,
                      tau=None, cliff=None):
    """Full constrained pipeline on a mesh (``quotas=`` is sugar for an
    exact-quota ``PartitionMatroid``; any label-count matroid works — the MR
    rounds only see group labels, the oracle enters at the replicated solve).

    Legacy spelling of ``repro.diversify`` with a constrained
    ``ProblemSpec`` and ``ExecutionSpec(mode="mapreduce", mesh=...)`` —
    prefer the facade for new code.

    Returns (solution_points (k, d), solution_labels (k,), value)."""
    from repro.api import (ExecutionSpec, ProblemSpec, _warn_legacy,
                           diversify)
    from .matroid import as_matroid

    _warn_legacy("repro.constrained.mr_fair_diversity")
    if mesh is None:
        raise ValueError("mr_fair_diversity requires a mesh")
    mat = as_matroid(matroid, quotas)
    res = diversify(
        ProblemSpec(points=points, k=mat.k, measure=measure, metric=metric,
                    labels=labels, matroid=mat),
        ExecutionSpec(mode="mapreduce", mesh=mesh,
                      data_axes=tuple(data_axes), kprime=kprime, b=b,
                      chunk=chunk, eps=eps, use_pallas=use_pallas,
                      swap_rounds=swap_rounds, tau=tau, cliff=cliff))
    return res.solution, res.labels, res.value


# --------------------------------------------------------------------------
# simulated-reducer path (CPU benchmarks / tests)
# --------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("m", "k", "kprime", "metric_name",
                                             "mode", "b", "chunk", "schedule"))
def _sim_round1(shards, slabels, m: int, k: int, kprime: int,
                metric_name: str, mode: str, b: int = 1, chunk: int = 0,
                schedule=None):
    def one(s, sl):
        return _round1(s, sl, m, k, kprime, metric_name, mode, False, b,
                       chunk, schedule)

    return jax.vmap(one)(shards, slabels)


def _sim_round1_detail(shards, slabels, m: int, k: int, kprime: int,
                       metric_name: str, mode: str, b: int = 1,
                       chunk: int = 0, schedule=None):
    """Per-reducer observability path — constrained analogue of
    ``core.distributed._sim_round1_detail``: one dispatch per reducer so
    each gets a real span; wall-clocks feed ``StragglerPolicy`` and flagged
    reducers land in the trace extras as ``mr_stragglers``."""
    from repro.distributed.fault_tolerance import StragglerPolicy

    policy = StragglerPolicy(min_history=3)
    outs, stragglers = [], []
    for i in range(int(shards.shape[0])):
        with _span(f"mr.reducer[{i}]", reducer=i) as sp:
            out = jax.block_until_ready(_sim_round1(
                shards[i:i + 1], slabels[i:i + 1], m, k, kprime, metric_name,
                mode, b, chunk, schedule))
        _count("device_dispatches")
        outs.append(out)
        if sp is not None and policy.observe(sp.seconds):
            stragglers.append(i)
    tr = _obs_active()
    if tr is not None:
        tr.annotate(mr_stragglers=tuple(stragglers))
    return tuple(jnp.concatenate([o[j] for o in outs], axis=0)
                 for j in range(4))


def _sim_round1_resilient(shards, slabels, m: int, k: int, kprime: int,
                          metric_name: str, mode: str, b, chunk, schedule,
                          policy):
    """Constrained analogue of ``core.distributed._sim_round1_resilient``:
    per-reducer dispatch with retry/degrade; failed reducers contribute
    all-zeros blocks with ``valid=False`` (the per-group composition is
    preserved — a dropped reducer only removes its shard's candidates).
    Returns (pts, labels, valid, radius, report)."""
    from repro.distributed.fault_tolerance import run_resilient

    l = int(shards.shape[0])

    def run_one(i):
        with _span(f"mr.reducer[{i}]", reducer=i):
            out = jax.block_until_ready(_sim_round1(
                shards[i:i + 1], slabels[i:i + 1], m, k, kprime, metric_name,
                mode, b, chunk, schedule))
        _count("device_dispatches")
        return out

    outs, report = run_resilient(l, run_one, policy, scope="reducer")
    ok = [o for o in outs if o is not None]
    if not ok:
        raise RuntimeError(
            f"all {l} reducers failed under on_failure="
            f"{policy.on_failure!r}; nothing to merge")
    outs = [o if o is not None else jax.tree.map(jnp.zeros_like, ok[0])
            for o in outs]
    merged = tuple(jnp.concatenate([o[j] for o in outs], axis=0)
                   for j in range(4))
    return merged + (report,)


def _simulate_fair_mr_impl(points, labels, quotas=None, *, matroid=None,
                           num_reducers: int,
                           measure: str = "remote-edge",
                           kprime=None, metric="euclidean",
                           partition: str = "contiguous", seed: int = 0,
                           swap_rounds: int = 10, b=1, chunk: int = 0,
                           eps: float = 0.1, tau=None, cliff=None,
                           resilience=None):
    """Execution body of the simulated ℓ-reducer constrained MR run (no
    deprecation warning — the ``repro.diversify`` facade routes here).
    Returns (sol, sol_labels, value, cert, report)."""
    from repro.core.distributed import partition_shards

    from .matroid import as_matroid

    mat = as_matroid(matroid, quotas)
    m, k = mat.m, mat.k
    if kprime is None:
        kprime = max(2 * k, 32)
    pts, shards, slabels = partition_shards(
        np.asarray(points, np.float32), num_reducers, partition=partition,
        seed=seed, labels=np.asarray(labels, np.int32))
    d = pts.shape[1]
    from repro.core.distributed import _resolve_reducer_plan
    if kprime != "auto":
        kprime = min(kprime, shards.shape[1])
    kprime, schedule, b, cert = _resolve_reducer_plan(
        pts, k, kprime, b, eps=eps, metric=metric, chunk=chunk,
        per_shard=shards.shape[1], labels=np.asarray(slabels).reshape(-1),
        m=m, tau=tau, cliff=cliff)
    mode = "ext" if measure in NEEDS_INJECTIVE else "plain"

    if _counting():
        from repro.core.distributed import _count_round1
        _count_round1(num_reducers, int(shards.shape[1]), d, kprime, b,
                      schedule, mode)
    report = None
    if resilience is not None:
        g_pts, g_lab, g_valid, g_rad, report = _sim_round1_resilient(
            shards, slabels, m, k, kprime, get_metric(metric).name, mode,
            b, chunk, schedule, resilience)
    elif _reducer_detail():
        g_pts, g_lab, g_valid, g_rad = _sim_round1_detail(
            shards, slabels, m, k, kprime, get_metric(metric).name, mode,
            b, chunk, schedule)
    else:
        with _span("mr.round1", reducers=num_reducers, kprime=kprime,
                   groups=m):
            g_pts, g_lab, g_valid, g_rad = _sim_round1(
                shards, slabels, m, k, kprime, get_metric(metric).name, mode,
                b, chunk, schedule)
            _count("device_dispatches")
            if _counting():
                jax.block_until_ready(g_rad)
    if report is not None and report.degraded:
        from repro.distributed.fault_tolerance import degraded_certificate
        cert = degraded_certificate(cert, kprime=kprime,
                                    radius=float(jnp.max(g_rad)),
                                    survivors=report.survivors,
                                    total=num_reducers,
                                    per_shard=int(shards.shape[1]))
    flat_pts = np.asarray(g_pts.reshape(-1, d))
    flat_lab = np.asarray(g_lab.reshape(-1))
    flat_valid = np.asarray(g_valid.reshape(-1))
    cand_pts = flat_pts[flat_valid]
    cand_lab = flat_lab[flat_valid]
    sel, value = solve_and_value(cand_pts, cand_lab, measure=measure,
                                 matroid=mat, metric=metric,
                                 swap_rounds=swap_rounds)
    return cand_pts[sel], cand_lab[sel], value, cert, report


def simulate_fair_mr(points, labels, quotas=None, *, matroid=None,
                     num_reducers: int,
                     measure: str = "remote-edge",
                     kprime=None, metric="euclidean",
                     partition: str = "contiguous", seed: int = 0,
                     swap_rounds: int = 10, b=1, chunk: int = 0,
                     eps: float = 0.1, tau=None, cliff=None):
    """Simulate the ℓ-reducer 2-round constrained MR run on one device.

    Legacy spelling of ``repro.diversify`` with a constrained
    ``ProblemSpec`` and ``ExecutionSpec(mode="mapreduce",
    num_reducers=...)`` — prefer the facade for new code.

    Returns (solution_points, solution_labels, value).  ``partition`` follows
    ``simulate_mr``: 'contiguous' | 'random' | 'adversarial'; ``quotas=`` is
    sugar for an exact-quota ``PartitionMatroid``."""
    from repro.api import (ExecutionSpec, ProblemSpec, _warn_legacy,
                           diversify)
    from .matroid import as_matroid

    _warn_legacy("repro.constrained.simulate_fair_mr")
    mat = as_matroid(matroid, quotas)
    res = diversify(
        ProblemSpec(points=points, k=mat.k, measure=measure, metric=metric,
                    labels=labels, matroid=mat),
        ExecutionSpec(mode="mapreduce", num_reducers=num_reducers,
                      kprime=kprime, b=b, chunk=chunk, eps=eps,
                      partition=partition, seed=seed,
                      swap_rounds=swap_rounds, tau=tau, cliff=cliff))
    return res.solution, res.labels, res.value
