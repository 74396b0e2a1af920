"""MapReduce diversity maximization on a jax device mesh (paper §5, §6.2).

Round structure (Thm 6):
  round 1  — every mesh device ("reducer") runs GMM / GMM-EXT / GMM-GEN on its
             local shard (shard_map over the data axes);
  round 2  — the per-device core-sets are aggregated with one ``all_gather``
             (the Spark shuffle of the paper becomes a single collective whose
             bytes we account in the roofline) and the sequential α-approx
             solver runs replicated on the union;
  round 3  — (generalized scheme, Thm 10) each device instantiates delegates
             for the kernel points it owns.

The recursive scheme (Thm 8) is a 2-level reduction: within-pod over the
``data`` axis, then across pods over the ``pod`` axis.

Two execution paths:
 * ``mesh`` path — real shard_map for the production mesh / dry-run;
 * ``simulate_reducers`` — ℓ logical reducers dispatched in turn on one
   device, each running the mesh path's per-device program, used by the
   benchmark suite to reproduce the paper's parallelism sweeps (Fig 4/5)
   without a mesh.
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core.gmm import (effective_block as _effective_block, gmm as _gmm,
                            gmm_batched, gmm_ext as _gmm_ext,
                            gmm_gen as _gmm_gen, schedule_fold_sizes)
from repro.obs.trace import (active as _obs_active, count as _count,
                             counting as _counting,
                             reducer_detail as _reducer_detail,
                             span as _span, sweep_bytes as _sweep_bytes)
from .coreset import Coreset, GeneralizedCoreset
from .measures import NEEDS_INJECTIVE, diversity
from .metrics import get_metric
from .sequential import instantiate, solve, solve_on_coreset


# --------------------------------------------------------------------------
# round 1 bodies (run per shard)
# --------------------------------------------------------------------------

def _local_coreset_plain(shard, kprime, metric, use_pallas, b=1, chunk=0,
                         schedule=None):
    if schedule is None:
        b = _effective_block(kprime, b)
    if schedule is not None or b > 1 or chunk:
        idx, radius, _ = gmm_batched(shard, kprime, b=b, metric=metric,
                                     chunk=chunk, use_pallas=use_pallas,
                                     schedule=schedule)
        return shard[idx], radius
    res = _gmm(shard, kprime, metric=metric, use_pallas=use_pallas)
    return shard[res.idx], res.radius


def _local_coreset_ext(shard, k, kprime, metric, use_pallas, b=1, chunk=0,
                       schedule=None):
    ext = _gmm_ext(shard, k, kprime, metric=metric, use_pallas=use_pallas,
                   b=b, chunk=chunk, schedule=schedule)
    pts = shard[ext.delegate_idx.reshape(-1)]
    valid = ext.delegate_valid.reshape(-1)
    return pts, valid, ext.radius


def _local_coreset_gen(shard, k, kprime, metric, use_pallas, b=1, chunk=0,
                       schedule=None):
    gen = _gmm_gen(shard, k, kprime, metric=metric, use_pallas=use_pallas,
                   b=b, chunk=chunk, schedule=schedule)
    return gen.points, gen.multiplicity, gen.radius


def _resolve_reducer_plan(points, k: int, kprime, b, *, eps: float,
                          metric, chunk: int, per_shard: int,
                          labels=None, m: int = 1, tau=None, cliff=None):
    """Freeze ``b="auto"``/``kprime="auto"`` into static reducer inputs.

    A shard_map body cannot run the host-paced controller, so a cheap probe
    (``core.adaptive.resolve_engine_plan``) runs once on a subsample of the
    global input and its decisions are compiled into every reducer as a
    static (block, rounds) schedule.  k' is clamped to the shard size.
    Returns (kprime:int, schedule|None, b:int, probe RadiusCertificate|None).
    """
    if b != "auto" and kprime != "auto":
        return kprime, None, b, None
    from repro.core.adaptive import plan_from_schedule, resolve_engine_plan

    with _span("mr.probe", k=k, kprime=kprime, b=b):
        kp, schedule, cert = resolve_engine_plan(np.asarray(points), k,
                                                 kprime, b, eps=eps,
                                                 metric=metric, labels=labels,
                                                 m=m, chunk=chunk, tau=tau,
                                                 cliff=cliff)
    kp = min(int(kp), per_shard)
    if schedule is not None:
        planned = sum(b_ * r for b_, r in schedule)
        if planned != kp:        # k' was clamped: re-fit the plan's fraction
            schedule = plan_from_schedule(schedule, kp, planned)
    # kprime="auto" with an explicit numeric b keeps that b (no schedule);
    # only b="auto" replaces the knob with the frozen plan
    return kp, schedule, (1 if b == "auto" else b), cert


def _count_round1(num_reducers: int, per_shard: int, d: int, kprime: int,
                  b, schedule, mode: str) -> None:
    """Model-based round-1 counters: the reducer bodies run inside jit
    (vmap / shard_map), where the engines' own host-wrapper counters cannot
    fire, so the driver charges the schedule's exact fold count per reducer
    (the same accounting ``core.gmm`` uses on the host path)."""
    if schedule is not None:
        folds = schedule_fold_sizes(schedule)
        sweeps, folded = len(folds), sum(folds)
    elif b not in (None, "auto") and b > 1:
        beff = _effective_block(kprime, b)
        folds = schedule_fold_sizes(((beff, kprime // beff),))
        sweeps, folded = len(folds), sum(folds)
    else:
        sweeps, folded = kprime, kprime
    if mode in ("ext", "gen") and (schedule is not None
                                   or (b not in (None, "auto") and b > 1)):
        sweeps, folded = sweeps + 1, folded + kprime     # assignment pass
    _count("distance_evals", num_reducers * per_shard * folded)
    _count("bytes_swept",
           num_reducers * _sweep_bytes(per_shard, d, sweeps=sweeps))


# --------------------------------------------------------------------------
# mesh path (shard_map)
# --------------------------------------------------------------------------

def mr_coreset(points, k: int, kprime, measure: str, mesh: Mesh,
               *, data_axes: Sequence[str] = ("data",), metric="euclidean",
               use_pallas: bool = False, generalized: bool = False,
               b=1, chunk: int = 0, eps: float = 0.1, tau=None, cliff=None):
    """2-round MR core-set on a mesh.  ``points`` is globally (n, d) and gets
    sharded over ``data_axes``; returns a replicated Coreset/GeneralizedCoreset
    for the union T = ∪ T_i.  ``b``/``chunk`` tune the per-reducer selection
    engine (lookahead-b batched GMM; see ``core.gmm.gmm_batched``);
    ``b="auto"`` / ``kprime="auto"`` run a host-side probe once and compile
    its decisions into every reducer as a static (block, rounds) schedule
    (``eps`` is the auto-k' accuracy target)."""
    from jax import shard_map

    axes = tuple(data_axes)
    nshards = int(np.prod([mesh.shape[a] for a in axes]))
    n, d = points.shape
    if n % nshards:
        raise ValueError(f"n={n} not divisible by {nshards} reducers")
    kprime, schedule, b, cert = _resolve_reducer_plan(
        points, k, kprime, b, eps=eps, metric=metric, chunk=chunk,
        per_shard=n // nshards, tau=tau, cliff=cliff)
    if _counting():
        _count("device_dispatches")
        _count_round1(nshards, n // nshards, d, kprime, b, schedule,
                      "gen" if generalized else
                      "ext" if measure in NEEDS_INJECTIVE else "plain")

    if generalized:
        def body(shard):
            pts, mult, radius = _local_coreset_gen(shard, k, kprime, metric,
                                                   use_pallas, b, chunk,
                                                   schedule)
            g_pts = jax.lax.all_gather(pts, axes, tiled=True)
            g_mult = jax.lax.all_gather(mult, axes, tiled=True)
            g_rad = jax.lax.pmax(radius, axes)
            return g_pts, g_mult, g_rad

        fn = shard_map(body, mesh=mesh, in_specs=P(axes),
                       out_specs=(P(), P(), P()), check_vma=False)
        with _span("mr.round1", reducers=nshards, kprime=kprime):
            g_pts, g_mult, g_rad = jax.jit(fn)(points)
            if _counting():
                jax.block_until_ready(g_rad)
        return GeneralizedCoreset(points=g_pts, multiplicity=g_mult,
                                  radius=g_rad, cert=cert)

    if measure in NEEDS_INJECTIVE:
        def body(shard):
            pts, valid, radius = _local_coreset_ext(shard, k, kprime, metric,
                                                    use_pallas, b, chunk,
                                                    schedule)
            g_pts = jax.lax.all_gather(pts, axes, tiled=True)
            g_valid = jax.lax.all_gather(valid, axes, tiled=True)
            g_rad = jax.lax.pmax(radius, axes)
            return g_pts, g_valid, g_rad

        fn = shard_map(body, mesh=mesh, in_specs=P(axes),
                       out_specs=(P(), P(), P()), check_vma=False)
        with _span("mr.round1", reducers=nshards, kprime=kprime):
            g_pts, g_valid, g_rad = jax.jit(fn)(points)
            if _counting():
                jax.block_until_ready(g_rad)
        return Coreset(points=g_pts, valid=g_valid,
                       weights=g_valid.astype(jnp.int32), radius=g_rad,
                       cert=cert)

    def body(shard):
        pts, radius = _local_coreset_plain(shard, kprime, metric, use_pallas,
                                           b, chunk, schedule)
        g_pts = jax.lax.all_gather(pts, axes, tiled=True)
        g_rad = jax.lax.pmax(radius, axes)
        return g_pts, g_rad

    fn = shard_map(body, mesh=mesh, in_specs=P(axes),
                   out_specs=(P(), P()), check_vma=False)
    with _span("mr.round1", reducers=nshards, kprime=kprime):
        g_pts, g_rad = jax.jit(fn)(points)
        if _counting():
            jax.block_until_ready(g_rad)
    m = g_pts.shape[0]
    return Coreset(points=g_pts, valid=jnp.ones((m,), bool),
                   weights=jnp.ones((m,), jnp.int32), radius=g_rad,
                   cert=cert)


def _mr_diversity_impl(points, k: int, measure: str, mesh: Mesh, *,
                       kprime=None,
                       data_axes: Sequence[str] = ("data",),
                       metric="euclidean",
                       use_pallas: bool = False, three_round: bool = False,
                       b=1, chunk: int = 0, eps: float = 0.1,
                       tau=None, cliff=None, resilience=None):
    """Execution body of the mesh MR pipeline (no deprecation warning — the
    ``repro.diversify`` facade routes here).  Returns (sol, value, cs,
    report).  A ``ResiliencePolicy`` retries the whole sharded round-1
    dispatch: the shard_map launch is one collective, so there is no
    per-reducer unit to degrade to — ``on_failure="degrade"`` behaves like
    retry-then-raise here (documented in ``retry_call``)."""
    if kprime is None:
        kprime = max(2 * k, 32)

    def round1(generalized):
        return mr_coreset(points, k, kprime, measure, mesh,
                          data_axes=data_axes, metric=metric,
                          use_pallas=use_pallas, generalized=generalized,
                          b=b, chunk=chunk, eps=eps, tau=tau, cliff=cliff)

    report = None
    if resilience is not None:
        from repro.distributed.fault_tolerance import retry_call
        cs, report = retry_call(
            lambda: jax.block_until_ready(round1(three_round)),
            resilience, point="round:mr.round1")
    else:
        cs = round1(three_round)
    if not three_round:
        sol = solve_on_coreset(cs, k, measure, metric=metric)
    else:
        pts, mult = cs.compact()
        idx = solve(measure, pts, k, weights=mult, metric=metric)
        uniq, counts = np.unique(idx, return_counts=True)
        # round 3: instantiate the chosen multiset against the full input
        sol = instantiate(pts[uniq], counts, np.asarray(points),
                          float(cs.radius), metric=metric)
    met = get_metric(metric)
    dm = np.asarray(met.pairwise(jnp.asarray(sol), jnp.asarray(sol)))
    return sol, diversity(measure, dm), cs, report


def mr_diversity(points, k: int, measure: str, mesh: Mesh, *,
                 kprime=None,
                 data_axes: Sequence[str] = ("data",), metric="euclidean",
                 use_pallas: bool = False, three_round: bool = False,
                 b=1, chunk: int = 0, eps: float = 0.1, tau=None, cliff=None):
    """Full pipeline: 2-round (Thm 6) or 3-round generalized (Thm 10).

    Legacy spelling of ``repro.diversify`` with ``ExecutionSpec(
    mode="mapreduce", mesh=...)`` — prefer the facade for new code.
    ``b="auto"`` / ``kprime="auto"`` probe once and freeze a static reducer
    plan (see ``mr_coreset``).  Returns (solution_points (k,d), value)."""
    from repro.api import (ExecutionSpec, ProblemSpec, _warn_legacy,
                           diversify)

    _warn_legacy("repro.core.distributed.mr_diversity")
    res = diversify(
        ProblemSpec(points=points, k=k, measure=measure, metric=metric),
        ExecutionSpec(mode="mapreduce", mesh=mesh,
                      data_axes=tuple(data_axes), kprime=kprime, b=b,
                      chunk=chunk, eps=eps, use_pallas=use_pallas,
                      three_round=three_round, tau=tau, cliff=cliff))
    return res.solution, res.value


def mr_coreset_recursive(points, k: int, kprime, measure: str, mesh: Mesh,
                         *, metric="euclidean", use_pallas: bool = False,
                         b=1, chunk: int = 0, eps: float = 0.1,
                         tau=None, cliff=None):
    """Thm 8: two-level reduction — per-device core-sets over ``data``,
    re-contracted over ``pod`` (requires a ('pod','data',...) mesh)."""
    from jax import shard_map

    if "pod" not in mesh.axis_names:
        raise ValueError("recursive scheme expects a 'pod' axis")
    ext = measure in NEEDS_INJECTIVE
    nshards = int(np.prod([mesh.shape[a] for a in ("pod", "data")]))
    kprime, schedule, b, cert = _resolve_reducer_plan(
        points, k, kprime, b, eps=eps, metric=metric, chunk=chunk,
        per_shard=points.shape[0] // nshards, tau=tau, cliff=cliff)

    def body(shard):
        if ext:
            pts, valid, radius = _local_coreset_ext(shard, k, kprime, metric,
                                                    use_pallas, b, chunk,
                                                    schedule)
            mask = valid
        else:
            pts, radius = _local_coreset_plain(shard, kprime, metric,
                                               use_pallas, b, chunk,
                                               schedule)
            mask = jnp.ones((pts.shape[0],), bool)
        # level 1: union within pod
        pod_pts = jax.lax.all_gather(pts, "data", tiled=True)
        pod_mask = jax.lax.all_gather(mask, "data", tiled=True)
        # level-2 core-set of the pod-level union (mask-aware GMM)
        res = _gmm(pod_pts, kprime, metric=metric, mask=pod_mask)
        lvl2 = pod_pts[res.idx]
        # level 2: union across pods
        g_pts = jax.lax.all_gather(lvl2, "pod", tiled=True)
        g_rad = jax.lax.pmax(jnp.maximum(radius, res.radius), ("pod", "data"))
        return g_pts, g_rad

    fn = shard_map(body, mesh=mesh, in_specs=P(("pod", "data")),
                   out_specs=(P(), P()), check_vma=False)
    g_pts, g_rad = jax.jit(fn)(points)
    m = g_pts.shape[0]
    return Coreset(points=g_pts, valid=jnp.ones((m,), bool),
                   weights=jnp.ones((m,), jnp.int32), radius=g_rad,
                   cert=cert)


# --------------------------------------------------------------------------
# simulated-reducer path (CPU benchmarks; paper Fig 4/5 parallelism sweeps)
# --------------------------------------------------------------------------

def partition_shards(points, num_reducers: int, *, partition: str = "contiguous",
                     seed: int = 0, labels=None):
    """Reducer-partition prep shared by the simulated MR paths.

    Pads the input to a multiple of ``num_reducers`` by repeating leading rows
    (duplicates only add candidates — they never win a greedy pick while a
    distinct point remains, and crucially no point is DROPPED: truncation
    would break quota feasibility for tiny groups in the constrained path).

    ``partition``: 'contiguous' | 'random' | 'adversarial' (paper §7.2 —
    adversarial = sort by first coordinate so each reducer sees a small-volume
    region).  Returns (pts (l·per, d), shards (l, per, d), slabels or None).
    """
    pts = np.asarray(points)
    n, d = pts.shape
    lab = None if labels is None else np.asarray(labels)
    per = -(-n // num_reducers)                      # ceil
    pad = per * num_reducers - n
    if pad:
        pts = np.concatenate([pts, pts[:pad]])
        if lab is not None:
            lab = np.concatenate([lab, lab[:pad]])
    if partition == "random":
        rng = np.random.default_rng(seed)
        perm = rng.permutation(per * num_reducers)
        pts = pts[perm]
        lab = None if lab is None else lab[perm]
    elif partition == "adversarial":
        order = np.argsort(pts[:, 0], kind="stable")
        pts = pts[order]
        lab = None if lab is None else lab[order]
    shards = jnp.asarray(pts.reshape(num_reducers, per, d))
    slabels = None if lab is None else jnp.asarray(lab.reshape(num_reducers,
                                                               per))
    return pts, shards, slabels

@functools.partial(jax.jit, static_argnames=("k", "kprime", "metric", "mode",
                                             "b", "chunk", "schedule"))
def _sim_reducer(shard, k: int, kprime: int, metric: str, mode: str,
                 b: int = 1, chunk: int = 0, schedule=None):
    """Round 1 of one simulated reducer on its (per, d) shard: the same
    unbatched program a device of the mesh path runs.  (A vmap over the
    reducers batches the distance matmul, which a TPU rounds differently
    from the per-device one, so the two paths' core-sets would part.)
    Returns (points, valid, radius) with a leading reducer axis of 1."""
    if mode == "plain":
        pts, radius = _local_coreset_plain(shard, kprime, metric, False,
                                           b, chunk, schedule)
        out = pts, jnp.ones((kprime,), bool), radius
    elif mode == "ext":
        ext = _gmm_ext(shard, k, kprime, metric=metric, b=b, chunk=chunk,
                       schedule=schedule)
        out = (shard[ext.delegate_idx.reshape(-1)],
               ext.delegate_valid.reshape(-1), ext.radius)
    else:  # gen
        g = _gmm_gen(shard, k, kprime, metric=metric, b=b, chunk=chunk,
                     schedule=schedule)
        out = g.points, g.multiplicity > 0, g.radius
    return tuple(x[None] for x in out)


def _stack_reducers(outs):
    return tuple(jnp.concatenate([o[j] for o in outs], axis=0)
                 for j in range(3))


def _sim_round1(shards, args, *, detail: bool = False):
    """Round 1 of every simulated reducer, one ``_sim_reducer`` dispatch
    each (``args``: its static arguments); returns the reducer-stacked
    (points, valid, radius).

    ``detail`` (``ExecutionSpec(trace="reducers")``) fences every reducer
    in its own span, so each gets a real wall-clock.  The per-reducer times
    feed ``distributed.fault_tolerance.StragglerPolicy`` (warmup-aware:
    reducer 0 carries the jit compile) and flagged reducers land in the
    trace extras as ``mr_stragglers``.  Slower than the pipelined dispatch
    by construction — an observability mode, not a production path."""
    from repro.distributed.fault_tolerance import StragglerPolicy

    policy = StragglerPolicy(min_history=3)
    outs, stragglers = [], []
    for i in range(int(shards.shape[0])):
        if not detail:
            outs.append(_sim_reducer(shards[i], *args))
            continue
        with _span(f"mr.reducer[{i}]", reducer=i) as sp:
            outs.append(jax.block_until_ready(_sim_reducer(shards[i], *args)))
        if sp is not None and policy.observe(sp.seconds):
            stragglers.append(i)
    _count("device_dispatches", len(outs))
    tr = _obs_active()
    if detail and tr is not None:
        tr.annotate(mr_stragglers=tuple(stragglers))
    return _stack_reducers(outs)


def _sim_round1_resilient(shards, args, policy):
    """Round 1 under a ``ResiliencePolicy``: the reducers of
    ``_sim_round1``, each an independently retryable unit.  Failed
    reducers (``on_failure="degrade"``) contribute an all-zeros block with
    ``valid=False`` — the merged layout is identical to the plain run,
    and the composable core-set property keeps the surviving union a valid
    core-set of the surviving shards.  Returns (pts, valid, radius, report).
    """
    from repro.distributed.fault_tolerance import run_resilient

    l = int(shards.shape[0])

    def run_one(i):
        with _span(f"mr.reducer[{i}]", reducer=i):
            out = jax.block_until_ready(_sim_reducer(shards[i], *args))
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
    return _stack_reducers(outs) + (report,)


def _simulate_mr_impl(points, k: int, measure: str, *, num_reducers: int,
                      kprime=None, metric="euclidean",
                      generalized: bool = False,
                      partition: str = "contiguous",
                      seed: int = 0, b=1, chunk: int = 0, eps: float = 0.1,
                      tau=None, cliff=None, resilience=None):
    """Execution body of the simulated ℓ-reducer MR run (no deprecation
    warning — the ``repro.diversify`` facade routes here).  Returns
    (sol, value, cs, report) — ``report`` is the ``ResilienceReport`` when a
    ``ResiliencePolicy`` governed the run, else None."""
    if kprime is None:
        kprime = max(2 * k, 32)
    pts, shards, _ = partition_shards(points, num_reducers,
                                      partition=partition, seed=seed)
    d = pts.shape[1]
    per_shard = int(shards.shape[1])
    kprime, schedule, b, cert = _resolve_reducer_plan(
        pts, k, kprime, b, eps=eps, metric=metric, chunk=chunk,
        per_shard=per_shard, tau=tau, cliff=cliff)

    mode = ("gen" if generalized else
            "ext" if measure in NEEDS_INJECTIVE else "plain")
    if _counting():
        _count_round1(num_reducers, per_shard, d, kprime, b,
                      schedule, mode)
    args = (k, kprime, metric, mode, b, chunk, schedule)
    report = None
    if resilience is not None:
        g_pts, g_valid, g_rad, report = _sim_round1_resilient(
            shards, args, resilience)
    elif _reducer_detail():
        g_pts, g_valid, g_rad = _sim_round1(shards, args, detail=True)
    else:
        with _span("mr.round1", reducers=num_reducers, kprime=kprime):
            g_pts, g_valid, g_rad = _sim_round1(shards, args)
            if _counting():
                jax.block_until_ready(g_rad)
    flat_pts = g_pts.reshape(-1, d)
    flat_valid = g_valid.reshape(-1)
    radius = jnp.max(g_rad)
    if report is not None and report.degraded:
        from repro.distributed.fault_tolerance import degraded_certificate
        cert = degraded_certificate(cert, kprime=kprime,
                                    radius=float(radius),
                                    survivors=report.survivors,
                                    total=num_reducers, per_shard=per_shard)

    if generalized:
        # rerun per-shard to obtain integer multiplicities (survivors only
        # under a degraded run — the dropped shards contribute nothing)
        survivors = (tuple(range(num_reducers)) if report is None
                     else report.survivors)
        gshards = (shards if len(survivors) == num_reducers
                   else shards[jnp.asarray(survivors)])

        def one(s):
            g = _gmm_gen(s, k, kprime, metric=metric, b=b, chunk=chunk,
                         schedule=schedule)
            return g.points, g.multiplicity, g.radius
        with _span("mr.round1.multiplicities", reducers=len(survivors)):
            gp, gm, gr = jax.jit(jax.vmap(one))(gshards)
            _count("device_dispatches")
            if _counting():
                jax.block_until_ready(gr)
        cs = GeneralizedCoreset(points=gp.reshape(-1, d),
                                multiplicity=gm.reshape(-1),
                                radius=jnp.max(gr), cert=cert)
        p, m = cs.compact()
        idx = solve(measure, p, k, weights=m, metric=metric)
        uniq, counts = np.unique(idx, return_counts=True)
        sol = instantiate(p[uniq], counts, pts, float(cs.radius),
                          metric=metric)
    else:
        cs = Coreset(points=flat_pts, valid=flat_valid,
                     weights=flat_valid.astype(jnp.int32), radius=radius,
                     cert=cert)
        sol = solve_on_coreset(cs, k, measure, metric=metric)

    met = get_metric(metric)
    dm = np.asarray(met.pairwise(jnp.asarray(sol), jnp.asarray(sol)))
    return sol, diversity(measure, dm), cs, report


def simulate_mr(points, k: int, measure: str, *, num_reducers: int,
                kprime=None, metric="euclidean",
                generalized: bool = False, partition: str = "contiguous",
                seed: int = 0, b=1, chunk: int = 0, eps: float = 0.1,
                tau=None, cliff=None):
    """Simulate the ℓ-reducer 2-round MR run on one device, one dispatch per
    reducer.

    Legacy spelling of ``repro.diversify`` with ``ExecutionSpec(
    mode="mapreduce", num_reducers=...)`` — prefer the facade for new code.
    ``partition``: 'contiguous' | 'random' | 'adversarial' (paper §7.2 —
    adversarial = sort by first coordinate so each reducer sees a small-volume
    region).  ``b="auto"`` / ``kprime="auto"`` probe once and freeze a static
    reducer schedule, exactly like ``mr_coreset``."""
    from repro.api import (ExecutionSpec, ProblemSpec, _warn_legacy,
                           diversify)

    _warn_legacy("repro.core.distributed.simulate_mr")
    res = diversify(
        ProblemSpec(points=points, k=k, measure=measure, metric=metric),
        ExecutionSpec(mode="mapreduce", num_reducers=num_reducers,
                      kprime=kprime, b=b, chunk=chunk, eps=eps,
                      generalized=generalized, partition=partition,
                      seed=seed, tau=tau, cliff=cliff))
    return res.solution, res.value
