"""GMM (Gonzalez' greedy k-center) and the paper's extensions.

``gmm``       — the kernel construction of Lemma 5 / Thm 4 (remote-edge/cycle).
``gmm_ext``   — kernel + up-to-(k-1) delegates per cluster (Lemma 6 / Thm 5).
``gmm_gen``   — kernel + multiplicities: generalized core-sets (Lemma 8 / Thm 10).

TPU adaptation (see DESIGN.md §2): each GMM round is one fused pass over the
local point set — distance to the newest center, running min, and argmax are
fused so HBM traffic is one read of ``points`` per round.  The distance uses the
``||x||² − 2x·c + ||c||²`` factorization so the bulk lands on the MXU as a
matmul when centers are blocked.  ``use_pallas=True`` routes the inner update
through the Pallas kernels (``repro.kernels.ops.gmm_update`` for b=1,
``ops.gmm_topb`` for the batched engine); the default pure lax path lowers to
the same fused HLO and is what the CPU test-suite exercises.

Single-sweep selection engine: ``gmm_batched`` (lookahead-``b`` center
blocking + chunk fusion) is the shared engine behind every core-set path —
``gmm_ext``/``gmm_gen`` here, the MapReduce reducers
(``core.distributed``, ``constrained.mapreduce``) and the grouped
(partition-matroid) builder (``constrained.coreset``) all take ``b``/``chunk``
knobs that bottom out in it.  Tuning guidance: ``b`` in 4–16 cuts the number
of point-set sweeps ~b× at a few-% anticover-radius cost (b=1 is exact
sequential GMM); ``chunk`` sizes the fused tile of the jax-level sweep
(2–8k rows; it is snapped down to divide n) and is ignored when the Pallas
kernel supplies the tiling.

All shapes are static; invalid points are handled with ``mask`` (their distance
is pinned to −inf so they are never selected and never win an argmax).
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.obs.trace import (count as _count, counting as _counting,
                             sweep_bytes as _sweep_bytes)

from .coreset import GeneralizedCoreset
from .metrics import get_metric


def _host_counting(x) -> bool:
    """Counters fire only on real host-driver calls: a call made while
    tracing another jit (x is a Tracer) runs once per compile, not per
    execution, so counting there would be wrong."""
    return _counting() and not isinstance(x, jax.core.Tracer)


def gather_rows(points, idx):
    """``points[idx]``, fetched as a gather of at least two rows.  On a TPU
    an (n, 3) array is stored column-major (``T(4,128)``); a one-row fetch
    inside a loop lowers to a dynamic-slice, for which XLA copies the whole
    array row-major and padded to 128 lanes (32 GB at n=2^26).  A gather of
    two or more rows reads it in place."""
    idx = jnp.asarray(idx)
    if idx.size > 1:
        return points[idx]
    flat = idx.reshape(-1)
    rows = points[jnp.concatenate([flat, flat])][:1]
    return rows.reshape(idx.shape + points.shape[1:])


class GMMResult(NamedTuple):
    idx: jnp.ndarray        # (k,) int32 — selected indices into points
    radius: jnp.ndarray     # () — max_p d(p, T)  (range r_T of the returned set)
    min_dist: jnp.ndarray   # (n,) — d(p, T) for every point
    assign: jnp.ndarray     # (n,) int32 — index (into 0..k-1) of nearest center
    sel_dist: jnp.ndarray   # (k,) — distance of each center to the prefix before it
                            #        (anticover distances; sel_dist[0] = +inf)


def _point_to_set_dist(metric, points, center):
    return metric.point_to_set(points, center)


@functools.partial(jax.jit, static_argnames=("k", "metric_name", "use_pallas"))
def _gmm_impl(points, mask, start, k: int, metric_name: str, use_pallas: bool):
    metric = get_metric(metric_name)
    n = points.shape[0]
    neg_inf = jnp.asarray(-jnp.inf, points.dtype)

    if use_pallas:
        from repro.kernels import ops as kops

        def update_select(min_dist, center):
            return kops.gmm_update_select(points, center[None, :], min_dist,
                                          mask, metric_name)
    else:
        def update_select(min_dist, center):
            d = _point_to_set_dist(metric, points, center)
            new = jnp.minimum(min_dist, d)
            masked = jnp.where(mask, new, neg_inf)
            j = jnp.argmax(masked)
            return new, j, masked[j]

    def body(i, state):
        min_dist, assign, idx, sel_dist, _ = state
        # distance from all points to the center chosen at step i-1; fused
        # running-min + masked argmax (one HBM sweep on the Pallas path)
        center = gather_rows(points, idx[i - 1])
        new_dist, j, jmax = update_select(min_dist, center)
        assign = jnp.where(new_dist < min_dist, i - 1, assign)
        idx = idx.at[i].set(j, mode="drop")          # i == k write is dropped
        sel_dist = sel_dist.at[i].set(jmax, mode="drop")
        return new_dist, assign, idx, sel_dist, jmax

    idx0 = jnp.zeros((k,), jnp.int32).at[0].set(start)
    min_dist0 = jnp.full((n,), jnp.inf, points.dtype)
    assign0 = jnp.zeros((n,), jnp.int32)
    sel_dist0 = jnp.full((k,), jnp.inf, points.dtype)
    min_dist, assign, idx, sel_dist, radius = jax.lax.fori_loop(
        1, k + 1, body, (min_dist0, assign0, idx0, sel_dist0,
                         jnp.asarray(jnp.inf, points.dtype))
    )
    # body ran for i = 1..k: min_dist/assign include the k-th center and
    # ``radius`` is the masked max after the final update (= r_T).
    return GMMResult(idx=idx, radius=radius, min_dist=min_dist, assign=assign,
                     sel_dist=sel_dist)


def gmm(points, k: int, *, metric="euclidean", mask=None, start=0,
        use_pallas: bool = False) -> GMMResult:
    """Run GMM(points, k).  Returns indices + anticover telemetry.

    The returned set satisfies the anticover property: r_T <= sel_dist[k-1]
    <= rho_T, which Fact 1 of the paper builds on.
    """
    points = jnp.asarray(points)
    n = points.shape[0]
    if not 1 <= k <= n:
        raise ValueError(f"k={k} out of range for n={n}")
    if mask is None:
        mask = jnp.ones((n,), bool)
    if _host_counting(points):
        _count("device_dispatches")
        _count("distance_evals", n * k)
        _count("bytes_swept", _sweep_bytes(n, points.shape[1], sweeps=k))
    return _gmm_impl(points, mask, jnp.asarray(start, jnp.int32), k,
                     get_metric(metric).name, use_pallas)


# --------------------------------------------------------------------------
# the single-sweep selection engine (schedule-driven, group-blocked)
#
# One implementation serves every core-set path: the unconstrained batched
# GMM is the m=1 case of the grouped (per-label lock-step) engine, and a
# selection *schedule* — a tuple of (block, rounds) phases — generalizes the
# fixed lookahead-b loop so the adaptive controller (``core.adaptive``) and
# the MapReduce reducers (which need a static plan inside shard_map) share
# the same compiled body.  Each sweep records the masked field max — the
# exact anticover radius of the set selected so far — at zero extra cost,
# which is what the radius certificates are built from.
# --------------------------------------------------------------------------

def _make_grouped_sweep(points, labels, m: int, p: int, chunk: int,
                        metric_name: str, use_pallas: bool):
    """Build the fused sweep closure: fold a center block into the shared
    running-min field and extract every group's top-``p`` candidates.

    ``centers`` is (m, bc, d) — ``bc`` centers per group; a point only folds
    its OWN group's block (the per-group GMM runs are independent), so each
    sweep costs n·bc·d distance work and the field stays (n,).  ``m == 1``
    takes the matmul fast path (no per-point gather); rows with label < 0
    (mask padding) match no group and can never be selected.
    """
    metric = get_metric(metric_name)
    n, d = points.shape
    neg_inf = jnp.asarray(-jnp.inf, jnp.float32)

    if m == 1:
        mask = labels >= 0
        if use_pallas:
            from repro.kernels import ops as kops

            def sweep(min_dist, centers):
                md, cd, ci = kops.gmm_topb(points, centers[0], min_dist,
                                           mask, metric_name, p=p)
                return md, cd[None, :], ci[None, :]
            return sweep

        nch = n // chunk

        def sweep(min_dist, centers):
            c2 = centers[0]                               # (bc, d)

            def chunk_fn(c):
                x = jax.lax.dynamic_slice(points, (c * chunk, 0), (chunk, d))
                md = jax.lax.dynamic_slice(min_dist, (c * chunk,), (chunk,))
                mk = jax.lax.dynamic_slice(mask, (c * chunk,), (chunk,))
                dist = metric.pairwise(x, c2)             # (chunk, bc)
                new_md = jnp.minimum(md, jnp.min(dist, axis=1))
                masked = jnp.where(mk, new_md, neg_inf)
                cd, ci = jax.lax.top_k(masked, min(p, chunk))
                return new_md, cd, (ci + c * chunk).astype(jnp.int32)

            new_md, cd, ci = jax.lax.map(chunk_fn, jnp.arange(nch))
            min_dist = new_md.reshape(n)
            flat_d, flat_i = cd.reshape(-1), ci.reshape(-1)
            sel_d, sel = jax.lax.top_k(flat_d, min(p, flat_d.shape[0]))
            return min_dist, sel_d[None, :], flat_i[sel][None, :]
        return sweep

    if use_pallas:
        from repro.kernels import ops as kops

        def sweep(min_dist, centers):
            return kops.grouped_gmm_topb(points, centers, min_dist, labels,
                                         metric_name, p)
        return sweep

    nch = n // chunk
    gids = jnp.arange(m, dtype=labels.dtype)[:, None]
    safe_lab = jnp.clip(labels, 0, m - 1)         # pad rows (-1) -> any group

    def sweep(min_dist, centers):
        """One fused pass for all groups: each point gathers its own group's
        bc-center block ((chunk, bc, d) — n·bc·d distance work total),
        updates the shared running-min field, and every group's chunk-local
        top-p is extracted under its label mask; the (n, m·bc) distance
        matrix never exists."""

        def chunk_fn(c):
            x = jax.lax.dynamic_slice(points, (c * chunk, 0), (chunk, d))
            lb = jax.lax.dynamic_slice(labels, (c * chunk,), (chunk,))
            sl = jax.lax.dynamic_slice(safe_lab, (c * chunk,), (chunk,))
            md = jax.lax.dynamic_slice(min_dist, (c * chunk,), (chunk,))
            cen = centers[sl]                         # (chunk, bc, d)
            dist = jax.vmap(metric.point_to_set)(cen, x)   # (chunk, bc)
            new_md = jnp.minimum(md, jnp.min(dist, axis=1))
            masked = jnp.where(lb[None, :] == gids, new_md[None, :],
                               neg_inf)               # (m, chunk)
            cd, ci = jax.lax.top_k(masked, min(p, chunk))   # (m, p)
            return new_md, cd, (ci + c * chunk).astype(jnp.int32)

        new_md, cd, ci = jax.lax.map(chunk_fn, jnp.arange(nch))
        pc = cd.shape[2]
        min_dist = new_md.reshape(n)
        flat_d = jnp.moveaxis(cd, 0, 1).reshape(m, nch * pc)
        flat_i = jnp.moveaxis(ci, 0, 1).reshape(m, nch * pc)
        sel_d, sel = jax.lax.top_k(flat_d, min(p, nch * pc))  # merge
        return min_dist, sel_d, jnp.take_along_axis(flat_i, sel, axis=1)

    return sweep


def _grouped_inblock(points, metric_name: str, cand_d, cand_i, take: int):
    """Exact local GMM over each group's candidate pool (vmapped; p×p):
    greedily keep ``take`` of the p candidates, correcting for mutual
    distances within the pool.  Returns (chosen (m, take), seld (m, take))
    where ``seld[g, j]`` is pick j's corrected anticover distance — the
    greedy-consistency signal the adaptive controller and the radius
    certificates consume."""
    metric = get_metric(metric_name)
    neg_inf = jnp.asarray(-jnp.inf, jnp.float32)

    def one(cd, ci):
        pool = points[ci]                            # (p, d) candidates

        def pick(j, carry):
            cd, chosen, seld = carry
            s = jnp.argmax(cd)
            chosen = chosen.at[j].set(ci[s])
            seld = seld.at[j].set(cd[s])
            dd = metric.point_to_set(pool, pool[s])
            cd = jnp.minimum(cd, dd).at[s].set(neg_inf)
            return cd, chosen, seld

        _, chosen, seld = jax.lax.fori_loop(
            0, take, pick, (cd, jnp.zeros((take,), jnp.int32),
                            jnp.zeros((take,), jnp.float32)))
        return chosen, seld

    return jax.vmap(one)(cand_d, cand_i)


def validate_schedule(schedule, k: int):
    """A schedule is a tuple of (block, rounds) phases covering k picks."""
    total = 0
    for i, (b, r) in enumerate(schedule):
        if b < 1 or r < 1:
            raise ValueError(f"bad schedule phase {(b, r)}")
        total += b * r
    if total != k:
        raise ValueError(f"schedule {schedule} covers {total} picks, not {k}")
    return tuple((int(b), int(r)) for b, r in schedule)


def schedule_sweep_counts(schedule):
    """Centers folded into the field at each sweep of ``schedule`` — the
    x-axis of the radius trajectory the engine emits (the final entry is the
    full selection, whose field max is the measured anticover radius)."""
    counts = []
    pos = 0
    for pi, (b, r) in enumerate(schedule):
        if pi == 0 and b > 1:
            counts.append(1)                      # seed sweep
        elif pi > 0:
            counts.append(pos)                    # transition sweep
        counts.extend(pos + t * b for t in range(1, r))
        pos += r * b
    counts.append(pos)                            # final fold
    return tuple(counts)


def schedule_fold_sizes(schedule):
    """Centers folded into the field BY each sweep (companion to
    ``schedule_sweep_counts``; same length).  ``n x sum(fold_sizes)`` is the
    engine's exact distance-evaluation count for the schedule — the number
    the ``distance_evals`` counter reports."""
    folds = []
    for pi, (b, r) in enumerate(schedule):
        if pi == 0 and b > 1:
            folds.append(1)                       # seed sweep
        elif pi > 0:
            folds.append(schedule[pi - 1][0])     # transition sweep
        folds.extend([b] * (r - 1))
    folds.append(schedule[-1][0])                 # final fold
    return tuple(folds)


@functools.partial(jax.jit, static_argnames=("m", "k", "schedule", "chunk",
                                             "metric_name", "use_pallas"))
def _schedule_select_impl(points, labels, starts, m: int, k: int, schedule,
                          chunk: int, metric_name: str, use_pallas: bool):
    """All ``m`` per-group GMM runs in lock-step under a selection schedule.

    Phase (b, r) selects r blocks of b centers each; b > 1 sweeps oversample
    4b candidates per group and an exact in-block GMM keeps the best b (the
    same lookahead the grouped engine shipped with, now shared by the
    unconstrained path — including block 0, which lookahead-fills slots
    1..b-1 from the seed sweep's pool instead of b thin sweeps).  b = 1 is
    exact sequential GMM, bit-for-bit.

    Returns (idx (m, k), radius (m,), min_dist (n,), traj (S, m),
    bcd (S-1, m)) where S = len(schedule_sweep_counts(schedule)); ``traj[s]``
    is each group's exact anticover radius after folding
    ``schedule_sweep_counts(...)[s]`` centers and ``bcd[s]`` is the minimum
    corrected pick distance of the block selected at sweep s (the
    greedy-consistency margin: a selection is anticover-certified when every
    block's margin stays above the final radius).
    """
    n, _ = points.shape
    S = len(schedule_sweep_counts(schedule))

    idx = jnp.zeros((m, k), jnp.int32).at[:, 0].set(starts)
    md = jnp.full((n,), jnp.inf, jnp.float32)
    traj = jnp.full((S, m), jnp.inf, jnp.float32)
    bcd = jnp.full((S - 1, m), jnp.inf, jnp.float32)

    sweeps = {}

    def get_sweep(p):
        if p not in sweeps:
            sweeps[p] = _make_grouped_sweep(points, labels, m, p, chunk,
                                            metric_name, use_pallas)
        return sweeps[p]

    sc = 0          # python sweep counter (static per phase)
    pos = 0         # python picks committed (static per phase)
    for pi, (b, r) in enumerate(schedule):
        p = min(4 * b, n) if b > 1 else 1
        sweep = get_sweep(p)
        if pi == 0 and b > 1:
            # seed sweep: fold the per-group seeds, lookahead-fill 1..b-1
            md, cd, ci = sweep(md, points[idx[:, 0]][:, None, :])
            traj = traj.at[sc].set(cd[:, 0])
            chosen, seld = _grouped_inblock(points, metric_name, cd, ci, b)
            idx = idx.at[:, 1:b].set(chosen[:, :b - 1])
            bcd = bcd.at[sc].set(jnp.min(seld[:, :b - 1], axis=1))
            sc += 1
        elif pi > 0:
            # transition sweep: fold the previous phase's pending block
            prev_b = schedule[pi - 1][0]
            prev = jax.lax.dynamic_slice(idx, (0, pos - prev_b), (m, prev_b))
            md, cd, ci = sweep(md, points[prev])
            traj = traj.at[sc].set(cd[:, 0])
            chosen, seld = _grouped_inblock(points, metric_name, cd, ci, b)
            idx = jax.lax.dynamic_update_slice(idx, chosen, (0, pos))
            bcd = bcd.at[sc].set(jnp.min(seld, axis=1))
            sc += 1
        if r > 1:
            base, sc_base = pos, sc

            def body(t, state, b=b, base=base, sc_base=sc_base, sweep=sweep):
                md, idx, traj, bcd = state
                prev = jax.lax.dynamic_slice(idx, (0, base + (t - 1) * b),
                                             (m, b))
                md, cd, ci = sweep(md, gather_rows(points, prev))
                si = sc_base + t - 1
                traj = jax.lax.dynamic_update_slice(traj, cd[:, :1].T,
                                                    (si, 0))
                chosen, seld = _grouped_inblock(points, metric_name, cd, ci,
                                                b)
                idx = jax.lax.dynamic_update_slice(idx, chosen,
                                                   (0, base + t * b))
                bcd = jax.lax.dynamic_update_slice(
                    bcd, jnp.min(seld, axis=1)[None, :], (si, 0))
                return md, idx, traj, bcd

            md, idx, traj, bcd = jax.lax.fori_loop(1, r, body,
                                                   (md, idx, traj, bcd))
            sc += r - 1
        pos += r * b

    # final fold: the per-group masked max IS the anticover radius r_T
    last_b = schedule[-1][0]
    prev = jax.lax.dynamic_slice(idx, (0, k - last_b), (m, last_b))
    md, cd, _ = get_sweep(1)(md, points[prev])
    traj = traj.at[S - 1].set(cd[:, 0])
    return idx, cd[:, 0], md, traj, bcd


def effective_block(k: int, b: int) -> int:
    """Largest selection-block size <= b that divides k (the engines select
    whole center blocks, so k must split into blocks; gcd keeps the caller's
    intent while staying exact on the block structure)."""
    import math
    if b <= 1:
        return 1
    return b if k % b == 0 else math.gcd(k, b)


def _adjust_chunk(n: int, chunk: int) -> int:
    """Clamp a chunk knob to the point count (0 -> whole array).  Ragged
    tails are handled by padding (``_pad_to_chunk``), not by shrinking."""
    if not chunk:
        return n
    return max(min(chunk, n), 1)


def _pad_to_chunk(n: int, chunk: int):
    """Rows of padding needed so chunk divides the point count."""
    return -(-n // chunk) * chunk - n


def pad_for_engine(points, labels, chunk: int):
    """Snap ``chunk`` to the point count and pad (points, labels) so that it
    divides n — pad rows carry label -1, which matches no group, so they can
    never be selected or counted.  Works under tracing (shapes are static).

    ``chunk=0`` defaults to 4096-row tiles (not the whole array): the sweep
    and the ext assign pass gather per-point center blocks, so an unbounded
    chunk would materialize an (n, b·d)/(n, k'·d) tile and defeat the
    engine's cache/VMEM-resident design.  b=1 selection is chunk-invariant
    (per-chunk top-k + first-max merge == global argmax), so the default
    only bounds memory, never changes results."""
    n = points.shape[0]
    ch = _adjust_chunk(n, chunk or 4096)
    pad = _pad_to_chunk(n, ch)
    if pad:
        points = jnp.pad(points, ((0, pad), (0, 0)))
        labels = jnp.pad(labels, (0, pad), constant_values=-1)
    return points, labels, ch


def mask_to_labels(mask):
    """Unconstrained masks as engine labels: valid rows are group 0, masked
    rows carry the sentinel label -1 (never selectable)."""
    return jnp.where(mask, 0, -1).astype(jnp.int32)


class ScheduleResult(NamedTuple):
    idx: jnp.ndarray        # (k,) selected indices
    radius: jnp.ndarray     # () — measured anticover radius r_T
    min_dist: jnp.ndarray   # (n,) — d(p, T) for every point
    counts: tuple           # static: centers folded at each sweep
    traj: jnp.ndarray       # (S,) — anticover radius at each sweep
    margins: jnp.ndarray    # (S-1,) — per-block min corrected pick distance
    schedule: tuple         # the executed (block, rounds) phases


def gmm_schedule(points, k: int, schedule, *, metric="euclidean", mask=None,
                 start=0, chunk: int = 0,
                 use_pallas: bool = False) -> ScheduleResult:
    """Run the selection engine under an explicit (block, rounds) schedule
    and return the full radius telemetry (trajectory + greedy-consistency
    margins).  This is the primitive behind ``gmm_batched`` (single-phase
    schedules), the MapReduce ``b="auto"`` plans (static multi-phase
    schedules resolved by a probe) and the certificates of
    ``core.adaptive``."""
    points = jnp.asarray(points)
    n = points.shape[0]
    schedule = validate_schedule(schedule, k)
    if mask is None:
        mask = jnp.ones((n,), bool)
    labels = mask_to_labels(mask)
    pts_p, lab_p, ch = pad_for_engine(points, labels, chunk)
    if _host_counting(points):
        folds = schedule_fold_sizes(schedule)
        _count("device_dispatches")
        _count("distance_evals", n * sum(folds))
        _count("bytes_swept",
               _sweep_bytes(n, points.shape[1], sweeps=len(folds)))
    idx, radius, min_dist, traj, bcd = _schedule_select_impl(
        pts_p, lab_p, jnp.asarray([start], jnp.int32), 1, k, schedule, ch,
        get_metric(metric).name, use_pallas)
    return ScheduleResult(idx=idx[0], radius=radius[0],
                          min_dist=min_dist[:n],
                          counts=schedule_sweep_counts(schedule),
                          traj=traj[:, 0], margins=bcd[:, 0],
                          schedule=schedule)


def gmm_batched(points, k: int, *, b=8, metric="euclidean", mask=None,
                start=0, chunk: int = 0, use_pallas: bool = False,
                schedule=None, sprint="auto"):
    """Batched GMM (beyond-paper optimization, EXPERIMENTS.md §Perf).

    Sequential GMM sweeps the point set once per center — arithmetic
    intensity ~0.5 flop/byte, hopelessly memory-bound.  This variant selects
    ``b`` centers per sweep: each sweep oversamples the top-4b candidates of
    the running min-distance field and an exact in-block correction (local
    GMM over the pool) keeps the best b.  HBM traffic drops ~b×; the
    selection differs from exact GMM only when a sweep's farthest-point
    field changes rank order mid-block (tests show the anticover radius
    within a few % of exact on benchmark distributions).  Block 0 is seeded
    the same way: one sweep from ``start`` lookahead-fills slots 1..b-1 from
    the oversampled pool, so a full run costs k/b + 1 sweeps.

    Tuning: ``b`` trades HBM traffic for selection fidelity — 4–16 is the
    sweet spot; b=1 is exact sequential GMM, bit-for-bit, and ``b="auto"``
    runs the radius-certified adaptive controller (``core.adaptive``), which
    shrinks the block to 1 as the radius curve flattens.  ``chunk`` bounds
    the per-sweep working set of the jax-level fused path (2–8k rows
    typically; 0 defaults to 4096-row tiles).  ``use_pallas=True`` swaps the
    chunked sweep for the fused ``gmm_topb`` kernel (chunking then happens
    in the kernel grid).  ``schedule`` overrides ``b`` with an explicit
    (block, rounds) phase plan (see ``gmm_schedule``).

    Without a schedule, k must be a multiple of b (use ``effective_block``
    to snap a knob).
    """
    if b == "auto" and schedule is None:
        from .adaptive import gmm_adaptive
        res = gmm_adaptive(points, k, metric=metric, mask=mask, start=start,
                           chunk=chunk, use_pallas=use_pallas, sprint=sprint)
        return res.idx, res.radius, res.min_dist
    if schedule is None:
        if k % b:
            raise ValueError(f"k={k} must be a multiple of b={b}")
        schedule = ((b, k // b),)
    res = gmm_schedule(points, k, schedule, metric=metric, mask=mask,
                       start=start, chunk=chunk, use_pallas=use_pallas)
    return res.idx, res.radius, res.min_dist


class GMMExtResult(NamedTuple):
    kernel_idx: jnp.ndarray     # (k',) kernel (center) indices
    delegate_idx: jnp.ndarray   # (k', k) indices; row j = center j + delegates
    delegate_valid: jnp.ndarray # (k', k) bool
    multiplicity: jnp.ndarray   # (k',) int32 = min(|C_j|, k)   (GMM-GEN output)
    radius: jnp.ndarray         # () kernel range r_T'
    assign: jnp.ndarray         # (n,) nearest-kernel-center assignment


def delegates_from_assign(idx, assign, mask, k: int, kprime: int):
    """Delegate extraction shared by GMM-EXT and the grouped (constrained)
    engine: given the kernel ``idx`` (k',) and a nearest-kernel-center
    ``assign`` (n,), compute the per-cluster delegate table.

    Returns (cand (k', k), valid (k', k), mult (k',), assign (n,)) where
    ``assign`` has invalid rows rerouted to the sentinel cluster k' and each
    center forced into its own cluster.
    """
    n = assign.shape[0]
    assign = jnp.where(mask, assign, kprime)  # invalid -> sentinel cluster
    # force each center into its own cluster (it is, by construction: dist 0,
    # but ties at 0 could have attached it to an earlier co-located center).
    assign = assign.at[idx].set(jnp.arange(kprime, dtype=jnp.int32))

    order = jnp.argsort(assign, stable=True)              # (n,)
    sorted_assign = assign[order]
    counts = jnp.bincount(assign, length=kprime + 1)[:kprime]
    starts = jnp.searchsorted(sorted_assign, jnp.arange(kprime))

    # delegate slot t of cluster j = order[starts[j] + t], valid while t < count
    t_grid = jnp.arange(k)[None, :]                       # (1, k)
    gather_pos = starts[:, None] + t_grid                 # (k', k)
    gather_pos = jnp.clip(gather_pos, 0, n - 1)
    cand = order[gather_pos]                              # (k', k)
    valid = t_grid < counts[:, None]

    # force-include the center in slot 0 (swap it in; if the center already
    # appears in another slot, that slot harmlessly duplicates — dedupe by
    # masking duplicates of slot 0)
    cand = cand.at[:, 0].set(idx)
    dup0 = (cand == idx[:, None]) & (jnp.arange(k)[None, :] > 0)
    valid = valid & ~dup0
    valid = valid.at[:, 0].set(counts > 0)

    mult = jnp.minimum(counts, k).astype(jnp.int32)
    return cand, valid, mult, assign


@functools.partial(jax.jit, static_argnames=("chunk", "metric_name"))
def _assign_to_centers_impl(points, idx, chunk: int, metric_name: str):
    """Nearest-selected-center index for every point, one chunked fused pass
    ((chunk, k') distance tile; the (n, k') matrix never materializes)."""
    metric = get_metric(metric_name)
    n, d = points.shape
    centers = points[idx]
    nch = n // chunk

    def chunk_fn(c):
        x = jax.lax.dynamic_slice(points, (c * chunk, 0), (chunk, d))
        dist = metric.pairwise(x, centers)               # (chunk, k')
        return jnp.argmin(dist, axis=1).astype(jnp.int32)

    return jax.lax.map(chunk_fn, jnp.arange(nch)).reshape(n)


def _assign_to_centers(points, idx, chunk: int, metric_name: str):
    """Padding wrapper for ``_assign_to_centers_impl`` (any chunk size)."""
    n = points.shape[0]
    if _host_counting(points):
        _count("device_dispatches")
        _count("distance_evals", n * int(idx.shape[0]))
        _count("bytes_swept", _sweep_bytes(n, points.shape[1]))
    ch = _adjust_chunk(n, chunk or 4096)
    pad = _pad_to_chunk(n, ch)
    if pad:
        points = jnp.pad(points, ((0, pad), (0, 0)))
    return _assign_to_centers_impl(points, idx, ch, metric_name)[:n]


def gmm_ext(points, k: int, kprime: int, *, metric="euclidean", mask=None,
            start=0, use_pallas: bool = False, b=1,
            chunk: int = 0, schedule=None) -> GMMExtResult:
    """GMM-EXT (Algorithm 1): kernel of k' centers + up to k-1 delegates each.

    Single scan formulation: the GMM loop already tracks the nearest-center
    assignment, so the clustering {C_j} is free; delegates are the first
    min(|C_j|, k) members of each cluster in index order, with the center
    force-included in slot 0.

    ``b > 1`` selects the kernel with the batched lookahead-b engine
    (``gmm_batched``; b is snapped to a divisor of k' via
    ``effective_block``), ``b="auto"`` with the radius-certified adaptive
    controller, and ``schedule`` with an explicit static phase plan; all
    recover the assignment with one extra chunked argmin pass —
    (k'/b + 2) sweeps total instead of k' (selection + assignment).
    """
    points = jnp.asarray(points)
    n = points.shape[0]
    if mask is None:
        mask = jnp.ones((n,), bool)
    metric_name = get_metric(metric).name
    if b != "auto" and schedule is None:
        b = effective_block(kprime, b)
    if b == "auto" or schedule is not None or b > 1 or chunk:
        idx, radius, _ = gmm_batched(points, kprime, b=b, metric=metric,
                                     mask=mask, start=start, chunk=chunk,
                                     use_pallas=use_pallas, schedule=schedule)
        assign = _assign_to_centers(points, idx, chunk, metric_name)
    else:
        res = gmm(points, kprime, metric=metric, mask=mask, start=start,
                  use_pallas=use_pallas)
        idx, radius, assign = res.idx, res.radius, res.assign
    cand, valid, mult, assign = delegates_from_assign(idx, assign, mask, k,
                                                      kprime)
    return GMMExtResult(kernel_idx=idx, delegate_idx=cand,
                        delegate_valid=valid, multiplicity=mult,
                        radius=radius, assign=assign)


def gmm_ext_from_kernel(points, idx, radius, k: int, *, metric="euclidean",
                        mask=None, chunk: int = 0) -> GMMExtResult:
    """Delegate extraction for an already-selected kernel ``idx`` (k',): one
    chunked argmin pass recovers the assignment, then the shared delegate
    table is built.  Used by the adaptive/auto paths, whose kernel selection
    happened in the host-paced controller."""
    points = jnp.asarray(points)
    n = points.shape[0]
    if mask is None:
        mask = jnp.ones((n,), bool)
    metric_name = get_metric(metric).name
    idx = jnp.asarray(idx, jnp.int32)
    kprime = int(idx.shape[0])
    assign = _assign_to_centers(points, idx, chunk, metric_name)
    cand, valid, mult, assign = delegates_from_assign(idx, assign, mask, k,
                                                      kprime)
    return GMMExtResult(kernel_idx=idx, delegate_idx=cand,
                        delegate_valid=valid, multiplicity=mult,
                        radius=jnp.asarray(radius), assign=assign)


def gmm_gen(points, k: int, kprime: int, *, metric="euclidean", mask=None,
            start=0, use_pallas: bool = False, b=1,
            chunk: int = 0, schedule=None) -> GeneralizedCoreset:
    """GMM-GEN: generalized core-set of size s(T)=k', expanded size <= k·k'."""
    ext = gmm_ext(points, k, kprime, metric=metric, mask=mask, start=start,
                  use_pallas=use_pallas, b=b, chunk=chunk, schedule=schedule)
    return GeneralizedCoreset(points=jnp.asarray(points)[ext.kernel_idx],
                              multiplicity=ext.multiplicity,
                              radius=ext.radius)
