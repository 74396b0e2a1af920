"""Radius-certified adaptive selection: auto-tuned lookahead blocks (b) and
accuracy-targeted core-set sizing (k').

The paper's (α+ε) guarantee hinges on the core-set size k' making the
anticover radius r_T(k') small against the optimal diversity; the k-center
companion line of work (Ceccarello et al., arXiv:1802.09205) shows the same
radius signal can *drive* the sizing instead of being checked after the
fact.  This module closes the loop on both engine knobs:

* **Adaptive b** (``gmm_adaptive`` / ``adaptive_select``): the lookahead-b
  engine is exact on each sweep's first pick but selects the rest of a block
  from a stale field, which degrades once k' exceeds the data's effective
  cluster count (the ROADMAP's "b=8 silently degrades" item).  Every sweep
  already measures the exact anticover radius (the masked field max) and
  every in-block pick its corrected anticover distance — the
  *greedy-consistency margin*.  Exact GMM satisfies margin >= every later
  radius; when a block's margin drops below the next measured radius the
  lookahead provably went sub-greedy, and the controller halves the block
  (down to a bit-exact b=1 continuation of plain GMM from the live state).
  The signal costs nothing: both scalars fall out of the sweep the engine
  runs anyway.

* **Auto k'** (``auto_kprime``): grow the selection geometrically and stop
  when the measured certificate hits the accuracy target.  The certificate
  compares r_T(k') against the anticover *scale* at k — the field max after
  the first k picks, a measured lower bound on the optimal remote-edge
  diversity (OPT >= rho_k >= scale_k, Fact 1) — so
  ``ratio = 2·r_T(k')/scale_k`` bounds the additive-relative core-set error
  for the remote measures; for the clique-type measures (which use the
  delegate construction on top of the same kernel) it is the standard
  conservative proxy.  Because the engine's state (field + prefix) is just a
  paused GMM run, growing k' resumes the same run — no work is repeated.

Everything is returned as a ``RadiusCertificate`` attached to the
``Coreset``/``GeneralizedCoreset`` containers, and ``resolve_engine_plan``
converts a cheap probe run into the *static* (block, rounds) schedule the
MapReduce reducers need inside ``shard_map`` (where a host-paced controller
cannot run).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.obs.trace import (count as _count, counting as _counting,
                             span as _span, sweep_bytes as _sweep_bytes)

from .gmm import (_grouped_inblock, _make_grouped_sweep, gather_rows,
                  pad_for_engine, mask_to_labels, schedule_sweep_counts,
                  validate_schedule)
from .metrics import get_metric


# Greedy-consistency bars of the adaptive-b controller (see
# ``adaptive_select``).  Tuned on the bench's synthetic families; every
# driver accepts per-call ``tau=`` / ``cliff=`` overrides that default to
# these (None anywhere in the stack means "use the module default").
DEFAULT_TAU = 0.15
DEFAULT_CLIFF = 0.35


def resolve_bars(tau: Optional[float],
                 cliff: Optional[float]) -> Tuple[float, float]:
    """Fill in the module-default tau/cliff bars for None overrides."""
    return (DEFAULT_TAU if tau is None else float(tau),
            DEFAULT_CLIFF if cliff is None else float(cliff))


def resolve_sprint(sprint, gamma: float = 0.0) -> bool:
    """Resolve the sprint knob ("auto" | True | False | None).

    Sprint mode runs post-certified multi-block segments as one fused
    ``lax.while_loop`` dispatch (``_sprint_impl``) and is bit-identical to
    the host-paced controller — EXCEPT under a nonzero cross-block
    ``gamma`` margin, whose block-halving decision is host-paced by design.
    ``"auto"``/None therefore enable sprint exactly when ``gamma == 0``
    (the default); ``True`` insists and raises on a conflicting ``gamma``;
    ``False`` keeps every block host-paced.
    """
    if sprint == "auto" or sprint is None:
        return gamma == 0.0
    if sprint and gamma != 0.0:
        raise ValueError(
            "sprint=True requires gamma=0: the cross-block gamma margin is "
            "a per-block host decision the fused segment cannot replay")
    return bool(sprint)


# --------------------------------------------------------------------------
# certificate container
# --------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class RadiusCertificate:
    """Measured evidence that a core-set meets its radius/accuracy target.

    ``radius`` is the exact anticover radius r_T of the selection (masked
    field max after the final fold — not a model, a measurement).  ``scale``
    is the anticover radius after the first k picks, a measured lower bound
    on the optimal diversity scale (OPT >= rho_k >= scale, paper Fact 1), and
    ``ratio = 2·radius/scale`` is the certified additive-relative core-set
    error bound for the remote measures.  ``counts``/``radii`` is the
    per-sweep radius trajectory (non-increasing by construction) and
    ``b_schedule`` the (block, rounds) phases the engine actually executed.
    ``kind`` is "batch" for the selection engines and "streaming" for the
    SMM states, where ``counts`` is points seen at each merge and ``radius``
    the 4·d_i proxy bound.
    """
    kprime: int
    radius: float
    scale: float
    ratio: float
    eps_target: Optional[float] = None
    meets_target: Optional[bool] = None
    counts: Tuple[int, ...] = ()
    radii: Tuple[float, ...] = ()
    b_schedule: Tuple[Tuple[int, int], ...] = ()
    kind: str = "batch"
    group_ratios: Optional[Tuple[float, ...]] = None
    # Graceful-degradation accounting (ResiliencePolicy(on_failure="degrade")
    # dropping failed reducers): the composable core-set property makes the
    # surviving merge a valid core-set OF THE SURVIVING SHARDS ONLY, so the
    # certificate must say which shards it covers.  ``points_covered`` /
    # ``points_total`` count shard rows (padded partitions).
    degraded: bool = False
    surviving_shards: Optional[Tuple[int, ...]] = None
    total_shards: Optional[int] = None
    points_covered: Optional[int] = None
    points_total: Optional[int] = None
    # Dynamic-index churn accounting (kind="dynamic", ``repro.dynamic``):
    # how many updates the leveled cover has absorbed incrementally since
    # its last from-scratch rebuild, and how many of them were deletions —
    # the drift the rebuild scheduler bounds.  None outside dynamic mode.
    updates_since_rebuild: Optional[int] = None
    deletions_absorbed: Optional[int] = None

    def to_dict(self) -> dict:
        return dataclasses.asdict(self)


def auto_milestones(k: int, n: int, kprime_max=None):
    """The geometric auto-k' growth plan shared by every auto path
    (single-machine, MR probe, grouped): start at max(2k, 32), double up to
    the cap (default max(256, 16k), clamped to n).  Returns
    (kmax, milestones); kmax itself is the implicit final milestone."""
    kmax = min(n, kprime_max if kprime_max else max(256, 16 * k))
    kmax = max(kmax, min(k, n))
    first = min(kmax, max(2 * k, 32))
    miles, c = [], first
    while c < kmax:
        miles.append(c)
        c *= 2
    return kmax, miles


def _secant_next(hist, eps: Optional[float], cur: int, cap: int) -> int:
    """Next auto-k' milestone: a secant step on the measured (k', ratio)
    curve in log-log space once two milestone measurements exist (on bounded
    doubling metrics the anticover radius decays like k'^(-1/dim), so the
    curve is near-linear there), clamped to the geometric x2 step as both
    the first move and the overshoot cap.

    >>> _secant_next([(32, 0.8), (64, 0.4)], 0.3, 64, 1024)
    86
    >>> _secant_next([(32, 0.8), (64, 0.4)], 0.1, 64, 1024)   # capped at x2
    128
    >>> _secant_next([(32, 0.4)], 0.1, 32, 1024)              # x2 first step
    64
    >>> _secant_next([(32, 0.4), (64, 0.4)], 0.1, 64, 1024)   # flat -> x2
    128
    """
    fallback = min(2 * cur, cap)
    if eps is None or eps <= 0 or len(hist) < 2:
        return fallback
    (k1, r1), (k2, r2) = hist[-2], hist[-1]
    if not (k2 > k1 > 0 and 0.0 < r2 < r1 and np.isfinite(r1)):
        return fallback
    slope = (np.log(r2) - np.log(r1)) / (np.log(k2) - np.log(k1))
    if not np.isfinite(slope) or slope >= 0:
        return fallback
    est = k2 * (eps / r2) ** (1.0 / slope)
    if not np.isfinite(est):
        return fallback
    return int(np.clip(np.ceil(est), cur + 1, fallback))


def _ratio(radius: float, scale: float) -> float:
    if radius <= 0.0:
        return 0.0
    if scale <= 0.0 or not np.isfinite(scale):
        return float("inf")
    return 2.0 * radius / scale


def certificate_from_trajectory(counts: Sequence[int],
                                radii: Sequence[float], k: int,
                                *, eps: Optional[float] = None,
                                b_schedule=(), kind: str = "batch",
                                group_ratios=None) -> RadiusCertificate:
    """Build the certificate from a (counts, radii) trajectory: the scale is
    the first radius sample with >= k centers folded (conservative — later
    samples are only smaller)."""
    counts = tuple(int(c) for c in counts)
    radii = tuple(float(r) for r in radii)
    radius = radii[-1] if radii else float("inf")
    scale = next((r for c, r in zip(counts, radii) if c >= k), radius)
    ratio = _ratio(radius, scale)
    return RadiusCertificate(
        kprime=counts[-1] if counts else 0, radius=radius, scale=scale,
        ratio=ratio, eps_target=eps,
        meets_target=None if eps is None else bool(ratio <= eps),
        counts=counts, radii=radii,
        b_schedule=tuple(tuple(x) for x in b_schedule), kind=kind,
        group_ratios=group_ratios)


# --------------------------------------------------------------------------
# jitted steps (shared by the m=1 and grouped adaptive loops)
# --------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("m", "p", "chunk", "metric_name",
                                             "use_pallas"),
                   donate_argnums=(2,))
def _fold_impl(points, labels, min_dist, pending, m: int, p: int, chunk: int,
               metric_name: str, use_pallas: bool):
    """Fold the pending center block (an (m, bp) int32 index block) into the
    field and surface each group's top-p candidate pool.  ``cd[:, 0]`` is
    the exact anticover radius of the selection folded so far — the
    controller's one host transfer."""
    sweep = _make_grouped_sweep(points, labels, m, p, chunk, metric_name,
                                use_pallas)
    return sweep(min_dist, points[pending])


@functools.partial(jax.jit, static_argnames=("m", "take", "p", "chunk",
                                             "metric_name", "use_pallas"),
                   donate_argnums=(2,))
def _block_step_impl(points, labels, min_dist, pending, m: int, take: int,
                     p: int, chunk: int, metric_name: str, use_pallas: bool):
    """One supervised engine block in a single dispatch: fold the pending
    centers, pull the oversampled pool, run the exact in-block GMM for
    ``take`` tentative picks.  ``pending`` is an (m, bp) int32 index block
    (gathered on device, saving a host-side dispatch).  Returns (min_dist,
    chosen (m, take), stats (m, take+1)) where ``stats[:, 0]`` is the exact
    anticover radius of everything folded so far and ``stats[:, 1:]`` the
    tentative picks' corrected anticover distances — packed so the host
    controller blocks on a single transfer per supervised block."""
    sweep = _make_grouped_sweep(points, labels, m, p, chunk, metric_name,
                                use_pallas)
    md, cd, ci = sweep(min_dist, points[pending])
    chosen, seld = _grouped_inblock(points, metric_name, cd, ci, take)
    return md, chosen, jnp.concatenate([cd[:, :1], seld], axis=1)


@functools.partial(jax.jit, static_argnames=("m", "b", "p", "rcap", "chunk",
                                             "metric_name", "use_pallas"),
                   donate_argnums=(2,))
def _sprint_impl(points, labels, min_dist, pending, counts, pos0, rmax,
                 tau, cliff, m: int, b: int, p: int, rcap: int, chunk: int,
                 metric_name: str, use_pallas: bool):
    """Device-resident sprint segment: up to ``rmax`` full lookahead blocks
    in ONE fused ``lax.while_loop`` dispatch (the tentpole of sprint mode).

    Each round folds the previously committed block into the donated field,
    samples the exact anticover radius, runs the pooled in-block GMM for
    ``b`` tentative picks, and evaluates the host controller's tau/cliff
    greedy-consistency bars ON DEVICE — the same float32 arithmetic the
    host applies to ``stats_np``, so the commit decision is bit-identical.
    A fully certified block commits into the block buffer and becomes the
    next round's fold; a block failing a bar past pick 0 is rolled back
    (nothing committed) and its stats/picks spill to the host, which
    truncates it exactly as a host-paced block.  The host blocks ONCE per
    segment, on the packed state below, instead of once per block.

    Returns ``(rounds, truncated, min_dist, pending, blocks (rcap, m, b),
    traj (rcap, m), spill_stats (m, b+1), spill_chosen (m, b))`` where
    ``rounds`` counts committed full blocks and ``traj[r]`` the radius
    observed when round ``r``'s fold landed (``traj[rounds]`` belongs to
    the spilled block when ``truncated``).
    """
    sweep = _make_grouped_sweep(points, labels, m, p, chunk, metric_name,
                                use_pallas)

    def cond(state):
        r, truncated = state[0], state[1]
        return (r < rmax) & jnp.logical_not(truncated)

    def body(state):
        r, _, md, pend, blocks, traj, spill_stats, spill_chosen = state
        md, cd, ci = sweep(md, points[pend])
        rnow = cd[:, 0]
        traj = traj.at[r].set(rnow)
        chosen, seld = _grouped_inblock(points, metric_name, cd, ci, b)
        # the host controller's truncation test, verbatim: every pick past
        # the first must clear tau*radius AND cliff*previous-pick in every
        # group that still has fresh points, else the block truncates.
        active = counts > (pos0 + r * b)
        thr = tau * jnp.maximum(rnow, 0.0)
        above_tau = seld >= thr[:, None]
        no_cliff = jnp.concatenate(
            [jnp.ones((m, 1), bool), seld[:, 1:] >= cliff * seld[:, :-1]],
            axis=1)
        ok = (~active[:, None]) | (above_tau & no_cliff)
        bad = jnp.logical_not(jnp.all(ok, axis=0)).at[0].set(False)
        full = jnp.logical_not(jnp.any(bad))
        blocks = jnp.where(full, blocks.at[r].set(chosen), blocks)
        pend = jnp.where(full, chosen, pend)
        stats = jnp.concatenate([cd[:, :1], seld], axis=1)
        spill_stats = jnp.where(full, spill_stats, stats)
        spill_chosen = jnp.where(full, spill_chosen, chosen)
        return (r + full.astype(jnp.int32), jnp.logical_not(full), md, pend,
                blocks, traj, spill_stats, spill_chosen)

    init = (jnp.asarray(0, jnp.int32), jnp.asarray(False), min_dist, pending,
            jnp.zeros((rcap, m, b), jnp.int32),
            jnp.zeros((rcap, m), jnp.float32),
            jnp.zeros((m, b + 1), jnp.float32),
            jnp.zeros((m, b), jnp.int32))
    return jax.lax.while_loop(cond, body, init)


@functools.partial(jax.jit, static_argnames=("m", "kcap", "chunk",
                                             "metric_name", "use_pallas"))
def _resume_impl(points, labels, min_dist, idx, start, end, m: int, kcap: int,
                 chunk: int, metric_name: str, use_pallas: bool):
    """Bit-exact b=1 continuation of plain GMM from a live engine state, in
    ONE dispatch: picks columns [start, end) (dynamic bounds).  Entry
    invariant: columns < start are selected and all but the last are folded
    (re-folding a folded column is a no-op, so a freshly-certified state
    resumes cleanly).  Returns (min_dist, idx, tcol) with tcol[r] = the
    per-group anticover radius measured when column r was picked."""
    sweep = _make_grouped_sweep(points, labels, m, 1, chunk, metric_name,
                                use_pallas)
    tcol = jnp.full((kcap, m), jnp.inf, jnp.float32)

    def body(r, state):
        md, idx, tcol = state
        prev = jax.lax.dynamic_slice(idx, (0, r - 1), (m, 1))
        md, cd, ci = sweep(md, gather_rows(points, prev))
        idx = jax.lax.dynamic_update_slice(idx, ci, (0, r))
        tcol = jax.lax.dynamic_update_slice(tcol, cd[:, :1].T, (r, 0))
        return md, idx, tcol

    return jax.lax.fori_loop(start, end, body, (min_dist, idx, tcol))


# --------------------------------------------------------------------------
# the host-paced adaptive loop (generic over m groups; m=1 == unconstrained)
# --------------------------------------------------------------------------

@dataclasses.dataclass
class AdaptiveRun:
    """Raw outcome of ``adaptive_select`` (device arrays + host telemetry)."""
    idx: np.ndarray            # (m, ksel) int32 selections
    ksel: int                  # centers selected per group
    radius: np.ndarray         # (m,) measured anticover radius
    min_dist: jnp.ndarray      # (n,) final field (device)
    counts: Tuple[int, ...]    # trajectory x-axis (centers folded)
    traj: np.ndarray           # (S, m) per-group radius at each sample
    schedule: Tuple[Tuple[int, int], ...]  # executed (block, rounds) phases
    shrink_at: Tuple[int, ...]  # positions where the controller shrank b


def _compress_schedule(takes: Sequence[int]) -> Tuple[Tuple[int, int], ...]:
    phases = []
    for t in takes:
        if phases and phases[-1][0] == t:
            phases[-1][1] += 1
        else:
            phases.append([t, 1])
    return tuple((b, r) for b, r in phases)


def adaptive_select(points, labels, starts, m: int, k_cap: int, *,
                    b0: int = 8, gamma: float = 0.0,
                    tau: Optional[float] = None,
                    cliff: Optional[float] = None,
                    chunk: int = 0, metric: str = "euclidean",
                    use_pallas: bool = False,
                    milestones: Sequence[int] = (), eps: Optional[float] = None,
                    scale_count: Optional[int] = None,
                    group_counts=None, sprint="auto") -> AdaptiveRun:
    """Adaptive engine: one fused fold+pool+pick dispatch per supervised
    block, a few-scalar certificate check on the host — and, with ``sprint``
    enabled, whole multi-block segments device-paced between those checks.

    Three adaptations keep every committed pick greedy-consistent without
    giving up the lookahead's sweep savings:

    * **within-block truncation** (``tau``, ``cliff``): a tentative pick is
      discarded — along with the rest of its block — when its corrected
      anticover distance falls below ``tau`` times the sweep's measured
      radius OR below ``cliff`` times the previous pick's distance.  The
      ``tau`` bar is the anticover scale of greedy consistency (exact GMM's
      picks always clear every later radius); the ``cliff`` bar is its
      scale-free complement: on clustered data the in-block distances drop
      off a cliff (to the within-cluster scale, a ≤0.2× step measured) the
      moment the pool's distinct clusters are exhausted, and that step
      stays diagnostic even late in a run when the radius itself has
      shrunk toward the cluster scale and a fixed ``tau·radius`` bar goes
      blind.  Healthy dense-field lookahead decays smoothly (≥0.6× steps,
      bottoming out around 0.2–0.4·radius — harmless: ≤ a few % of final
      radius at full commitment), so ``tau=0.15``/``cliff=0.35`` split the
      regimes with ~2× margin on either side and the engine degrades
      toward one certified pick per sweep exactly where lookahead stops
      paying.
    * **pool widening**: heavy truncation usually means the pool itself is
      too narrow (on strongly clustered data the top-p field values can all
      sit in one or two clusters), so the oversampling factor doubles (16b
      up to 32b) whenever less than half a block commits, and relaxes back
      when full blocks flow again — the sweep cost is unchanged (the pool
      is a fused per-tile top-k), only the tiny in-block GMM grows.
    * **cross-block margin** (``gamma``, off by default): if a committed
      block's weakest corrected distance drops below ``gamma`` times the
      next measured radius, the lookahead went sub-greedy despite the pool
      and the block size is halved.  Committed picks already clear
      ``tau·floor ≈ tau·radius``, so any ``gamma`` near ``tau`` fights the
      truncation (measured: it spirals block sizes down on healthy dense
      data); it exists as an extra-strict knob, not a default.

    Two consecutive single-pick blocks switch to ``_resume_impl`` — a
    bit-exact b=1 continuation of plain GMM in one dispatch.

    **Sprint mode** (``sprint="auto"|True|False``, see ``resolve_sprint``):
    after a supervised block certifies fully, the controller state is
    stable (pool relaxed to 16b, streak reset), so the following blocks up
    to the next milestone / k_cap run as ONE fused ``lax.while_loop``
    dispatch (``_sprint_impl``) that evaluates the tau/cliff bars on
    device, commits certified blocks into donated buffers, rolls back a
    truncating block (spilling its stats for the host to truncate exactly
    as a host-paced block) and returns to the host only at the segment
    boundary.  Picks, trajectory, executed schedule — and therefore the
    ``RadiusCertificate`` — are bit-identical to the host-paced loop, but
    ``host_syncs`` drops from O(k'/b) to O(#segments).

    With ``milestones`` (sorted center counts) and ``eps``, the loop stops
    at the first milestone whose measured certificate ratio
    (2·radius/scale, scale sampled at ``scale_count``) meets ``eps`` in
    every inhabited group — this is the ``auto_kprime`` growth loop, and it
    never repeats work because the engine state is just a paused GMM run.
    An unmet milestone re-plans the next one with a secant step on the
    measured ratio curve (``_secant_next``; x2 first step, fallback and
    overshoot cap), so only the initial ``milestones`` need to be the
    geometric plan.
    """
    tau, cliff = resolve_bars(tau, cliff)
    points = jnp.asarray(points)
    labels = jnp.asarray(labels, jnp.int32)
    n = points.shape[0]
    metric_name = get_metric(metric).name
    pts_p, lab_p, ch = pad_for_engine(points, labels, chunk)
    counts_np = (np.asarray(group_counts, np.int64)
                 if group_counts is not None else np.full((m,), n, np.int64))
    k_cap = max(1, min(k_cap, n))
    starts_np = np.asarray(starts, np.int32)
    sprint_on = resolve_sprint(sprint, gamma)
    counts_dev = jnp.asarray(np.minimum(counts_np, 2 ** 31 - 1)
                             .astype(np.int32))

    idx_host = np.zeros((m, k_cap), np.int32)
    idx_host[:, 0] = starts_np
    md = jnp.full((pts_p.shape[0],), jnp.inf, jnp.float32)
    b_cur = max(1, min(b0, k_cap))
    pending = jnp.asarray(starts_np)[:, None]      # (m, bp) index block
    pending_folded = False
    pos = 1
    traj_counts, traj_vals, takes, shrink_at = [], [], [], []
    prev_margin = prev_active = None
    ones_streak = 0
    miles = sorted(c for c in set(int(x) for x in milestones) if c < k_cap)
    mile_hist: list = []     # (k', worst certified ratio) per unmet milestone
    scale = None
    stopped = False
    last_rnow = None

    def milestone_eval(rnow):
        """(met, worst ratio) across inhabited, unfinished groups."""
        if eps is None or scale is None:
            return False, float("inf")
        alive = counts_np > 0
        done = counts_np <= pos
        ratios = np.array([_ratio(float(r), float(s))
                           for r, s in zip(rnow, scale)])
        live = alive & ~done
        if not live.any():
            return True, 0.0
        worst = float(ratios[live].max())
        return bool(worst <= eps), worst

    def observe(rnow):
        nonlocal scale, stopped, miles
        traj_counts.append(pos)
        traj_vals.append(rnow)
        if scale is None and scale_count is not None and pos >= scale_count:
            scale = rnow.copy()
        crossed = False
        while miles and pos >= miles[0]:
            miles.pop(0)
            crossed = True
        if not crossed:
            return
        met, worst = milestone_eval(rnow)
        if met:
            stopped = True
        elif eps is not None:
            # unmet milestone: re-plan the next one with a secant step on
            # the measured ratio curve (x2 is the first step and the
            # overshoot cap; see _secant_next) instead of walking the
            # pre-seeded geometric plan.
            if np.isfinite(worst) and worst > 0.0:
                mile_hist.append((pos, worst))
            nxt = _secant_next(mile_hist, eps, pos, k_cap)
            miles = [nxt] if nxt < k_cap else []

    d = int(points.shape[1])

    def _step_obs(folded: int, sweeps: int = 1, syncs: int = 1) -> None:
        """One controller round-trip: ``sweeps`` dispatched sweeps folding
        ``folded`` centers total, read back with ``syncs`` blocking
        transfers (host_syncs is THE pacing metric of this engine)."""
        _count("device_dispatches")
        _count("host_syncs", syncs)
        _count("distance_evals", n * folded)
        _count("bytes_swept", _sweep_bytes(n, d, sweeps=sweeps, m=m))

    def commit_block(chosen, stats_np, take):
        """Host bookkeeping for one evaluated block — shared verbatim by the
        supervised path and the sprint spill replay, so a device-rolled-back
        block truncates bit-identically to a host-paced one.

        Certified within-block truncation: keep the prefix of picks that
        clear BOTH bars in every group that still has fresh points — tau x
        the current radius (the greedy-consistency scale) and cliff x the
        previous pick (the scale-free cluster cliff detector).  The pool
        floor is NOT a usable reference: on tightly clustered data a wide
        pool's tail digs into within-cluster mass and the floor collapses
        with it."""
        nonlocal b_cur, ones_streak, p_mult, pending, pending_folded, pos, \
            prev_active, prev_margin
        rnow = stats_np[:, 0]
        active = counts_np > pos
        if prev_margin is not None and np.any(
                prev_active & (prev_margin
                               < gamma * np.maximum(rnow, 0.0))):
            b_cur = max(1, b_cur // 2)
            shrink_at.append(pos)
        seld_np = stats_np[:, 1:]
        thr = tau * np.maximum(rnow, 0.0)
        above_tau = seld_np >= thr[:, None]
        no_cliff = np.ones_like(above_tau)
        if take > 1:
            no_cliff[:, 1:] = seld_np[:, 1:] >= cliff * seld_np[:, :-1]
        ok = ~active[:, None] | (above_tau & no_cliff)
        take_eff = take
        for j in range(1, take):
            if not ok[:, j].all():
                take_eff = j
                break
        committed = chosen[:, :take_eff]
        idx_host[:, pos:pos + take_eff] = np.asarray(committed)
        pending = committed
        prev_margin = np.min(
            np.where(active[:, None], seld_np[:, :take_eff], np.inf),
            axis=1)
        prev_active = active
        takes.append(take_eff)
        pending_folded = False
        pos += take_eff
        # pool adaptation: heavy truncation -> widen; full blocks -> relax
        if take_eff <= take // 2:
            if p_mult < 32:
                _count("pool_widenings")
            p_mult = min(32, p_mult * 2)
        elif take_eff == take:
            p_mult = max(16, p_mult // 2)
        if take_eff == 1:
            ones_streak += 1
            if ones_streak >= 2 and b_cur > 1:
                b_cur = 1
                shrink_at.append(pos)
        else:
            ones_streak = 0
        return take_eff

    def sprint_segment():
        """Device-paced segment: run the next full b_cur-blocks as ONE fused
        while_loop dispatch, stopping before the next milestone observe /
        k_cap (so every host decision stays host-made) or on the first
        device-detected truncation.  The committed blocks are replayed into
        the host bookkeeping from the single packed readback; a truncated
        block spills through ``commit_block`` exactly like a supervised one.
        Returns False when the remaining segment is too short to pay for a
        dispatch (< 2 full blocks)."""
        nonlocal md, pending, pending_folded, last_rnow, pos, \
            prev_active, prev_margin, ones_streak
        bseg = b_cur
        rmax = (k_cap - pos) // bseg
        if miles:
            if pos >= miles[0]:
                return False
            # observes land at pos, pos+b, ...: stay strictly below the
            # milestone so its eval (stop / secant re-plan) runs host-paced
            rmax = min(rmax, (miles[0] - 1 - pos) // bseg + 1)
        if rmax < 2:
            return False
        p = min(p_mult * bseg, pts_p.shape[0])
        rcap = max(1, k_cap // bseg)
        with _span("adaptive.sprint", pos=pos, b=bseg, rmax=int(rmax)):
            (r_dev, trunc_dev, md2, _pend, blocks_dev, traj_dev,
             spill_stats_dev, spill_chosen_dev) = _sprint_impl(
                pts_p, lab_p, md, pending, counts_dev,
                jnp.asarray(pos, jnp.int32), jnp.asarray(rmax, jnp.int32),
                jnp.asarray(tau, jnp.float32), jnp.asarray(cliff, jnp.float32),
                m, bseg, p, rcap, ch, metric_name, use_pallas)
            rounds = int(r_dev)           # the one blocking transfer
            truncated = bool(trunc_dev)
            traj_seg = np.asarray(traj_dev)
            blocks_seg = np.asarray(blocks_dev)
        md = md2
        if _counting():
            folds = rounds + (1 if truncated else 0)
            _count("sprint_segments")
            _step_obs(folded=folds * bseg, sweeps=folds)
        for r in range(rounds):
            # replay the committed rounds: observe cannot stop or re-plan
            # here (the segment ends before the next milestone observe)
            rnow = traj_seg[r]
            pending_folded, last_rnow = True, rnow
            observe(rnow)
            idx_host[:, pos:pos + bseg] = blocks_seg[r]
            takes.append(bseg)
            pos += bseg
        if rounds:
            # full commits: the host loop would relax the (already-relaxed)
            # pool, zero the ones streak and never consult the margin at
            # gamma=0 (committed picks clear tau*radius >= 0)
            pending = blocks_dev[rounds - 1]
            pending_folded = False
            prev_margin = prev_active = None
            ones_streak = 0
        if truncated:
            stats_np = np.asarray(spill_stats_dev)
            rnow = stats_np[:, 0]
            pending_folded, last_rnow = True, rnow
            observe(rnow)
            if not stopped:
                commit_block(spill_chosen_dev, stats_np, bseg)
        return True

    p_mult = 16
    while pos < k_cap and not stopped:
        if b_cur > 1:
            take = min(b_cur, k_cap - pos)
            p = min(p_mult * b_cur, pts_p.shape[0])
            with _span("adaptive.block", pos=pos, b=b_cur, p=p):
                md, chosen, stats = _block_step_impl(
                    pts_p, lab_p, md, pending, m, take, p, ch, metric_name,
                    use_pallas)
                stats_np = np.asarray(stats)    # the one blocking transfer
            if _counting():
                _step_obs(folded=int(pending.shape[1]))
            rnow = stats_np[:, 0]
            pending_folded, last_rnow = True, rnow
            observe(rnow)
            if stopped:
                break
            take_eff = commit_block(chosen, stats_np, take)
            # a fully-certified opening block hands the segment to the
            # device: the pool just relaxed to 16b and the streak reset, so
            # the controller state is dispatch-stable until the boundary
            if (sprint_on and b_cur > 1 and take_eff == take == b_cur
                    and p_mult == 16 and pos < k_cap):
                sprint_segment()
        else:
            # bit-exact b=1 tail, one dispatch per milestone segment
            if not pending_folded:
                with _span("adaptive.fold", pos=pos):
                    md, cd, _ = _fold_impl(pts_p, lab_p, md, pending, m, 1,
                                           ch, metric_name, use_pallas)
                    rnow = np.asarray(cd[:, 0])
                if _counting():
                    _step_obs(folded=int(pending.shape[1]))
                pending_folded, last_rnow = True, rnow
                observe(rnow)
                if stopped:
                    break
            end = k_cap
            for c in miles:
                if c > pos:
                    end = min(end, c)
                    break
            with _span("adaptive.resume", start=pos, end=end):
                idx_dev = jnp.asarray(idx_host)
                md, idx_dev, tcol = _resume_impl(
                    pts_p, lab_p, md, idx_dev, jnp.asarray(max(pos, 1)),
                    jnp.asarray(end), m, k_cap, ch, metric_name, use_pallas)
                idx_host = np.asarray(idx_dev)
                tc = np.asarray(tcol)
            if _counting():
                seg = max(end - pos, 1)
                _step_obs(folded=seg, sweeps=seg)
            for r in range(pos, end):
                traj_counts.append(r)
                traj_vals.append(tc[r])
                if scale is None and scale_count is not None \
                        and r >= scale_count:
                    scale = tc[r].copy()
            takes.extend([1] * (end - pos))
            prev_margin = prev_active = None
            pending = idx_dev[:, end - 1:end]
            pending_folded = False
            pos = end
            if miles and pos >= miles[0]:
                with _span("adaptive.fold", pos=pos):
                    md, cd, _ = _fold_impl(pts_p, lab_p, md, pending, m, 1,
                                           ch, metric_name, use_pallas)
                    rnow = np.asarray(cd[:, 0])
                if _counting():
                    _step_obs(folded=int(pending.shape[1]))
                pending_folded, last_rnow = True, rnow
                observe(rnow)

    # final fold: the measured anticover radius of everything selected
    if not pending_folded:
        with _span("adaptive.fold", pos=pos):
            md, cd, _ = _fold_impl(pts_p, lab_p, md, pending, m, 1, ch,
                                   metric_name, use_pallas)
            rfin = np.asarray(cd[:, 0])
        if _counting():
            _step_obs(folded=int(pending.shape[1]))
        traj_counts.append(pos)
        traj_vals.append(rfin)
    else:
        rfin = last_rnow

    return AdaptiveRun(idx=idx_host[:, :pos], ksel=pos,
                       radius=rfin, min_dist=md[:n],
                       counts=tuple(traj_counts),
                       traj=np.stack(traj_vals, axis=0),
                       schedule=_compress_schedule(takes),
                       shrink_at=tuple(shrink_at))


# --------------------------------------------------------------------------
# unconstrained front-ends
# --------------------------------------------------------------------------

class AdaptiveGMMResult(NamedTuple):
    idx: jnp.ndarray          # (ksel,) selected indices
    radius: jnp.ndarray       # () measured anticover radius
    min_dist: jnp.ndarray     # (n,)
    counts: tuple             # trajectory x-axis
    traj: np.ndarray          # (S,) radius trajectory
    schedule: tuple           # executed (block, rounds) phases
    cert: RadiusCertificate


def gmm_adaptive(points, k: int, *, b0: int = 8, metric="euclidean",
                 mask=None, start=0, chunk: int = 0,
                 use_pallas: bool = False, gamma: float = 0.0,
                 tau: Optional[float] = None, cliff: Optional[float] = None,
                 scale_count: Optional[int] = None,
                 eps: Optional[float] = None,
                 sprint="auto") -> AdaptiveGMMResult:
    """Adaptive-b GMM: lookahead-b speed where the radius curve is steep, a
    bit-exact b=1 fallback once it flattens (``b="auto"`` everywhere in the
    public API routes here).  Unlike ``gmm_batched``, any k works — the
    schedule is discovered, not prescribed.  ``tau``/``cliff`` override the
    controller's greedy-consistency bars (None = ``DEFAULT_TAU`` /
    ``DEFAULT_CLIFF``); ``sprint`` selects the device-paced segment runner
    (bit-identical results, fewer host syncs — see ``adaptive_select``)."""
    points = jnp.asarray(points)
    n = points.shape[0]
    if mask is None:
        mask = jnp.ones((n,), bool)
    labels = mask_to_labels(jnp.asarray(mask))
    run = adaptive_select(points, labels, [start], 1, k, b0=b0, gamma=gamma,
                          tau=tau, cliff=cliff, chunk=chunk, metric=metric,
                          use_pallas=use_pallas,
                          scale_count=scale_count or min(k, n), eps=eps,
                          sprint=sprint)
    cert = certificate_from_trajectory(
        run.counts, run.traj[:, 0], scale_count or min(k, n), eps=eps,
        b_schedule=run.schedule)
    return AdaptiveGMMResult(idx=jnp.asarray(run.idx[0]),
                             radius=jnp.asarray(float(run.radius[0])),
                             min_dist=run.min_dist, counts=run.counts,
                             traj=run.traj[:, 0], schedule=run.schedule,
                             cert=cert)


def auto_kprime(points, k: int, eps: float = 0.1,
                measure: str = "remote-edge", *, metric="euclidean",
                b="auto", chunk: int = 0, use_pallas: bool = False,
                kprime_max: Optional[int] = None, mask=None,
                start=0, tau: Optional[float] = None,
                cliff: Optional[float] = None,
                sprint="auto") -> AdaptiveGMMResult:
    """ε-targeted core-set sizing: grow k' until the measured radius
    certificate meets the target (ratio = 2·r_T(k')/scale_k <= eps),
    resuming the same engine run at every milestone.  The first growth step
    is geometric (x2); once two milestone measurements exist the next
    milestone comes from a secant step on the measured ratio curve
    (``_secant_next``), which overshoots less at large k' while keeping x2
    as the fallback and the per-step cap.

    ``measure`` is recorded for context; the certificate is the remote-edge
    bound, which the delegate/multiplicity constructions for the clique-type
    measures are built on top of (their kernel is this selection).  Returns
    an ``AdaptiveGMMResult`` whose ``idx`` has the chosen k' and whose
    ``cert`` carries the full trajectory.

    >>> import numpy as np
    >>> rng = np.random.default_rng(0)
    >>> pts = rng.normal(size=(3000, 2)).astype(np.float32)
    >>> res = auto_kprime(pts, k=5, eps=0.5)
    >>> res.cert.meets_target            # measured 2*r/scale <= eps
    True
    >>> int(res.idx.shape[0]) == res.cert.kprime
    True
    >>> list(res.cert.radii) == sorted(res.cert.radii, reverse=True)
    True
    """
    del measure  # certificate is measure-agnostic (remote-edge bound)
    points = jnp.asarray(points)
    n = points.shape[0]
    if mask is None:
        mask = jnp.ones((n,), bool)
    labels = mask_to_labels(jnp.asarray(mask))
    if k < 1 or k > n:
        raise ValueError(f"k={k} out of range for n={n}")
    kmax, miles = auto_milestones(k, n, kprime_max)
    b0 = 8 if b == "auto" else max(1, int(b))
    run = adaptive_select(points, labels, [start], 1, kmax, b0=b0, tau=tau,
                          cliff=cliff, chunk=chunk, metric=metric,
                          use_pallas=use_pallas,
                          milestones=miles, eps=eps, scale_count=k,
                          sprint=sprint)
    cert = certificate_from_trajectory(run.counts, run.traj[:, 0], k,
                                       eps=eps, b_schedule=run.schedule)
    return AdaptiveGMMResult(idx=jnp.asarray(run.idx[0]),
                             radius=jnp.asarray(float(run.radius[0])),
                             min_dist=run.min_dist, counts=run.counts,
                             traj=run.traj[:, 0], schedule=run.schedule,
                             cert=cert)


# --------------------------------------------------------------------------
# probe -> static plan (for shard_map reducers, where no host loop can run)
# --------------------------------------------------------------------------

def plan_from_schedule(executed, kprime: int,
                       probe_k: int) -> Tuple[Tuple[int, int], ...]:
    """Convert an executed adaptive schedule into a static two-phase plan
    covering ``kprime`` picks: keep the probe's leading full-size blocks for
    the same *fraction* of the run, finish at b=1.  Exact-GMM tails and
    whole-run lookahead both fall out naturally."""
    if not executed:
        return ((1, kprime),)
    b0 = executed[0][0]
    head_picks = 1  # the seed
    for bsz, rounds in executed:
        if bsz != b0:
            break
        head_picks += bsz * rounds
    if b0 <= 1:
        return ((1, kprime),)
    frac = min(1.0, head_picks / max(probe_k, 1))
    head_rounds = int(frac * kprime) // b0
    head_rounds = max(0, min(head_rounds, kprime // b0))
    tail = kprime - head_rounds * b0
    if head_rounds == 0:
        return ((1, kprime),)
    if tail == 0:
        return ((b0, head_rounds),)
    return ((b0, head_rounds), (1, tail))


def resolve_engine_plan(points, k: int, kprime, b, *, eps: float = 0.1,
                        metric="euclidean", labels=None, m: int = 1,
                        chunk: int = 0, use_pallas: bool = False,
                        sample: int = 8192, tau: Optional[float] = None,
                        cliff: Optional[float] = None, sprint="auto"):
    """Resolve ``b="auto"`` / ``kprime="auto"`` into static engine inputs for
    paths that run inside ``shard_map``/``vmap`` (the MapReduce reducers): a
    cheap strided-subsample probe runs the adaptive controller once on the
    host, and its outcome is frozen into (kprime:int, schedule|None, cert).

    Numeric knobs pass through untouched (schedule=None means "use ``b`` as
    given").
    """
    if b != "auto" and kprime != "auto":
        return kprime, None, None
    pts = np.asarray(points)
    n = pts.shape[0]
    stride = max(1, n // max(1, min(sample, n)))
    sub = pts[::stride]
    lab = (np.zeros((sub.shape[0],), np.int32) if labels is None
           else np.asarray(labels)[::stride].astype(np.int32))
    mm = 1 if labels is None else m
    sn = sub.shape[0]
    counts = np.bincount(lab[lab >= 0], minlength=mm)[:mm]
    starts = np.zeros((mm,), np.int32)
    for g in range(mm):
        hits = np.nonzero(lab == g)[0]
        starts[g] = hits[0] if hits.size else 0
    k_probe = min(k, sn)
    if kprime == "auto":
        kmax, miles = auto_milestones(k_probe, sn)
        run = adaptive_select(sub, lab, starts, mm, kmax,
                              b0=8 if b == "auto" else max(1, int(b)),
                              tau=tau, cliff=cliff,
                              chunk=chunk, metric=metric,
                              use_pallas=use_pallas, milestones=miles,
                              eps=eps, scale_count=k_probe,
                              group_counts=counts if labels is not None
                              else None, sprint=sprint)
        kp = run.ksel
    else:
        kp = int(kprime)
        run = adaptive_select(sub, lab, starts, mm, min(kp, sn), b0=8,
                              tau=tau, cliff=cliff,
                              chunk=chunk, metric=metric,
                              use_pallas=use_pallas, scale_count=k_probe,
                              group_counts=counts if labels is not None
                              else None, sprint=sprint)
    cert = certificate_from_trajectory(
        run.counts, run.traj.max(axis=1), k_probe,
        eps=eps if kprime == "auto" else None, b_schedule=run.schedule)
    schedule = (plan_from_schedule(run.schedule, kp, run.ksel)
                if b == "auto" else None)
    if schedule is not None:
        validate_schedule(schedule, kp)
    return kp, schedule, cert
