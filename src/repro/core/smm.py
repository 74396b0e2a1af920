"""SMM / SMM-EXT / SMM-GEN — the paper's streaming core-set constructions (§4, §6.1).

The doubling algorithm of Charikar et al. adapted per the paper:

* state is a set ``T`` of at most ``k'+1`` centers and a threshold ``d_i``;
* each phase starts with a *merge* step — a maximal independent set of the
  graph with edges ``d(t1,t2) <= 2 d_i`` — and continues with an *update* step
  that discards points with ``d(p,T) <= 4 d_i`` and inserts farther points
  until ``T`` reaches ``k'+1`` points, whereupon ``d_{i+1} = 2 d_i``;
* the ``M`` buffer (points removed by the most recent merge) tops ``T`` up to
  ``>= k`` points at stream end (the paper's fix after Lemma 3);
* SMM-EXT keeps up to ``k`` delegates per center (slot 0 = the center itself);
  on merge, a removed center's delegates are inherited by a kept center within
  ``2 d_i`` — the paper prints ``max{|E_t1|, k-|E_t2|}`` which we read as the
  obvious ``min`` (you cannot inherit more points than exist nor exceed the
  capacity ``k``); on update, a discarded point joins its nearest center's
  delegate set if there is room;
* SMM-GEN (Thm 9, 2-pass scheme) keeps only *counts* — a generalized core-set.

TPU/throughput adaptation (DESIGN.md §2): the stream is consumed in chunks; a
single ``(chunk, |T|)`` distance matmul classifies every point, the common-case
"all discarded" path is fully vectorized (including the capacity-respecting
delegate scatter), and from the first point beyond ``4 d_i`` on, an in-jit
loop (``_seq_insert``) visits only the points beyond ``4 d_i`` of the centres
it started with — the only ones whose answer can change — and inserts those
still far.  This is an exact execution of the per-point algorithm (discard
decisions are order-independent within a chunk because ``T`` only grows
between merges, and a near point's delegate goes to its nearest centre among
those valid at its own position).

The chunk loop is sync-free in the common case: classification, the on-device
first-far-position search and the near-prefix absorb are fused into one
dispatch (``_classify_absorb``) and the host reads back a single int32 — the
full ``far`` mask is never materialized on the host, so a no-far chunk costs
exactly one scalar transfer.
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.obs.trace import count as _count, span as _span

from .coreset import Coreset, GeneralizedCoreset
from .metrics import get_metric


class SMMState(NamedTuple):
    T: jnp.ndarray          # (cap, d) centers
    t_valid: jnp.ndarray    # (cap,)
    e_pts: jnp.ndarray      # (cap, k_slots, d) delegates (slot 0 = center); (cap,1,d) when unused
    e_cnt: jnp.ndarray      # (cap,) delegates/multiplicity count (incl. center)
    M: jnp.ndarray          # (cap, d) last-merge-removed buffer
    m_valid: jnp.ndarray    # (cap,)
    d_thr: jnp.ndarray      # () current d_i
    n_phases: jnp.ndarray   # () int32


def _pairwise(metric_name, a, b):
    return get_metric(metric_name).pairwise(a, b)


# Rows per distance block in ``_seq_insert``.  A TPU program's code is held
# in device memory, and a distance matmul's code grows with its rows: one
# over the whole chunk added 1.3 MB to each compiled tail length, 16 rows add
# ~0.1 MB, for ~256 short loop steps a 4096-row chunk.
_BLOCK = 16


def _by_blocks(fn, chunk, dtype):
    """``fn(rows, at)`` -> one value per row of ``chunk[at:at + len(rows)]``,
    over blocks of at most ``_BLOCK`` rows (the last one overlapping its
    predecessor), gathered into a ``(len(chunk),)`` array."""
    c = chunk.shape[0]
    b = min(_BLOCK, c)

    def body(i, out):
        at = jnp.minimum(i * b, c - b)
        rows = jax.lax.dynamic_slice_in_dim(chunk, at, b)
        return jax.lax.dynamic_update_slice_in_dim(out, fn(rows, at), at, 0)

    return jax.lax.fori_loop(0, -(-c // b), body, jnp.zeros((c,), dtype))


def _readback(*xs):
    """Read device scalars ``xs`` to the host as Python numbers: one
    blocking transfer, spanned as ``smm.readback`` and counted in
    ``host_syncs`` (one value, or a list of them)."""
    with _span("smm.readback"):
        out = [np.asarray(x).item() for x in jax.device_get(xs)]
    _count("host_syncs")
    return out[0] if len(out) == 1 else out


@functools.partial(jax.jit, static_argnames=("metric_name",))
def _init_threshold(T, metric_name):
    dm = _pairwise(metric_name, T, T)
    cap = T.shape[0]
    off = jnp.where(jnp.eye(cap, dtype=bool), jnp.inf, dm)
    # smallest strictly-positive pairwise distance (duplicates excluded);
    # falls back to a tiny epsilon if all points coincide.
    pos = jnp.where(off > 0, off, jnp.inf)
    d1 = jnp.min(pos)
    return jnp.where(jnp.isfinite(d1), d1, jnp.asarray(1e-30, dm.dtype))


@functools.partial(jax.jit, static_argnames=("metric_name", "mode", "k"))
def _merge(state: SMMState, metric_name: str, mode: str, k: int) -> SMMState:
    """One merge step: MIS at threshold 2 d_i, M capture, delegate inheritance."""
    cap = state.T.shape[0]
    dm = _pairwise(metric_name, state.T, state.T)
    thr = 2.0 * state.d_thr

    def mis_body(j, carry):
        keep, covered = carry
        can = state.t_valid[j] & ~covered[j]
        keep = keep.at[j].set(can)
        covered = covered | (can & (dm[j] <= thr))
        return keep, covered

    keep0 = jnp.zeros((cap,), bool)
    covered0 = jnp.zeros((cap,), bool)
    keep, _ = jax.lax.fori_loop(0, cap, mis_body, (keep0, covered0))
    removed = state.t_valid & ~keep

    M = jnp.where(removed[:, None], state.T, 0.0)
    m_valid = removed

    e_pts, e_cnt = state.e_pts, state.e_cnt
    if mode in ("ext", "gen"):
        k_slots = e_pts.shape[1]

        def inherit_body(j, carry):
            e_pts, e_cnt = carry
            is_rem = removed[j]
            dr = jnp.where(keep, dm[j], jnp.inf)
            t2 = jnp.argmin(dr)
            take = jnp.minimum(e_cnt[j], k - e_cnt[t2])
            take = jnp.where(is_rem, jnp.maximum(take, 0), 0)
            if mode == "ext":
                slot = jnp.arange(k_slots)
                src_pos = jnp.clip(slot - e_cnt[t2], 0, k_slots - 1)
                newrow = jnp.where(
                    ((slot >= e_cnt[t2]) & (slot - e_cnt[t2] < take))[:, None],
                    e_pts[j][src_pos],
                    e_pts[t2],
                )
                e_pts = e_pts.at[t2].set(newrow)
            e_cnt = e_cnt.at[t2].add(take)
            e_cnt = e_cnt.at[j].set(jnp.where(is_rem, 0, e_cnt[j]))
            return e_pts, e_cnt

        e_pts, e_cnt = jax.lax.fori_loop(0, cap, inherit_body, (e_pts, e_cnt))
    else:
        e_cnt = jnp.where(keep, e_cnt, 0)

    return state._replace(t_valid=keep, e_pts=e_pts, e_cnt=e_cnt, M=M,
                          m_valid=m_valid, n_phases=state.n_phases + 1)


@functools.partial(jax.jit, static_argnames=("metric_name",))
def _classify(state: SMMState, chunk, cvalid, metric_name):
    """Vector phase: nearest center + far mask for a whole chunk."""
    dm = _pairwise(metric_name, chunk, state.T)          # (c, cap)
    dm = jnp.where(state.t_valid[None, :], dm, jnp.inf)
    near_d = jnp.min(dm, axis=1)
    nearest = jnp.argmin(dm, axis=1)
    far = (near_d > 4.0 * state.d_thr) & cvalid
    return near_d, nearest, far


@functools.partial(jax.jit, static_argnames=("metric_name", "mode", "k"))
def _classify_absorb(state: SMMState, chunk, metric_name: str, mode: str,
                     k: int):
    """Fused vector phase: classify the chunk, locate the first far point ON
    DEVICE, and commit the near-prefix updates in the same dispatch.

    Returns (state', first_far) where first_far == len(chunk) means the whole
    chunk was absorbed (the sync-free fast path: the caller transfers exactly
    one int32 scalar and touches nothing else)."""
    c = chunk.shape[0]
    cvalid = jnp.ones((c,), bool)
    _, nearest, far = _classify(state, chunk, cvalid, metric_name)
    first_far = jnp.where(jnp.any(far), jnp.argmax(far), c).astype(jnp.int32)
    state = _absorb_near_prefix(state, chunk, cvalid, nearest, far, first_far,
                                metric_name, mode, k)
    return state, first_far


@functools.partial(jax.jit, static_argnames=("metric_name", "mode", "k"))
def _absorb_near_prefix(state: SMMState, chunk, cvalid, nearest, far, upto,
                        metric_name: str, mode: str, k: int) -> SMMState:
    """Commit delegate/count updates for the near points at positions < upto.

    Capacity-respecting and order-preserving: the r-th near point routed to a
    given center lands in slot e_cnt + r, provided that is < k.
    """
    c = chunk.shape[0]
    cap = state.T.shape[0]
    pos = jnp.arange(c)
    near_mask = cvalid & ~far & (pos < upto)
    if mode == "plain":
        return state  # discards only
    nst = jnp.where(near_mask, nearest, cap)             # sentinel group = cap
    key = nst * (c + 1) + pos
    order = jnp.argsort(key)
    snst = nst[order]
    starts = jnp.searchsorted(snst, jnp.arange(cap + 1))
    rank_sorted = jnp.arange(c) - starts[jnp.clip(snst, 0, cap)]
    rank = jnp.zeros((c,), jnp.int32).at[order].set(rank_sorted.astype(jnp.int32))
    slot = state.e_cnt[jnp.clip(nst, 0, cap - 1)] + rank
    accept = near_mask & (slot < k)
    adds = jax.ops.segment_sum(accept.astype(jnp.int32),
                               jnp.where(accept, nst, cap), num_segments=cap + 1)[:cap]
    e_cnt = jnp.minimum(state.e_cnt + adds, k)
    e_pts = state.e_pts
    if mode == "ext":
        row = jnp.where(accept, nst, cap)                # OOB -> dropped
        col = jnp.where(accept, slot, state.e_pts.shape[1])
        e_pts = e_pts.at[row, col].set(chunk, mode="drop")
    return state._replace(e_pts=e_pts, e_cnt=e_cnt)


@functools.partial(jax.jit, static_argnames=("metric_name", "mode", "k"))
def _seq_insert(state: SMMState, chunk, cvalid, start, metric_name: str,
                mode: str, k: int):
    """Sequential per-point processing from ``start``; stops when T fills.

    Only the rows whose answer can change are visited.  Centres are only
    added between merges, so a row within ``4 d_i`` of the call's starting
    centres stays near for the whole call: one ``(chunk, cap)`` distance
    pass (``_by_blocks``) finds the candidates (rows beyond ``4 d_i`` of
    every starting centre), and the loop visits them in stream order,
    inserting each one still far from every centre (the new ones included)
    in the first free slot.  In ext/gen the near rows of ``[start, next_pos)`` are then
    absorbed at once, each to its nearest centre among those valid at its
    own position (lowest slot on ties), with the capacity-respecting,
    position-ordered adds of ``_absorb_near_prefix``.

    Returns (state, next_pos, became_full, steps), ``steps`` the rows the
    loop visited.
    """
    cap = state.T.shape[0]
    c = chunk.shape[0]
    metric = get_metric(metric_name)
    pos = jnp.arange(c)
    thr = 4.0 * state.d_thr
    valid0 = state.t_valid
    live = cvalid & (pos >= start)
    T0 = state.T

    def far(rows, at):
        dm = jnp.where(valid0[None, :], _pairwise(metric_name, rows, T0),
                       jnp.inf)
        return jnp.min(dm, axis=1) > thr

    cand = live & _by_blocks(far, chunk, bool)

    def cond(carry):
        state, cand, r, i, full, filled_at, inserted = carry
        return jnp.any(cand) & ~full

    def body(carry):
        state, cand, r, i, full, filled_at, inserted = carry
        r = jnp.argmax(cand).astype(jnp.int32)           # first unvisited
        cand = cand.at[r].set(False)
        p = chunk[r]
        d = metric.point_to_set(state.T, p)
        d = jnp.where(state.t_valid, d, jnp.inf)
        is_far = jnp.min(d) > thr

        # --- far: insert as a new center in the first invalid slot
        free = jnp.argmin(state.t_valid)                 # first False
        T = state.T.at[free].set(jnp.where(is_far, p, state.T[free]))
        t_valid = state.t_valid.at[free].set(jnp.where(is_far, True,
                                                       state.t_valid[free]))
        e_pts = state.e_pts
        e_cnt = state.e_cnt
        if mode in ("ext", "gen"):
            if mode == "ext":
                e_pts = e_pts.at[free, 0].set(jnp.where(is_far, p, e_pts[free, 0]))
            e_cnt = e_cnt.at[free].set(jnp.where(is_far, 1, e_cnt[free]))
        filled_at = filled_at.at[free].set(jnp.where(is_far, r,
                                                     filled_at[free]))
        inserted = inserted.at[r].set(is_far)
        new_state = state._replace(T=T, t_valid=t_valid, e_pts=e_pts, e_cnt=e_cnt)
        full = jnp.sum(t_valid) >= cap
        return new_state, cand, r, i + 1, full, filled_at, inserted

    state, _, r, steps, full, filled_at, inserted = jax.lax.while_loop(
        cond, body, (state, cand, jnp.asarray(0, jnp.int32),
                     jnp.asarray(0, jnp.int32), jnp.asarray(False),
                     jnp.full((cap,), c, jnp.int32), jnp.zeros((c,), bool)))
    # T filled at the last row visited: the caller merges, then goes on
    next_pos = jnp.where(full, r + 1, c).astype(jnp.int32)
    if mode in ("ext", "gen"):
        T = state.T

        def nearest(rows, at):
            # a slot is seen from the rows after the one that filled it
            at = at + jnp.arange(rows.shape[0])
            seen = valid0[None, :] | (filled_at[None, :] < at[:, None])
            dm = jnp.where(seen, _pairwise(metric_name, rows, T), jnp.inf)
            return jnp.argmin(dm, axis=1).astype(jnp.int32)

        state = _absorb_near_prefix(state, chunk, live,
                                    _by_blocks(nearest, chunk, jnp.int32),
                                    inserted, next_pos, metric_name, mode, k)
    return state, next_pos, full, steps


class StreamingCoreset:
    """Host-side driver around the jitted SMM steps — the paper's one-pass
    streaming core-set (§4/§6.1) with `O(k'·k)` state.

    ``mode="plain"`` keeps centers only (remote-edge/cycle, Thm 4);
    ``mode="ext"`` keeps up to k delegates per center (the clique-type
    measures, Thm 5); ``mode="gen"`` keeps multiplicities (generalized
    core-sets, Thm 9).  Feed chunks of any size — state is chunk-invariant.

    >>> import numpy as np
    >>> from repro.core import StreamingCoreset, solve_on_coreset
    >>> rng = np.random.default_rng(0)
    >>> smm = StreamingCoreset(k=4, kprime=16, dim=3)
    >>> for _ in range(5):                  # any chunking works
    ...     smm.update(rng.normal(size=(200, 3)).astype(np.float32))
    >>> smm.n_seen
    1000
    >>> cs = smm.finalize()                 # composable Coreset
    >>> sol = solve_on_coreset(cs, k=4, measure="remote-edge")
    >>> sol.shape
    (4, 3)
    """

    def __init__(self, k: int, kprime: int, dim: int, *, metric="euclidean",
                 mode: str = "plain", dtype=jnp.float32,
                 eps: Optional[float] = None):
        if mode not in ("plain", "ext", "gen"):
            raise ValueError(mode)
        if kprime < k:
            raise ValueError("k' must be >= k")
        m = get_metric(metric)
        if not m.is_metric:
            raise ValueError(f"SMM needs a true metric, got {metric!r}")
        self.k, self.kprime, self.dim = k, kprime, dim
        self.metric, self.mode, self.dtype = m.name, mode, dtype
        self.eps = eps           # accuracy target recorded in the certificate
        self.cap = kprime + 1
        self._prefix = []        # buffers the first cap points
        self._state: Optional[SMMState] = None
        self.n_seen = 0
        # Host-side cache-invalidation token for the serving layer
        # (``repro.serving.rerank``): bumped whenever an update could change
        # the finalized core-set or its certificate — boot, far-point insert,
        # merge, any pre-boot buffering, and (ext/gen) any absorbed point.
        # A fully-absorbed chunk in ``plain`` mode leaves it unchanged, which
        # is exactly the certificate-reuse fast path.  NOT part of the
        # certified state: different chunkings of the same stream may count
        # different generations even though the SMM state is chunk-invariant.
        self.generation = 0
        # per-merge re-certification log: (n_seen, d_i) at every merge — the
        # streaming analogue of the batch engine's radius trajectory (the
        # proxy-distance bound is 4·d_i, and d_i only moves at merges)
        self._phase_log = []

    # -- init ---------------------------------------------------------------
    def _boot(self, pts0):
        self._n_processed = self.cap
        cap, k, dim = self.cap, self.k, self.dim
        k_slots = k if self.mode == "ext" else 1
        T = self._upload(pts0)
        e_pts = jnp.zeros((cap, k_slots, dim), self.dtype)
        if self.mode == "ext":
            e_pts = e_pts.at[:, 0].set(T)
        state = SMMState(
            T=T,
            t_valid=jnp.ones((cap,), bool),
            e_pts=e_pts,
            e_cnt=jnp.ones((cap,), jnp.int32),
            M=jnp.zeros((cap, dim), self.dtype),
            m_valid=jnp.zeros((cap,), bool),
            d_thr=_init_threshold(T, self.metric),
            n_phases=jnp.asarray(0, jnp.int32),
        )
        # T is full after initialization -> Phase 1 begins with a merge
        _count("device_dispatches")          # _init_threshold
        _count("points_absorbed", cap)       # the boot prefix
        self.generation += 1
        self._state = self._merge_until_room(state)

    def _merge_until_room(self, state: SMMState) -> SMMState:
        with _span("smm.merge", n_processed=self._n_processed):
            state = _merge(state, self.metric, self.mode, self.k)
            _count("device_dispatches")
            # if the MIS removed nothing (all pairwise > 2 d_i) the update
            # step is empty: double the threshold and merge again (see
            # module docstring).
            while _readback(jnp.sum(state.t_valid)) >= self.cap:
                state = state._replace(d_thr=state.d_thr * 2.0)
                state = _merge(state, self.metric, self.mode, self.k)
                _count("device_dispatches")
            _count("merges")
            # stamp with the exact number of stream points processed when the
            # merge fired (NOT n_seen, which already counts the whole
            # in-flight chunk) — this keeps the re-certification log
            # chunk-invariant.
            self._phase_log.append((self._n_processed,
                                    _readback(state.d_thr)))
        return state

    def _upload(self, rows):
        """Put host ``rows`` on the device: spanned as ``smm.upload`` and
        counted in ``h2d_bytes``."""
        with _span("smm.upload"):
            out = jnp.asarray(rows, self.dtype)
        _count("h2d_bytes", out.nbytes)
        return out

    # -- streaming ----------------------------------------------------------
    def update(self, chunk) -> None:
        chunk = np.asarray(chunk, dtype=np.dtype(self.dtype.dtype.name)
                           if hasattr(self.dtype, "dtype") else np.float32)
        chunk = np.atleast_2d(chunk)
        if chunk.shape[0] == 0:
            return
        with _span("smm.update", n=chunk.shape[0]):
            self.n_seen += chunk.shape[0]
            gen0 = self.generation
            if self._state is None:
                need = self.cap - sum(len(p) for p in self._prefix)
                self._prefix.append(chunk[:need])
                chunk = chunk[need:]
                if sum(len(p) for p in self._prefix) >= self.cap:
                    self._boot(np.concatenate(self._prefix, axis=0))
                    self._prefix = []
                else:
                    # still buffering: finalize() would return the grown
                    # prefix
                    self.generation += 1
                if chunk.shape[0] == 0:
                    return
            self._consume(self._upload(chunk), self.n_seen - chunk.shape[0])
            if self.mode != "plain" and self.generation == gen0:
                # ext/gen: even fully-absorbed points mutate delegate sets /
                # multiplicities, so the finalized core-set may change
                self.generation += 1

    def _consume(self, chunk, base: int = 0) -> None:
        """Sync-free chunk loop: ``_classify_absorb`` classifies the tail,
        finds the first far position and commits the near-prefix updates in
        one device dispatch; the host reads back a single int32 scalar.  On
        the common no-far-point path that scalar is the only transfer for the
        whole chunk — the ``far`` mask itself never leaves the device.

        ``base`` is the number of stream points processed before this chunk
        (re-certification log stamps only)."""
        c = chunk.shape[0]
        pos = 0
        state = self._state
        while pos < c:
            with _span("smm.classify"):
                tail = chunk[pos:]
                state, first_far = _classify_absorb(state, tail, self.metric,
                                                    self.mode, self.k)
            _count("device_dispatches")
            first_far = _readback(first_far)    # the one host transfer
            if first_far == tail.shape[0]:      # whole tail absorbed
                pos = c
                break
            self.generation += 1                # far insert mutates T
            with _span("smm.insert"):
                cvalid = jnp.ones((tail.shape[0],), bool)
                state, consumed, full, steps = _seq_insert(
                    state, tail, cvalid, first_far, self.metric, self.mode,
                    self.k)
            _count("device_dispatches")
            consumed, full, steps = _readback(consumed, full, steps)
            _count("insert_steps", steps)
            pos += consumed
            if full:
                state = state._replace(d_thr=state.d_thr * 2.0)
                self._n_processed = base + pos
                state = self._merge_until_room(state)
        self._state = state
        _count("points_absorbed", c)

    # -- certification ------------------------------------------------------
    def certificate(self):
        """Streaming ``RadiusCertificate``: the proxy-distance bound 4·d_i
        against the anticover scale measured on the live centers.

        ``radius`` is the certified upper bound on any point's distance to
        its proxy (the stream's points are gone, so unlike the batch engine
        this is the paper's bound, not a re-measurement).  ``scale`` runs
        exact GMM over the <= k'+1 live centers — stream points all within
        ``radius`` of T, so T's anticover scale at k lower-bounds the
        stream's diversity scale up to the same proxy error.  The
        trajectory is the per-merge phase log (n_seen, 4·d_i): chunking the
        stream differently cannot change it, because the SMM state itself is
        chunk-invariant."""
        from .adaptive import RadiusCertificate, _ratio
        from .gmm import gmm as _gmm

        counts = tuple(n for n, _ in self._phase_log)
        radii = tuple(4.0 * d for _, d in self._phase_log)
        if self._state is None:
            return RadiusCertificate(
                kprime=self.kprime, radius=0.0, scale=0.0, ratio=0.0,
                eps_target=self.eps,
                meets_target=None if self.eps is None else True,
                counts=counts, radii=radii, kind="streaming")
        state = self._state
        radius = 4.0 * float(state.d_thr)
        n_valid = int(jnp.sum(state.t_valid))
        if n_valid >= self.k:
            res = _gmm(state.T, self.k, metric=self.metric,
                       mask=state.t_valid,
                       start=int(jnp.argmax(state.t_valid)))
            scale = float(res.radius)
        else:
            scale = 0.0
        ratio = _ratio(radius, scale)
        return RadiusCertificate(
            kprime=self.kprime, radius=radius, scale=scale, ratio=ratio,
            eps_target=self.eps,
            meets_target=None if self.eps is None else bool(ratio <= self.eps),
            counts=counts, radii=radii, kind="streaming")

    # -- output -------------------------------------------------------------
    def finalize(self, *, allow_small: bool = False):
        """``allow_small=True`` returns whatever the stream held when it had
        fewer than ``k`` points (used by the constrained driver, where a tiny
        group legitimately contributes all of its members).  The returned
        core-set carries the streaming ``RadiusCertificate`` as ``.cert``."""
        if self._state is None:
            # tiny stream: everything fits in the prefix buffer
            pts = np.concatenate(self._prefix, axis=0) if self._prefix else \
                np.zeros((0, self.dim), np.float32)
            if pts.shape[0] < self.k and not allow_small:
                raise ValueError(f"stream had {pts.shape[0]} < k={self.k} points")
            w = np.ones((pts.shape[0],), np.int32)
            return Coreset(points=jnp.asarray(pts), valid=jnp.ones(len(pts), bool),
                           weights=jnp.asarray(w), radius=jnp.asarray(0.0),
                           cert=self.certificate())
        cert = self.certificate()
        state = self._state
        n_valid = int(jnp.sum(state.t_valid))
        # top-up from M so that |T| >= k (paper's fix: M ∪ I has >= k'+1 >= k pts)
        if n_valid < self.k:
            state = _topup_from_M(state, self.k)
        radius = 4.0 * state.d_thr
        if self.mode == "plain":
            return Coreset(points=state.T, valid=state.t_valid,
                           weights=jnp.where(state.t_valid, 1, 0).astype(jnp.int32),
                           radius=radius, cert=cert)
        if self.mode == "gen":
            mult = jnp.where(state.t_valid, jnp.maximum(state.e_cnt, 1), 0)
            return GeneralizedCoreset(points=state.T, multiplicity=mult,
                                      radius=radius, cert=cert)
        # ext: union of delegate sets
        cap, k_slots, dim = state.e_pts.shape
        pts = state.e_pts.reshape(cap * k_slots, dim)
        slot = jnp.tile(jnp.arange(k_slots), (cap,))
        row = jnp.repeat(jnp.arange(cap), k_slots)
        valid = state.t_valid[row] & (slot < state.e_cnt[row])
        return Coreset(points=pts, valid=valid,
                       weights=valid.astype(jnp.int32), radius=radius,
                       cert=cert)

    @property
    def state(self) -> Optional[SMMState]:
        return self._state

    @property
    def phase_log(self):
        """Per-merge (n_seen, d_i) re-certification log (read-only copy)."""
        return tuple(self._phase_log)

    # -- checkpoint / resume -------------------------------------------------
    # The SMM state is chunk-invariant: everything a resumed run needs is the
    # SMMState arrays plus a handful of host-side scalars (n_seen, the phase
    # log, the pre-boot prefix buffer).  Serializing exactly that through
    # CheckpointManager therefore gives BIT-IDENTICAL resume — a stream
    # killed mid-way and restored finalizes to the same core-set and
    # certificate as an uninterrupted run (asserted in tests/test_resilience).

    def _zero_state(self) -> SMMState:
        """An all-zeros SMMState with this stream's shapes/dtypes — the
        restore template (CheckpointManager takes shapes from the archive,
        dtypes + tree structure from the template)."""
        cap, dim = self.cap, self.dim
        k_slots = self.k if self.mode == "ext" else 1
        return SMMState(
            T=jnp.zeros((cap, dim), self.dtype),
            t_valid=jnp.zeros((cap,), bool),
            e_pts=jnp.zeros((cap, k_slots, dim), self.dtype),
            e_cnt=jnp.zeros((cap,), jnp.int32),
            M=jnp.zeros((cap, dim), self.dtype),
            m_valid=jnp.zeros((cap,), bool),
            d_thr=jnp.asarray(0.0, self.dtype),
            n_phases=jnp.asarray(0, jnp.int32))

    def state_dict(self):
        """``(arrays, meta)`` snapshot of the entire streaming progress.
        ``arrays`` is a flat dict of jax arrays (the SMMState fields plus the
        pre-boot prefix buffer); ``meta`` holds the host-side scalars and the
        phase log (JSON-serializable, stored in the checkpoint's meta.json)."""
        prefix = (np.concatenate(self._prefix, axis=0) if self._prefix
                  else np.zeros((0, self.dim), np.float32))
        booted = self._state is not None
        st = self._state if booted else self._zero_state()
        arrays = {"prefix": jnp.asarray(prefix, self.dtype),
                  "T": st.T, "t_valid": st.t_valid, "e_pts": st.e_pts,
                  "e_cnt": st.e_cnt, "M": st.M, "m_valid": st.m_valid,
                  "d_thr": st.d_thr, "n_phases": st.n_phases}
        meta = {"k": self.k, "kprime": self.kprime, "dim": self.dim,
                "metric": self.metric, "mode": self.mode, "eps": self.eps,
                "dtype": np.dtype(self.dtype).name,
                "n_seen": int(self.n_seen),
                "n_prefix": int(prefix.shape[0]),
                "n_processed": int(getattr(self, "_n_processed", 0)),
                "generation": int(self.generation),
                "booted": booted,
                "phase_log": [[int(n), float(d)] for n, d in self._phase_log]}
        return arrays, meta

    def save(self, manager, step: int) -> None:
        """Blocking checkpoint at ``step`` (for a stream: chunks consumed so
        far) through a ``repro.checkpoint.CheckpointManager``."""
        arrays, meta = self.state_dict()
        manager.save(step, arrays, extra=meta, blocking=True)
        _count("checkpoints_written")

    @classmethod
    def from_state_dict(cls, arrays, meta) -> "StreamingCoreset":
        smm = cls(int(meta["k"]), int(meta["kprime"]), int(meta["dim"]),
                  metric=meta["metric"], mode=meta["mode"],
                  dtype=getattr(jnp, meta["dtype"]), eps=meta["eps"])
        smm.n_seen = int(meta["n_seen"])
        smm.generation = int(meta.get("generation", 0))
        smm._phase_log = [(int(n), float(d)) for n, d in meta["phase_log"]]
        n_prefix = int(meta["n_prefix"])
        if n_prefix:
            smm._prefix = [np.asarray(arrays["prefix"])[:n_prefix]]
        if meta["booted"]:
            smm._n_processed = int(meta["n_processed"])
            smm._state = SMMState(
                T=jnp.asarray(arrays["T"], smm.dtype),
                t_valid=jnp.asarray(arrays["t_valid"], bool),
                e_pts=jnp.asarray(arrays["e_pts"], smm.dtype),
                e_cnt=jnp.asarray(arrays["e_cnt"], jnp.int32),
                M=jnp.asarray(arrays["M"], smm.dtype),
                m_valid=jnp.asarray(arrays["m_valid"], bool),
                d_thr=jnp.asarray(arrays["d_thr"], smm.dtype),
                n_phases=jnp.asarray(arrays["n_phases"], jnp.int32))
        return smm

    @classmethod
    def restore(cls, manager, step: Optional[int] = None):
        """Rebuild a ``StreamingCoreset`` from checkpoint ``step`` (default:
        the latest).  Returns ``(smm, step)``, or ``(None, None)`` when the
        directory holds no checkpoint yet."""
        if step is None:
            step = manager.latest_step()
            if step is None:
                return None, None
        meta = manager.read_meta(step)["extra"]
        tmp = cls(int(meta["k"]), int(meta["kprime"]), int(meta["dim"]),
                  metric=meta["metric"], mode=meta["mode"],
                  dtype=getattr(jnp, meta["dtype"]), eps=meta["eps"])
        st = tmp._zero_state()
        template = {"prefix": jnp.zeros((0, tmp.dim), tmp.dtype),
                    "T": st.T, "t_valid": st.t_valid, "e_pts": st.e_pts,
                    "e_cnt": st.e_cnt, "M": st.M, "m_valid": st.m_valid,
                    "d_thr": st.d_thr, "n_phases": st.n_phases}
        arrays = manager.restore(step, template)
        return cls.from_state_dict(arrays, meta), step


@functools.partial(jax.jit, static_argnames=("k",))
def _topup_from_M(state: SMMState, k: int) -> SMMState:
    cap = state.T.shape[0]

    def body(j, st):
        need = k - jnp.sum(st.t_valid)
        use = st.m_valid[j] & (need > 0)
        free = jnp.argmin(st.t_valid)
        T = st.T.at[free].set(jnp.where(use, st.M[j], st.T[free]))
        t_valid = st.t_valid.at[free].set(jnp.where(use, True, st.t_valid[free]))
        e_cnt = st.e_cnt.at[free].set(jnp.where(use, 1, st.e_cnt[free]))
        e_pts = st.e_pts.at[free, 0].set(jnp.where(use, st.M[j], st.e_pts[free, 0]))
        return st._replace(T=T, t_valid=t_valid, e_cnt=e_cnt, e_pts=e_pts)

    return jax.lax.fori_loop(0, cap, body, state)
