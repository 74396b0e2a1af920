"""Diversity-driven data selection — the paper's technique as a first-class
data-pipeline feature (DESIGN.md §2 point 2).

Given a pool of examples, embed them (mean-pooled token embeddings through
the model's own embedding table, or a seeded random projection when no model
is at hand), then run the MR core-set construction to pick the k most diverse
examples.  This is the standard "diverse subset for curation / dedup" loop
the paper motivates, applicable to all 10 assigned architectures.
"""
from __future__ import annotations

from typing import Optional

import jax.numpy as jnp
import numpy as np

def embed_examples(token_batches: np.ndarray, embedding: Optional[jnp.ndarray]
                   = None, dim: int = 64, seed: int = 0) -> np.ndarray:
    """token_batches (N, S) int32 -> (N, dim) float32 embeddings."""
    toks = np.asarray(token_batches)
    if embedding is not None:
        emb = np.asarray(embedding, np.float32)
        pooled = emb[toks].mean(axis=1)                    # (N, D)
        if pooled.shape[1] > dim:
            rng = np.random.default_rng(seed)
            proj = rng.normal(size=(pooled.shape[1], dim)).astype(np.float32)
            proj /= np.sqrt(pooled.shape[1])
            pooled = pooled @ proj
        return pooled
    # seeded random-projection sketch of token histograms
    rng = np.random.default_rng(seed)
    vmax = int(toks.max()) + 1
    proj = rng.normal(size=(vmax, dim)).astype(np.float32) / np.sqrt(vmax)
    out = np.zeros((toks.shape[0], dim), np.float32)
    for i, row in enumerate(toks):
        out[i] = proj[row].sum(axis=0)
    return out


def balanced_quotas(group_labels: np.ndarray, k: int, m: Optional[int] = None
                    ) -> np.ndarray:
    """Default quotas for ``select_diverse(..., group_labels=...)``: as close
    to k/m per group as the group sizes allow, remainder going to the largest
    groups first."""
    labels = np.asarray(group_labels)
    if m is None:
        m = int(labels.max()) + 1 if labels.size else 0
    counts = np.bincount(labels, minlength=m)[:m]
    if counts.sum() < k:
        raise ValueError(f"k={k} exceeds the {counts.sum()} labelled points")
    quotas = np.minimum(counts, k // max(m, 1))
    # distribute the remainder one pick at a time, round-robin over groups
    # with spare capacity, largest group first — keeps the split balanced
    order = np.argsort(-counts)
    while quotas.sum() < k:
        for g in order:
            if quotas.sum() >= k:
                break
            if quotas[g] < counts[g]:
                quotas[g] += 1
    return quotas.astype(np.int64)


def select_diverse(embeddings: np.ndarray, k: int, *, measure="remote-edge",
                   kprime=None, num_reducers: int = 1,
                   metric="euclidean", group_labels=None, quotas=None,
                   matroid=None, b=1, chunk: int = 0,
                   eps: float = 0.1, tau=None, cliff=None) -> np.ndarray:
    """Returns indices of the k selected examples.

    Legacy spelling of ``repro.diversify`` (whose ``DiversityResult`` also
    carries the row ``indices``) — prefer the facade for new code.

    With ``group_labels`` (an ``(n,)`` int array of category ids) the
    selection is matroid-constrained via the ``repro.constrained``
    subsystem: ``quotas=`` is sugar for an exact-quota partition matroid
    (``quotas[g]`` picks from every group g, defaulting to a balanced split
    of k across groups), while ``matroid=`` accepts any
    ``repro.constrained.matroid`` oracle — quota ranges, transversal slot
    eligibility, laminar nested caps.

    ``b``/``chunk`` tune the single-sweep selection engine shared by every
    path (lookahead-b center blocking + chunk-fused sweeps; see
    ``core.gmm.gmm_batched`` / ``constrained.coreset``): ``b=1`` is exact
    GMM, ``b`` in 4–16 cuts point-set sweeps ~b× for large pools at a few-%
    selection-fidelity cost, and ``b="auto"`` / ``kprime="auto"`` run the
    radius-certified adaptive engine (``core.adaptive``; ``eps`` sets the
    auto-k' accuracy target).

    >>> import numpy as np
    >>> rng = np.random.default_rng(0)
    >>> emb = rng.normal(size=(200, 8)).astype(np.float32)
    >>> idx = select_diverse(emb, 8)                     # unconstrained
    >>> len(idx) == len(set(idx.tolist())) == 8
    True
    >>> lab = rng.integers(0, 4, size=200)
    >>> idx = select_diverse(emb, 6, group_labels=lab, quotas=[3, 1, 1, 1])
    >>> np.bincount(lab[idx], minlength=4).tolist()
    [3, 1, 1, 1]
    """
    from repro.api import (ExecutionSpec, ProblemSpec, _warn_legacy,
                           diversify)

    _warn_legacy("repro.data.select_diverse")
    pts = np.asarray(embeddings, np.float32)
    res = diversify(
        ProblemSpec(points=pts, k=k, measure=measure, metric=metric,
                    labels=group_labels, matroid=matroid, quotas=quotas),
        ExecutionSpec(mode="mapreduce" if num_reducers > 1 else "batch",
                      num_reducers=num_reducers if num_reducers > 1 else None,
                      kprime=kprime, b=b, chunk=chunk, eps=eps, tau=tau,
                      cliff=cliff))
    return res.indices


def _match_rows(pts: np.ndarray, sol: np.ndarray, k: int, *,
                row_labels=None, sol_labels=None) -> np.ndarray:
    """Map solution points back to distinct row indices.

    A solution point equal to an input row (of its own group, with
    ``row_labels``/``sol_labels`` — preserves quota feasibility) maps to the
    lowest-index such row not taken yet.  Every row equal to a solution
    point shares its first coordinate, so one binary search per row over the
    solution's first coordinates finds all candidates in a single pass.  A
    solution point with no untaken exact match falls back to the nearest
    untaken row (a masked argmin, O(n))."""
    pts, sol = np.asarray(pts), np.asarray(sol)
    labels_np = None if row_labels is None else np.asarray(row_labels)
    taken = np.zeros(pts.shape[0], bool)
    first = np.sort(sol[:, 0])
    col = pts[:, 0]
    hit = first[np.minimum(np.searchsorted(first, col), len(first) - 1)]
    cand = np.flatnonzero(hit == col)
    cand_rows = pts[cand]
    idx = []
    for t, s in enumerate(sol):
        ok = np.all(cand_rows == s, axis=1) & ~taken[cand]
        if labels_np is not None:
            ok &= labels_np[cand] == sol_labels[t]
        if ok.any():
            j = int(cand[np.argmax(ok)])
        else:
            d = np.linalg.norm(pts - s[None, :], axis=1)
            if labels_np is not None:
                d = np.where(labels_np == sol_labels[t], d, np.inf)
            d[taken] = np.inf
            j = int(np.argmin(d))
            if not np.isfinite(d[j]):
                continue
        idx.append(j)
        taken[j] = True
    return np.asarray(idx[:k])
