"""SMM streaming tests: invariants, reference equivalence, EXT/GEN modes."""
import numpy as np
import pytest

import jax.numpy as jnp
from repro.core import StreamingCoreset
from repro.core.metrics import get_metric


def reference_smm(stream, k, kprime):
    """Pure-python per-point doubling algorithm (paper §4 verbatim)."""
    cap = kprime + 1
    T = [p for p in stream[:cap]]
    rest = stream[cap:]
    # d1 = min positive pairwise
    d1 = np.inf
    for i in range(cap):
        for j in range(i + 1, cap):
            d = np.linalg.norm(T[i] - T[j])
            if d > 0:
                d1 = min(d1, d)
    d = d1 if np.isfinite(d1) else 1e-30
    M = []

    def merge(T, d):
        keep = []
        removed = []
        for t in T:
            if all(np.linalg.norm(t - u) > 2 * d for u in keep):
                keep.append(t)
            else:
                removed.append(t)
        return keep, removed

    T, M = merge(T, d)
    while len(T) >= cap:
        d *= 2
        T, M = merge(T, d)
    for p in rest:
        dist = min(np.linalg.norm(p - t) for t in T)
        if dist > 4 * d:
            T.append(p)
            if len(T) >= cap:
                d *= 2
                T, M = merge(T, d)
                while len(T) >= cap:
                    d *= 2
                    T, M = merge(T, d)
    return np.asarray(T), d, np.asarray(M) if M else np.zeros((0, 3))


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_smm_matches_reference(seed):
    rng = np.random.default_rng(seed)
    stream = rng.normal(size=(3000, 3)).astype(np.float32)
    k, kp = 8, 32
    smm = StreamingCoreset(k=k, kprime=kp, dim=3)
    for i in range(0, 3000, 250):
        smm.update(stream[i:i + 250])
    cs = smm.finalize()
    got = np.asarray(sorted(map(tuple, cs.compact())))
    T_ref, d_ref, _ = reference_smm(stream, k, kp)
    want = np.asarray(sorted(map(tuple, T_ref)))
    # M top-up only fires when |T| < k; compare the T sets
    if len(T_ref) >= k:
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("mode", ["plain", "ext", "gen"])
def test_smm_invariants(mode, rng):
    stream = np.random.default_rng(7).normal(size=(5000, 3)) \
        .astype(np.float32)
    k, kp = 6, 24
    smm = StreamingCoreset(k=k, kprime=kp, dim=3, mode=mode)
    for i in range(0, 5000, 500):
        smm.update(stream[i:i + 500])
    st = smm.state
    T = np.asarray(st.T)[np.asarray(st.t_valid)]
    d_thr = float(st.d_thr)
    # invariant 2: pairwise distance of centers > d_i
    m = get_metric("euclidean")
    dm = np.asarray(m.pairwise(jnp.asarray(T), jnp.asarray(T))).copy()
    np.fill_diagonal(dm, np.inf)
    assert dm.min() > d_thr - 1e-5
    # invariant 1 (coverage): every stream point within 4 d_i of T
    dall = np.asarray(m.pairwise(jnp.asarray(stream), jnp.asarray(T)))
    assert dall.min(axis=1).max() <= 4 * d_thr + 1e-4

    cs = smm.finalize()
    if mode == "gen":
        assert cs.expanded_size >= k
        assert int(np.asarray(cs.multiplicity).max()) <= k
    else:
        assert cs.size >= k


def test_smm_ext_delegate_capacity():
    stream = np.random.default_rng(3).normal(size=(4000, 2)) \
        .astype(np.float32)
    smm = StreamingCoreset(k=5, kprime=20, dim=2, mode="ext")
    for i in range(0, 4000, 313):   # ragged chunks on purpose
        smm.update(stream[i:i + 313])
    st = smm.state
    cnt = np.asarray(st.e_cnt)
    valid = np.asarray(st.t_valid)
    assert (cnt[valid] >= 1).all() and (cnt[valid] <= 5).all()
    cs = smm.finalize()
    assert cs.size >= 5


def test_smm_duplicate_points_dont_hang():
    pts = np.ones((500, 3), np.float32)
    pts[::7] = 2.0   # two distinct values, heavy duplication
    smm = StreamingCoreset(k=2, kprime=8, dim=3)
    for i in range(0, 500, 100):
        smm.update(pts[i:i + 100])
    cs = smm.finalize()
    assert cs.size >= 2


def test_smm_small_stream_prefix_only():
    pts = np.random.default_rng(0).normal(size=(10, 3)).astype(np.float32)
    smm = StreamingCoreset(k=4, kprime=16, dim=3)
    smm.update(pts)
    cs = smm.finalize()   # stream smaller than k'+1: prefix buffer path
    assert cs.size == 10


# --------------------------------------------------------------------------
# the candidate-visiting insert against the per-row walk it replaced
# --------------------------------------------------------------------------

import functools  # noqa: E402

import jax  # noqa: E402

from repro.core import smm as smm_mod  # noqa: E402


@functools.partial(jax.jit, static_argnames=("metric_name", "mode", "k"))
def _walk_oracle(state, chunk, cvalid, start, metric_name: str,
                 mode: str, k: int):
    """Sequential per-point processing from ``start``; stops when T fills.

    Returns (state, next_pos, became_full).
    """
    cap = state.T.shape[0]
    c = chunk.shape[0]
    metric = get_metric(metric_name)

    def cond(carry):
        state, pos, full = carry
        return (pos < c) & ~full

    def body(carry):
        state, pos, full = carry
        p = chunk[pos]
        ok = cvalid[pos]
        d = metric.point_to_set(state.T, p)
        d = jnp.where(state.t_valid, d, jnp.inf)
        nd = jnp.min(d)
        nst = jnp.argmin(d)
        is_far = ok & (nd > 4.0 * state.d_thr)

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
            # --- near: delegate add if room
            room = e_cnt[nst] < k
            do_add = ok & ~is_far & room
            if mode == "ext":
                e_pts = e_pts.at[nst, jnp.clip(e_cnt[nst], 0, e_pts.shape[1] - 1)].set(
                    jnp.where(do_add, p, e_pts[nst, jnp.clip(e_cnt[nst], 0,
                                                             e_pts.shape[1] - 1)]))
            e_cnt = e_cnt.at[nst].add(jnp.where(do_add, 1, 0))
        new_state = state._replace(T=T, t_valid=t_valid, e_pts=e_pts, e_cnt=e_cnt)
        full = jnp.sum(t_valid) >= cap
        return new_state, pos + 1, full

    state, next_pos, full = jax.lax.while_loop(
        cond, body, (state, jnp.asarray(start, jnp.int32), jnp.asarray(False)))
    return state, next_pos, full


def _walk_insert(state, chunk, cvalid, start, metric_name, mode, k):
    # the walk visits every row from ``start`` until it stops
    state, next_pos, full = _walk_oracle(state, chunk, cvalid, start,
                                         metric_name, mode, k)
    return state, next_pos, full, next_pos - start


def _growing_stream(n=2048, d=8):
    # spread keeps growing: dense far rows, many merges (as in test_obs)
    rng = np.random.default_rng(0)
    return (rng.normal(size=(n, d))
            * np.geomspace(1, 1e4, n)[:, None]).astype(np.float32)


def _unit(x):
    return x / np.linalg.norm(x, axis=-1, keepdims=True)


def _topic_feed(chunk=256, d=768, chunks=8, new_per_chunk=4, seed=0):
    # 4 topics in the boot prefix; chunks 1..4 each bring new_per_chunk new
    # topics, the last of them on the chunk's last row; rows lie ~5 deg off
    # their topic
    rng = np.random.default_rng(seed)
    n_new = 4 * new_per_chunk
    topics = _unit(rng.normal(size=(4 + n_new, d)))
    sigma = np.tan(np.radians(5.0)) / np.sqrt(d)
    rows = []
    for j in range(chunks):
        t = rng.integers(0, 4 + min(j, 4) * new_per_chunk - (
            new_per_chunk if 1 <= j <= 4 else 0), size=chunk)
        if 1 <= j <= 4:
            first = 4 + (j - 1) * new_per_chunk
            at = np.sort(rng.choice(np.arange(64, chunk - 1),
                                    new_per_chunk - 1, replace=False))
            t[at] = np.arange(first, first + new_per_chunk - 1)
            t[-1] = first + new_per_chunk - 1
        rows.append(topics[t] + sigma * rng.normal(size=(chunk, d)))
    return np.concatenate(rows).astype(np.float32), chunk


def _tie_stream():
    # boot: slots 0, 2, 4, 5 survive the merge at 2 d_1 = 2; 1, 3, 6 free.
    # (-6,0) and (12,0) are far and fill slots 1 and 3; the rows at
    # (-3,0), (3,0) and (9,0) lie exactly between two centres, a start slot
    # and an inserted one on either side, so the lowest slot must win; the
    # (9,0) before (12,0) arrives sees only slot 4; (40,0) fills T last.
    boot = [(0, 100), (0, 101), (0, 0), (1, 0), (6, 0), (0, -100),
            (0, -101)]
    rows = [(0.5, 0), (9, 0), (-6, 0), (-3, 0), (3, 0), (-3, 0), (12, 0),
            (9, 0), (9, 0), (3, 0), (-3, 0), (0, 99), (9, 0), (0, -99),
            (3, 0), (-3, 0)]
    return np.asarray(boot + rows + rows[::-1] + [(40, 0)], np.float32), 16


_STREAMS = {
    "growing-256": (lambda: (_growing_stream(), 256), "euclidean", 8, 16),
    "growing-512": (lambda: (_growing_stream(), 512), "euclidean", 8, 16),
    "cosine-topics": (_topic_feed, "cosine", 4, 16),
    "tie": (_tie_stream, "euclidean", 2, 6),
}


def _smm_view(s):
    st = s.state
    valid = np.asarray(st.t_valid)
    return {"T": np.asarray(st.T)[valid], "t_valid": valid,
            "e_cnt": np.asarray(st.e_cnt), "e_pts": np.asarray(st.e_pts),
            "d_thr": np.asarray(st.d_thr), "phase_log": s.phase_log}


@pytest.mark.parametrize("stream", sorted(_STREAMS))
@pytest.mark.parametrize("mode", ["plain", "ext", "gen"])
def test_seq_insert_matches_walk(mode, stream, monkeypatch):
    make, metric, k, kprime = _STREAMS[stream]
    pts, chunk = make()
    kw = dict(k=k, kprime=kprime, dim=pts.shape[1], metric=metric, mode=mode)
    got, want = StreamingCoreset(**kw), StreamingCoreset(**kw)
    for i in range(0, len(pts), chunk):
        got.update(pts[i:i + chunk])
        with monkeypatch.context() as m:
            m.setattr(smm_mod, "_seq_insert", _walk_insert)
            want.update(pts[i:i + chunk])
        g, w = _smm_view(got), _smm_view(want)
        for key in w:
            if key == "phase_log":
                assert g[key] == w[key], (i, key)
            else:
                np.testing.assert_array_equal(g[key], w[key],
                                              err_msg=f"row {i}: {key}")
    assert len(got.phase_log) > 1           # some insert filled T
