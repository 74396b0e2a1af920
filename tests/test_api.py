"""The ``repro.diversify`` facade: planner mode selection, ``plan.explain``
golden output, bit-identity between every legacy entry point and its
``diversify()`` spelling, deprecation hygiene, and the tau/cliff + secant
satellite knobs."""
import warnings

import numpy as np
import pytest

import repro
from repro.api import ExecutionSpec, Plan, ProblemSpec, diversify, plan

pytestmark = pytest.mark.filterwarnings("ignore::DeprecationWarning")


def _pts(n=2048, d=4, seed=0):
    rng = np.random.default_rng(seed)
    return rng.normal(size=(n, d)).astype(np.float32)


def _labelled(n=2048, m=3, seed=0):
    pts = _pts(n, seed=seed)
    rng = np.random.default_rng(seed + 1)
    lab = rng.integers(0, m, size=n)
    lab[:m] = np.arange(m)
    return pts, lab


def _chunks(pts, step):
    for i in range(0, pts.shape[0], step):
        yield pts[i:i + step]


# --------------------------------------------------------------------------
# planner mode selection
# --------------------------------------------------------------------------

def test_auto_mode_array_is_batch():
    p = plan(ProblemSpec(points=_pts(), k=4))
    assert p.mode == "batch" and not p.constrained
    assert "array" in p.reason


def test_auto_mode_iterator_is_streaming():
    p = plan(ProblemSpec(points=_chunks(_pts(), 256), k=4, dim=4))
    assert p.mode == "streaming"
    assert "iterator" in p.reason


def test_auto_mode_num_reducers_is_mapreduce():
    p = plan(ProblemSpec(points=_pts(), k=4),
             ExecutionSpec(num_reducers=4))
    assert p.mode == "mapreduce" and p.num_reducers == 4


def test_auto_mode_mesh_is_mapreduce():
    import jax
    from jax.sharding import Mesh

    mesh = Mesh(np.array(jax.devices()[:1]), ("data",))
    p = plan(ProblemSpec(points=_pts(), k=4), ExecutionSpec(mesh=mesh))
    assert p.mode == "mapreduce" and p.mesh is mesh
    assert "mesh" in p.reason


def test_auto_mode_memory_budget_streams():
    pts = _pts(4096, 8)          # 128 KiB of float32
    p = plan(ProblemSpec(points=pts, k=4),
             ExecutionSpec(memory_budget_bytes=64 * 1024))
    assert p.mode == "streaming"
    assert "memory budget" in p.reason
    # a budget the array fits in keeps batch
    p2 = plan(ProblemSpec(points=pts, k=4),
              ExecutionSpec(memory_budget_bytes=1 << 30))
    assert p2.mode == "batch"


def test_labels_select_constrained_variant():
    pts, lab = _labelled()
    p = plan(ProblemSpec(points=pts, k=6, labels=lab))
    assert p.constrained
    assert p.matroid.__class__.__name__ == "PartitionMatroid"
    assert p.matroid.m == 3 and p.matroid.k == 6


def test_ext_variant_follows_measure():
    assert plan(ProblemSpec(points=_pts(), k=4)).variant == "plain"
    assert plan(ProblemSpec(points=_pts(), k=4,
                            measure="remote-clique")).variant == "ext"
    assert plan(ProblemSpec(points=_pts(), k=4),
                ExecutionSpec(generalized=True)).variant == "gen"


def test_plan_validation_errors():
    pts, lab = _labelled()
    with pytest.raises(ValueError, match="labels"):
        plan(ProblemSpec(points=pts, k=4, quotas=[2, 2]))
    with pytest.raises(ValueError, match="not both"):
        from repro.constrained import PartitionMatroid
        plan(ProblemSpec(points=pts, k=4, labels=lab,
                         quotas=[2, 2], matroid=PartitionMatroid([2, 2])))
    with pytest.raises(ValueError, match="sum"):
        plan(ProblemSpec(points=pts, k=5, labels=lab, quotas=[2, 2]))
    with pytest.raises(ValueError, match="streaming"):
        plan(ProblemSpec(points=_chunks(pts, 256), k=4),
             ExecutionSpec(mode="batch"))
    with pytest.raises(ValueError, match="mapreduce"):
        plan(ProblemSpec(points=pts, k=4), ExecutionSpec(mode="mapreduce"))
    with pytest.raises(ValueError, match="measure"):
        plan(ProblemSpec(points=pts, k=4, measure="nope"))
    with pytest.raises(ValueError, match="batch-only"):
        plan(ProblemSpec(points=pts, k=4, weights=np.ones(len(pts))),
             ExecutionSpec(mode="streaming"))
    with pytest.raises(ValueError, match="needs k="):
        diversify(pts)
    # problem keywords next to a ProblemSpec must error, never drop silently
    with pytest.raises(ValueError, match="not both"):
        diversify(ProblemSpec(points=pts, k=4), labels=lab)
    with pytest.raises(ValueError, match="not both"):
        diversify(ProblemSpec(points=pts, k=4), quotas=[2, 2])
    # flags whose execution path does not exist must fail at plan time
    with pytest.raises(ValueError, match="three_round"):
        plan(ProblemSpec(points=pts, k=4),
             ExecutionSpec(mode="mapreduce", num_reducers=2,
                           three_round=True))
    with pytest.raises(ValueError, match="recursive"):
        plan(ProblemSpec(points=pts, k=4),
             ExecutionSpec(mode="mapreduce", num_reducers=2, recursive=True))


def test_indices_are_lazy_for_matched_paths():
    res = diversify(ProblemSpec(points=_pts(1024), k=4),
                    ExecutionSpec(mode="batch", kprime=16, b=1))
    assert callable(res._indices)        # not computed yet
    idx = res.indices                    # first access matches rows
    assert not callable(res._indices)    # cached
    np.testing.assert_array_equal(idx, res.indices)
    assert len(set(idx.tolist())) == 4


def _match_rows_by_scan(pts, sol, k, row_labels=None, sol_labels=None):
    """The per-point O(n) nearest-row scan ``_match_rows`` must agree with."""
    idx, taken = [], np.zeros(len(pts), bool)
    for t, s in enumerate(sol):
        d = np.linalg.norm(pts - s[None, :], axis=1)
        if row_labels is not None:
            d = np.where(row_labels == sol_labels[t], d, np.inf)
        d[taken] = np.inf
        j = int(np.argmin(d))
        if np.isfinite(d[j]):
            idx.append(j)
            taken[j] = True
    return np.asarray(idx[:k])


@pytest.mark.parametrize("labelled", [False, True])
def test_match_rows_single_pass_equals_scan(labelled):
    """Duplicate input rows, repeated solution rows, shared first
    coordinates and a solution point that is no input row."""
    from repro.data.selection import _match_rows

    rng = np.random.default_rng(3)
    pts = rng.normal(size=(500, 4)).astype(np.float32)
    pts[10] = pts[3]                     # duplicate row
    pts[20:40, 0] = pts[5, 0]            # many rows share a first coordinate
    lab = rng.integers(0, 3, size=500)
    sol = np.concatenate([pts[[3, 3, 5, 25, 77]],
                          pts[[120]] + np.float32(1e-3)])
    sol_lab = lab[[3, 10, 5, 25, 77, 120]] if labelled else None
    kw = dict(row_labels=lab, sol_labels=sol_lab) if labelled else {}
    np.testing.assert_array_equal(_match_rows(pts, sol, 6, **kw),
                                  _match_rows_by_scan(pts, sol, 6, **kw))


# --------------------------------------------------------------------------
# plan.explain golden
# --------------------------------------------------------------------------

def test_plan_explain_golden_fixed_knobs():
    pts = np.zeros((4096, 8), np.float32)
    p = plan(ProblemSpec(points=pts, k=8),
             ExecutionSpec(mode="batch", kprime=64, b=4, chunk=1024))
    assert p.explain() == "\n".join([
        "DiversityPlan",
        "  mode: batch (requested)",
        "  problem: k=8, measure=remote-edge, metric=euclidean,"
        " input=(4096, 8), constrained=no",
        "  coreset: plain construction, kprime=64 (fixed)",
        "  engine: b=4, chunk=1024, schedule=none, use_pallas=False,"
        " tau=0.15, cliff=0.35",
        "  layout: single machine, one partition",
        "  predicted coreset: 64 rows, 2.0 KiB",
        "  solver: sequential alpha=2.0 (remote-edge)",
    ])


def test_plan_explain_golden_auto_constrained_mr():
    pts, lab = _labelled(4096, m=4, seed=3)
    p = plan(ProblemSpec(points=pts, k=8, labels=lab),
             ExecutionSpec(num_reducers=8, eps=0.3))
    assert p.explain() == "\n".join([
        "DiversityPlan",
        "  mode: mapreduce (auto: num_reducers=8)",
        "  problem: k=8, measure=remote-edge, metric=euclidean,"
        " input=(4096, 4), constrained=yes (PartitionMatroid, m=4)",
        "  coreset: plain construction, kprime=auto (milestones 32 -> 64"
        " -> 128 -> 256, eps=0.3, x2 first step, secant-refined),"
        " composed over 8 reducers x 4 groups",
        "  engine: b=auto, chunk=0, schedule=none, use_pallas=False,"
        " tau=0.15, cliff=0.35, sprint=auto",
        "  layout: simulated mapreduce, 8 reducers"
        " (vmap, partition=contiguous), 4 matroid groups",
        "  predicted coreset: <=8192 rows, <=128.0 KiB",
        "  solver: sequential alpha=2.0 (remote-edge),"
        " feasible greedy + 10 swap rounds",
    ])


def test_explain_is_stable_across_calls():
    spec = ProblemSpec(points=_pts(), k=4)
    assert plan(spec).explain() == plan(spec).explain()


# --------------------------------------------------------------------------
# bit-identity: legacy entry point == its diversify() spelling
# --------------------------------------------------------------------------

def test_batch_bit_identical_fixed_and_auto():
    from repro.core import diversity_maximize

    pts = _pts()
    sol_l, val_l, cs_l = diversity_maximize(pts, 6, "remote-edge", kprime=32)
    res = diversify(ProblemSpec(points=pts, k=6),
                    ExecutionSpec(mode="batch", kprime=32, b=1))
    np.testing.assert_array_equal(sol_l, res.solution)
    assert val_l == res.value
    assert res.cert is None and cs_l.cert is None
    # adaptive spelling carries an identical certificate
    sol_a, val_a, cs_a = diversity_maximize(pts, 6, "remote-edge",
                                            kprime="auto", b="auto", eps=0.4)
    res_a = diversify(ProblemSpec(points=pts, k=6),
                      ExecutionSpec(mode="batch", kprime="auto", b="auto",
                                    eps=0.4))
    np.testing.assert_array_equal(sol_a, res_a.solution)
    assert val_a == res_a.value
    assert cs_a.cert.to_dict() == res_a.cert.to_dict()


def test_batch_ext_measure_bit_identical():
    from repro.core import diversity_maximize

    pts = _pts(1024)
    sol_l, val_l, _ = diversity_maximize(pts, 4, "remote-clique", kprime=16)
    res = diversify(ProblemSpec(points=pts, k=4, measure="remote-clique"),
                    ExecutionSpec(mode="batch", kprime=16, b=1))
    np.testing.assert_array_equal(sol_l, res.solution)
    assert val_l == res.value and res.plan.variant == "ext"


def test_streaming_chunk_invariance_and_manual_parity():
    from repro.core import StreamingCoreset, solve_on_coreset

    pts = _pts()
    smm = StreamingCoreset(k=6, kprime=32, dim=4)
    for i in range(0, len(pts), 256):
        smm.update(pts[i:i + 256])
    sol_manual = solve_on_coreset(smm.finalize(), 6, "remote-edge")

    base = diversify(ProblemSpec(points=pts, k=6),
                     ExecutionSpec(mode="streaming", kprime=32, chunk=256))
    np.testing.assert_array_equal(sol_manual, base.solution)
    assert base.cert is not None and base.cert.kind == "streaming"
    # SMM state is chunk-invariant: any chunking, array or iterator source
    for chunks in (_chunks(pts, 100), _chunks(pts, 999), [pts]):
        res = diversify(ProblemSpec(points=chunks, k=6, dim=4),
                        ExecutionSpec(kprime=32))
        assert res.plan.mode == "streaming"
        np.testing.assert_array_equal(base.solution, res.solution)


def test_simulated_mr_bit_identical():
    from repro.core.distributed import simulate_mr

    pts = _pts()
    sol_l, val_l = simulate_mr(pts, 6, "remote-edge", num_reducers=4,
                               kprime=24)
    res = diversify(ProblemSpec(points=pts, k=6),
                    ExecutionSpec(mode="mapreduce", num_reducers=4,
                                  kprime=24, b=1))
    np.testing.assert_array_equal(sol_l, res.solution)
    assert val_l == res.value
    assert len(set(res.indices.tolist())) == 6


def test_constrained_bit_identical_all_paths():
    from repro.constrained import (fair_diversity_maximize,
                                   fair_streaming_diversity,
                                   simulate_fair_mr)

    pts, lab = _labelled()
    quotas = [2, 2, 2]
    idx_l, val_l, _ = fair_diversity_maximize(pts, lab, quotas, kprime=24)
    res = diversify(ProblemSpec(points=pts, k=6, labels=lab, quotas=quotas),
                    ExecutionSpec(mode="batch", kprime=24, b=1))
    np.testing.assert_array_equal(np.asarray(idx_l), res.indices)
    assert val_l == res.value
    np.testing.assert_array_equal(np.bincount(res.labels), quotas)

    sp, sl = fair_streaming_diversity(pts, lab, quotas, kprime=24, chunk=500)
    res = diversify(ProblemSpec(points=pts, k=6, labels=lab, quotas=quotas),
                    ExecutionSpec(mode="streaming", kprime=24, chunk=500))
    np.testing.assert_array_equal(sp, res.solution)
    np.testing.assert_array_equal(sl, res.labels)

    sp, sl, v = simulate_fair_mr(pts, lab, quotas, num_reducers=4, kprime=24)
    res = diversify(ProblemSpec(points=pts, k=6, labels=lab, quotas=quotas),
                    ExecutionSpec(mode="mapreduce", num_reducers=4,
                                  kprime=24, b=1))
    np.testing.assert_array_equal(sp, res.solution)
    assert v == res.value


def test_select_diverse_and_rerank_bit_identical():
    from repro.data import select_diverse
    from repro.serving import diverse_rerank

    pts, lab = _labelled(512)
    i1 = select_diverse(pts, 8)
    r1 = diversify(ProblemSpec(points=pts, k=8),
                   ExecutionSpec(mode="batch", kprime=None, b=1))
    np.testing.assert_array_equal(i1, r1.indices)

    i2 = select_diverse(pts, 6, group_labels=lab, num_reducers=4)
    r2 = diversify(ProblemSpec(points=pts, k=6, labels=lab),
                   ExecutionSpec(mode="mapreduce", num_reducers=4,
                                 kprime=None, b=1))
    np.testing.assert_array_equal(i2, r2.indices)

    i3 = diverse_rerank(pts[:64], 6, group_labels=lab[:64], quotas=[2, 2, 2])
    r3 = diversify(ProblemSpec(points=pts[:64], k=6, labels=lab[:64],
                               quotas=[2, 2, 2]),
                   ExecutionSpec(mode="batch", kprime=None, b=1))
    np.testing.assert_array_equal(i3, r3.indices)


def test_result_carries_telemetry_and_plan():
    res = diversify(ProblemSpec(points=_pts(1024), k=4),
                    ExecutionSpec(mode="batch", kprime=16, b=1))
    assert isinstance(res.plan, Plan)
    names = [p["name"] for p in res.telemetry["phases"]]
    assert "coreset" in names and "solve" in names
    assert all(p["seconds"] >= 0 for p in res.telemetry["phases"])


def test_weights_batch_path():
    pts = _pts(64)
    res = diversify(ProblemSpec(points=pts, k=4,
                                weights=np.ones(64, np.int64)))
    assert res.solution.shape == (4, 4) and res.value > 0


# --------------------------------------------------------------------------
# deprecation hygiene: one warning per legacy call, none from the facade
# --------------------------------------------------------------------------

def _count_deprecations(fn):
    with warnings.catch_warnings(record=True) as rec:
        warnings.simplefilter("always")
        fn()
    return sum(1 for w in rec if issubclass(w.category, DeprecationWarning))


@pytest.mark.filterwarnings("default")
def test_legacy_wrappers_warn_exactly_once():
    from repro.constrained import (fair_diversity_maximize,
                                   fair_streaming_diversity,
                                   simulate_fair_mr)
    from repro.core import diversity_maximize
    from repro.core.distributed import simulate_mr
    from repro.data import select_diverse
    from repro.serving import diverse_rerank

    pts, lab = _labelled(256)
    quotas = [2, 2, 2]
    legacy_calls = {
        "diversity_maximize":
            lambda: diversity_maximize(pts, 4, "remote-edge", kprime=16),
        "simulate_mr":
            lambda: simulate_mr(pts, 4, "remote-edge", num_reducers=2,
                                kprime=16),
        "fair_diversity_maximize":
            lambda: fair_diversity_maximize(pts, lab, quotas, kprime=16),
        "fair_streaming_diversity":
            lambda: fair_streaming_diversity(pts, lab, quotas, kprime=16),
        "simulate_fair_mr":
            lambda: simulate_fair_mr(pts, lab, quotas, num_reducers=2,
                                     kprime=16),
        "select_diverse": lambda: select_diverse(pts, 4),
        "diverse_rerank": lambda: diverse_rerank(pts, 4),
    }
    for name, fn in legacy_calls.items():
        assert _count_deprecations(fn) == 1, \
            f"{name} must emit exactly one DeprecationWarning"


@pytest.mark.filterwarnings("default")
def test_facade_emits_no_warnings():
    pts, lab = _labelled(256)

    def facade():
        diversify(ProblemSpec(points=pts, k=4),
                  ExecutionSpec(mode="batch", kprime=16))
        diversify(ProblemSpec(points=pts, k=6, labels=lab,
                              quotas=[2, 2, 2]),
                  ExecutionSpec(mode="streaming", kprime=16))
        diversify(ProblemSpec(points=pts, k=4),
                  ExecutionSpec(mode="mapreduce", num_reducers=2, kprime=16))

    assert _count_deprecations(facade) == 0


# --------------------------------------------------------------------------
# satellite knobs: tau/cliff overrides + secant milestone step
# --------------------------------------------------------------------------

def test_tau_cliff_overrides_reach_the_controller():
    from repro.core.adaptive import gmm_adaptive

    rng = np.random.default_rng(7)
    pts = rng.uniform(size=(8000, 3)).astype(np.float32)
    relaxed = gmm_adaptive(pts, 32, b0=8, tau=0.0, cliff=0.0)
    strict = gmm_adaptive(pts, 32, b0=8, tau=0.99, cliff=0.99)
    # tau ~= 1 rejects essentially every in-block pick -> the controller
    # collapses to b=1; tau = 0 commits full blocks on uniform data
    assert relaxed.schedule[0] == (8, 1) or relaxed.schedule[0][0] == 8
    assert any(b == 1 for b, _ in strict.schedule)
    assert strict.schedule != relaxed.schedule
    # the b=1 collapse is still exact GMM: radius no worse than relaxed
    assert float(strict.radius) <= float(relaxed.radius) * 1.10 + 1e-9


def test_tau_cliff_thread_through_drivers():
    from repro.core import build_coreset

    pts = _pts(1024)
    cs = build_coreset(pts, k=4, kprime=32, measure="remote-edge", b="auto",
                       tau=0.5, cliff=0.5)
    assert cs.cert is not None and cs.cert.kprime == 32
    res = diversify(ProblemSpec(points=pts, k=4),
                    ExecutionSpec(mode="batch", kprime=32, b="auto",
                                  tau=0.5, cliff=0.5))
    assert res.cert.to_dict() == cs.cert.to_dict()


def test_secant_next_step():
    from repro.core.adaptive import _secant_next

    # x2 first step and x2 cap
    assert _secant_next([], 0.3, 32, 1024) == 64
    assert _secant_next([(32, 0.8)], 0.3, 32, 1024) == 64
    # log-log secant: ratio halves per doubling -> slope -1
    assert _secant_next([(32, 0.8), (64, 0.4)], 0.3, 64, 1024) == 86
    # far target capped at x2
    assert _secant_next([(32, 0.8), (64, 0.4)], 0.05, 64, 1024) == 128
    # flat or inverted curves fall back to x2
    assert _secant_next([(32, 0.4), (64, 0.4)], 0.1, 64, 1024) == 128
    assert _secant_next([(32, 0.4), (64, 0.5)], 0.1, 64, 1024) == 128
    # the cap clamps to kmax
    assert _secant_next([(32, 0.8), (64, 0.4)], 0.05, 64, 100) == 100


def test_secant_auto_kprime_still_meets_target_and_can_stop_off_grid():
    from repro.core.adaptive import auto_kprime

    rng = np.random.default_rng(11)
    pts = rng.normal(size=(4000, 2)).astype(np.float32)
    res = auto_kprime(pts, k=5, eps=0.45)
    assert res.cert.meets_target
    # trajectory still monotone after re-planned milestones
    traj = np.asarray(res.traj)
    assert np.all(np.diff(traj) <= 1e-5)


def test_top_level_exports():
    assert repro.diversify is diversify
    assert repro.plan is plan
    assert repro.ProblemSpec is ProblemSpec
    assert repro.ExecutionSpec is ExecutionSpec
