"""Fault-injection matrix for the resilience layer (ISSUE 7 tentpole).

Three acceptance properties, asserted per execution path:

1. retry     — a single injected failure, replayed under ``on_failure="retry"``,
               produces a BIT-IDENTICAL solution/value to the no-fault run
               (injection fires before any state mutation, so the replay sees
               pristine inputs).
2. degrade   — a permanently-lost unit yields a ``RadiusCertificate`` with
               ``degraded=True`` and surviving-shard coverage accounting.
3. resume    — a streaming run killed mid-stream and restarted from its
               checkpoint finalizes to the same core-set and certificate as
               the uninterrupted run.

The fast lane here runs small-n instances; the heavy sweep is ``slow``.
"""
import numpy as np
import pytest

import jax.numpy as jnp

import repro
from repro.api import ExecutionSpec, ProblemSpec, diversify, plan
from repro.distributed import (FailureInjector, InjectedFailure,
                               ResiliencePolicy, retry_call, run_resilient)


def _pts(n=640, d=4, seed=0):
    return np.random.default_rng(seed).normal(size=(n, d)).astype(np.float32)


def _labelled(n=640, d=4, seed=0):
    pts = _pts(n, d, seed)
    lab = np.arange(n) % 3
    return pts, lab


def _mr(pts, pol=None, **kw):
    return diversify(ProblemSpec(points=pts, k=4),
                     ExecutionSpec(mode="mapreduce", num_reducers=4,
                                   kprime=16, b=1, resilience=pol, **kw))


def _stream(chunks, pol=None, **kw):
    return diversify(ProblemSpec(points=iter(chunks), k=4),
                     ExecutionSpec(mode="streaming", kprime=16,
                                   resilience=pol, **kw))


# -- injector / policy units --------------------------------------------------

def test_injector_fires_once_per_point():
    inj = FailureInjector(fail_at=("reducer:1",))
    with pytest.raises(InjectedFailure):
        inj.maybe_fail("reducer:1")
    inj.maybe_fail("reducer:1")  # second hit: already fired, no raise
    inj.maybe_fail("reducer:0")
    assert inj.fired == ("reducer:1",)


def test_injector_rate_is_seeded_and_deterministic():
    hits = []
    for _ in range(2):
        inj = FailureInjector(rate=0.5, seed=7)
        fired = []
        for j in range(32):
            try:
                inj.maybe_fail(f"chunk:{j}")
            except InjectedFailure:
                fired.append(j)
        hits.append(tuple(fired))
    assert hits[0] == hits[1]
    assert 0 < len(hits[0]) < 32


def test_policy_validation():
    with pytest.raises(ValueError, match="on_failure"):
        ResiliencePolicy(on_failure="panic")
    with pytest.raises(ValueError, match="max_retries"):
        ResiliencePolicy(max_retries=-1)
    with pytest.raises(ValueError, match="checkpoint_every"):
        ResiliencePolicy(checkpoint_every=0)
    assert ResiliencePolicy(backoff_s=0.5).backoff(2) == 2.0


def test_run_resilient_retry_and_exhaustion():
    calls = []

    def run_one(i):
        calls.append(i)
        return i * 10

    pol = ResiliencePolicy(max_retries=2,
                           injector=FailureInjector(fail_at=("reducer:1",)))
    out, rep = run_resilient(3, run_one, pol)
    assert out == [0, 10, 20]
    assert rep.retries == 1 and rep.failures_injected == 1
    assert rep.recovered == 1 and not rep.degraded

    pol0 = ResiliencePolicy(max_retries=0,
                            injector=FailureInjector(fail_at=("reducer:0",)))
    with pytest.raises(InjectedFailure):
        run_resilient(3, run_one, pol0)


def test_run_resilient_degrade_collects_survivors():
    pol = ResiliencePolicy(on_failure="degrade",
                           injector=FailureInjector(fail_at=("reducer:2",)))
    out, rep = run_resilient(4, lambda i: i, pol)
    assert out == [0, 1, None, 3]
    assert rep.failed == [2] and rep.survivors == (0, 1, 3)
    assert rep.degraded and rep.to_dict()["degraded"]


@pytest.mark.parametrize("on_failure", ["retry", "degrade"])
def test_program_errors_are_not_lost_units(on_failure):
    """A compile / memory / program error is not a lost reducer: it
    propagates under every policy instead of being retried or dropped."""
    from jax.errors import JaxRuntimeError

    pol = ResiliencePolicy(on_failure=on_failure, max_retries=3)
    for err in (ValueError("bad lowering"),
                JaxRuntimeError("RESOURCE_EXHAUSTED: out of memory"),
                JaxRuntimeError("INTERNAL: Mosaic failed to compile")):
        def run_one(i, err=err):
            raise err
        with pytest.raises(type(err)):
            run_resilient(2, run_one, pol)
        with pytest.raises(type(err)):
            retry_call(lambda: run_one(0), pol, point="round:x")
    lost = JaxRuntimeError("UNAVAILABLE: device lost")
    seen = []

    def flaky(i):
        if not seen:
            seen.append(i)
            raise lost
        return i
    out, rep = run_resilient(2, flaky, pol)
    assert rep.degraded == (on_failure == "degrade")
    assert rep.retries == (on_failure == "retry")


def test_retry_call_round_scope():
    attempts = []
    inj = FailureInjector(fail_at=("round:mr.round1",))
    pol = ResiliencePolicy(max_retries=1, injector=inj)

    def fn():
        attempts.append(1)
        return 42

    out, rep = retry_call(fn, pol, point="round:mr.round1")
    assert out == 42
    assert len(attempts) == 1 and rep.retries == 1


# -- plan validation + explain ------------------------------------------------

def test_plan_rejects_resilience_on_batch():
    with pytest.raises(ValueError, match="batch"):
        plan(ProblemSpec(points=_pts(), k=4),
             ExecutionSpec(mode="batch", resilience=ResiliencePolicy()))
    with pytest.raises(TypeError, match="ResiliencePolicy"):
        plan(ProblemSpec(points=_pts(), k=4),
             ExecutionSpec(mode="mapreduce", num_reducers=4,
                           resilience={"max_retries": 2}))


def test_plan_rejects_constrained_stream_checkpoint():
    pts, lab = _labelled()
    with pytest.raises(ValueError, match="constrained"):
        plan(ProblemSpec(points=pts, k=6, labels=lab, quotas=[2, 2, 2]),
             ExecutionSpec(mode="streaming", kprime=16,
                           resilience=ResiliencePolicy(checkpoint_dir="/x")))


def test_explain_renders_resilience_line_only_when_set():
    pts = _pts()
    base = plan(ProblemSpec(points=pts, k=4),
                ExecutionSpec(mode="mapreduce", num_reducers=4, kprime=16))
    assert "resilience" not in base.explain()
    pol = ResiliencePolicy(max_retries=3, on_failure="degrade",
                           injector=FailureInjector(rate=0.1))
    p = plan(ProblemSpec(points=pts, k=4),
             ExecutionSpec(mode="mapreduce", num_reducers=4, kprime=16,
                           resilience=pol))
    line = [l for l in p.explain().splitlines() if "resilience" in l]
    assert line and "on_failure=degrade" in line[0]
    assert "max_retries=3" in line[0] and "injector=armed" in line[0]


# -- simulated MapReduce ------------------------------------------------------

def test_mr_retry_bit_identical_and_counted():
    pts = _pts()
    base = _mr(pts)                                      # vmapped, no policy
    clean = _mr(pts, ResiliencePolicy(max_retries=2))    # per-reducer dispatch
    np.testing.assert_array_equal(np.asarray(base.solution),
                                  np.asarray(clean.solution))
    pol = ResiliencePolicy(max_retries=2,
                           injector=FailureInjector(fail_at=("reducer:1",)))
    faulted = _mr(pts, pol, trace=True)
    np.testing.assert_array_equal(np.asarray(base.solution),
                                  np.asarray(faulted.solution))
    assert faulted.value == base.value
    counters = faulted.telemetry["counters"]
    assert counters["retries"] == 1
    assert counters["failures_injected"] == 1
    assert counters["reducers_recovered"] == 1
    res = faulted.telemetry["resilience"]
    assert res["retries"] == 1 and not res["degraded"]


def test_mr_degrade_yields_certified_coverage():
    pts = _pts()
    pol = ResiliencePolicy(on_failure="degrade",
                           injector=FailureInjector(fail_at=("reducer:1",)))
    res = _mr(pts, pol)
    cert = res.cert
    assert cert.degraded
    assert cert.surviving_shards == (0, 2, 3)
    assert cert.total_shards == 4
    # coverage accounting is in shard rows: 3 of 4 equal partitions survive
    assert cert.points_covered == cert.points_total * 3 // 4
    assert res.value > 0
    assert res.telemetry["resilience"]["failed"] == [1]


def test_mr_all_reducers_lost_raises():
    pts = _pts()
    pol = ResiliencePolicy(
        on_failure="degrade",
        injector=FailureInjector(fail_at=tuple(f"reducer:{i}"
                                               for i in range(4))))
    with pytest.raises(RuntimeError, match="all"):
        _mr(pts, pol)


def test_mr_raise_propagates():
    pts = _pts()
    pol = ResiliencePolicy(on_failure="raise",
                           injector=FailureInjector(fail_at=("reducer:0",)))
    with pytest.raises(InjectedFailure):
        _mr(pts, pol)


def test_mr_generalized_degrade_reruns_survivor_multiplicities():
    pts = _pts()
    pol = ResiliencePolicy(on_failure="degrade",
                           injector=FailureInjector(fail_at=("reducer:2",)))
    res = diversify(ProblemSpec(points=pts, k=4, measure="remote-clique"),
                    ExecutionSpec(mode="mapreduce", num_reducers=4,
                                  kprime=16, b=1, generalized=True,
                                  resilience=pol))
    assert res.cert.degraded and res.cert.surviving_shards == (0, 1, 3)
    assert res.value > 0


# -- constrained MapReduce ----------------------------------------------------

def _fair_mr(pts, lab, pol=None):
    return diversify(ProblemSpec(points=pts, k=6, labels=lab,
                                 quotas=[2, 2, 2]),
                     ExecutionSpec(mode="mapreduce", num_reducers=4,
                                   kprime=24, b=1, resilience=pol))


def test_fair_mr_retry_bit_identical():
    pts, lab = _labelled()
    base = _fair_mr(pts, lab)
    pol = ResiliencePolicy(max_retries=2,
                           injector=FailureInjector(fail_at=("reducer:3",)))
    faulted = _fair_mr(pts, lab, pol)
    np.testing.assert_array_equal(np.asarray(base.solution),
                                  np.asarray(faulted.solution))
    np.testing.assert_array_equal(base.labels, faulted.labels)
    assert base.value == faulted.value


def test_fair_mr_degrade_certificate():
    pts, lab = _labelled()
    pol = ResiliencePolicy(on_failure="degrade",
                           injector=FailureInjector(fail_at=("reducer:0",)))
    res = _fair_mr(pts, lab, pol)
    cert = res.cert
    assert cert.degraded and cert.surviving_shards == (1, 2, 3)
    assert cert.total_shards == 4
    assert cert.points_covered == cert.points_total * 3 // 4
    np.testing.assert_array_equal(np.bincount(res.labels), [2, 2, 2])


# -- streaming ----------------------------------------------------------------

def test_stream_chunk_retry_bit_identical():
    pts = _pts()
    chunks = [pts[i * 64:(i + 1) * 64] for i in range(10)]
    base = _stream(chunks)
    pol = ResiliencePolicy(max_retries=2,
                           injector=FailureInjector(fail_at=("chunk:3",)))
    faulted = _stream(chunks, pol, trace=True)
    np.testing.assert_array_equal(np.asarray(base.solution),
                                  np.asarray(faulted.solution))
    assert base.value == faulted.value
    assert faulted.telemetry["counters"]["retries"] == 1
    assert faulted.telemetry["resilience"]["scope"] == "chunk"


def test_stream_degrade_drops_chunk_with_accounting():
    pts = _pts()
    chunks = [pts[i * 64:(i + 1) * 64] for i in range(10)]
    pol = ResiliencePolicy(on_failure="degrade",
                           injector=FailureInjector(fail_at=("chunk:4",)))
    res = _stream(chunks, pol)
    cert = res.cert
    assert cert.degraded
    assert cert.total_shards == 10 and 4 not in cert.surviving_shards
    assert cert.points_total == 640 and cert.points_covered == 640 - 64
    assert res.value > 0


def test_stream_kill_resume_matches_uninterrupted(tmp_path):
    pts = _pts()
    chunks = [pts[i * 64:(i + 1) * 64] for i in range(10)]
    base = _stream(chunks)

    kill = ResiliencePolicy(on_failure="raise", checkpoint_dir=str(tmp_path),
                            checkpoint_every=3,
                            injector=FailureInjector(fail_at=("chunk:7",)))
    with pytest.raises(InjectedFailure):
        _stream(chunks, kill)

    resume = ResiliencePolicy(checkpoint_dir=str(tmp_path),
                              checkpoint_every=3)
    res = _stream(chunks, resume, trace=True)
    np.testing.assert_array_equal(np.asarray(base.solution),
                                  np.asarray(res.solution))
    assert res.value == base.value
    assert res.cert.radius == base.cert.radius
    assert res.cert.kprime == base.cert.kprime
    rs = res.telemetry["resilience"]
    assert rs["resumed_from"] is not None  # picked up mid-stream
    assert res.telemetry["counters"]["checkpoints_written"] >= 1


def test_stream_checkpoints_written_uninterrupted(tmp_path):
    pts = _pts()
    chunks = [pts[i * 64:(i + 1) * 64] for i in range(9)]
    pol = ResiliencePolicy(checkpoint_dir=str(tmp_path), checkpoint_every=2)
    res = _stream(chunks, pol, trace=True)
    assert res.telemetry["counters"]["checkpoints_written"] >= 4
    base = _stream(chunks)
    np.testing.assert_array_equal(np.asarray(base.solution),
                                  np.asarray(res.solution))


def test_fair_stream_chunk_retry():
    pts, lab = _labelled()
    spec = ProblemSpec(points=pts, k=6, labels=lab, quotas=[2, 2, 2])
    base = diversify(spec, ExecutionSpec(mode="streaming", kprime=24,
                                         chunk=80))
    pol = ResiliencePolicy(max_retries=1,
                           injector=FailureInjector(fail_at=("chunk:2",)))
    faulted = diversify(spec, ExecutionSpec(mode="streaming", kprime=24,
                                            chunk=80, resilience=pol))
    np.testing.assert_array_equal(np.asarray(base.solution),
                                  np.asarray(faulted.solution))
    np.testing.assert_array_equal(base.labels, faulted.labels)


# -- streaming core-set state round-trip --------------------------------------

def test_smm_state_dict_roundtrip():
    from repro.checkpoint import CheckpointManager
    from repro.core.smm import StreamingCoreset

    pts = _pts(512)
    smm = StreamingCoreset(k=4, kprime=16, dim=4)
    for i in range(8):
        smm.update(pts[i * 64:(i + 1) * 64])
    arrays, meta = smm.state_dict()
    smm2 = StreamingCoreset.from_state_dict(arrays, meta)
    a = smm.finalize()
    b = smm2.finalize()
    np.testing.assert_array_equal(np.asarray(a.points), np.asarray(b.points))
    assert a.cert.radius == b.cert.radius


def test_smm_save_restore_via_manager(tmp_path):
    from repro.checkpoint import CheckpointManager
    from repro.core.smm import StreamingCoreset

    pts = _pts(512)
    smm = StreamingCoreset(k=4, kprime=16, dim=4)
    for i in range(5):
        smm.update(pts[i * 64:(i + 1) * 64])
    mgr = CheckpointManager(str(tmp_path), keep_k=2)
    smm.save(mgr, step=5)
    got, step = StreamingCoreset.restore(mgr)
    assert step == 5
    for i in range(5, 8):
        chunk = pts[i * 64:(i + 1) * 64]
        smm.update(chunk)
        got.update(chunk)
    a, b = smm.finalize(), got.finalize()
    np.testing.assert_array_equal(np.asarray(a.points), np.asarray(b.points))
    assert a.cert.radius == b.cert.radius


def test_smm_restore_empty_dir_returns_none(tmp_path):
    from repro.checkpoint import CheckpointManager
    from repro.core.smm import StreamingCoreset

    got, step = StreamingCoreset.restore(CheckpointManager(str(tmp_path)))
    assert got is None and step is None


# -- heavy sweep (tier-1 local only) ------------------------------------------

@pytest.mark.slow
@pytest.mark.parametrize("measure", ["remote-edge", "remote-clique"])
@pytest.mark.parametrize("victim", [0, 2, 5])
def test_mr_retry_matrix_heavy(measure, victim):
    pts = _pts(3000, 6, seed=9)
    spec = ProblemSpec(points=pts, k=6, measure=measure)
    base = diversify(spec, ExecutionSpec(mode="mapreduce", num_reducers=6,
                                         kprime=32, b=1))
    pol = ResiliencePolicy(
        max_retries=2,
        injector=FailureInjector(fail_at=(f"reducer:{victim}",)))
    faulted = diversify(spec, ExecutionSpec(mode="mapreduce", num_reducers=6,
                                            kprime=32, b=1, resilience=pol))
    np.testing.assert_array_equal(np.asarray(base.solution),
                                  np.asarray(faulted.solution))
    assert base.value == faulted.value


@pytest.mark.slow
def test_mr_random_rate_chaos_converges():
    """Seeded random-rate injection under retry: always bit-identical."""
    pts = _pts(2000, 5, seed=3)
    spec = ProblemSpec(points=pts, k=5)
    base = diversify(spec, ExecutionSpec(mode="mapreduce", num_reducers=8,
                                         kprime=32, b=1))
    for seed in range(4):
        pol = ResiliencePolicy(max_retries=4,
                               injector=FailureInjector(rate=0.3, seed=seed))
        res = diversify(spec, ExecutionSpec(mode="mapreduce", num_reducers=8,
                                            kprime=32, b=1, resilience=pol))
        np.testing.assert_array_equal(np.asarray(base.solution),
                                      np.asarray(res.solution))


@pytest.mark.slow
def test_stream_resume_matrix_heavy(tmp_path):
    """Kill at several points; every resume matches the uninterrupted run."""
    pts = _pts(2048, 5, seed=4)
    chunks = [pts[i * 128:(i + 1) * 128] for i in range(16)]
    base = diversify(ProblemSpec(points=iter(chunks), k=5),
                     ExecutionSpec(mode="streaming", kprime=32))
    for kill_at in (2, 9, 15):
        d = tmp_path / f"kill{kill_at}"
        kill = ResiliencePolicy(on_failure="raise", checkpoint_dir=str(d),
                                checkpoint_every=2,
                                injector=FailureInjector(
                                    fail_at=(f"chunk:{kill_at}",)))
        with pytest.raises(InjectedFailure):
            diversify(ProblemSpec(points=iter(chunks), k=5),
                      ExecutionSpec(mode="streaming", kprime=32,
                                    resilience=kill))
        res = diversify(ProblemSpec(points=iter(chunks), k=5),
                        ExecutionSpec(mode="streaming", kprime=32,
                                      resilience=ResiliencePolicy(
                                          checkpoint_dir=str(d),
                                          checkpoint_every=2)))
        np.testing.assert_array_equal(np.asarray(base.solution),
                                      np.asarray(res.solution))
        assert res.cert.radius == base.cert.radius
