"""The ``repro.obs`` observability layer: exporter golden schemas, counter
correctness against hand-derived sweep counts, chunk-invariance of streaming
traces, the disabled-mode zero-allocation guarantee and the enabled-mode
overhead budget."""
import json
import tracemalloc

import numpy as np
import pytest

import repro
from repro.obs import (RunTrace, summary_markdown, to_chrome_trace, to_jsonl)
from repro.obs import trace as T


def _pts(n=2048, d=8, seed=0):
    rng = np.random.default_rng(seed)
    return rng.normal(size=(n, d)).astype(np.float32)


def _run(pts, *, mode="batch", trace=True, **exec_kw):
    return repro.diversify(pts, k=8, execution=repro.ExecutionSpec(
        mode=mode, trace=trace, **exec_kw))


# --------------------------------------------------------------------------
# exporter golden schemas
# --------------------------------------------------------------------------

def test_chrome_trace_schema():
    res = _run(_pts(), kprime=32, b=1)
    doc = to_chrome_trace(res.telemetry)
    assert sorted(doc) == ["displayTimeUnit", "otherData", "traceEvents"]
    assert doc["displayTimeUnit"] == "ms"
    events = doc["traceEvents"]
    assert events, "traced run must emit events"
    for ev in events:
        assert ev["ph"] in ("X", "C")
        assert {"name", "ts", "pid", "tid"} <= set(ev)
        if ev["ph"] == "X":
            assert ev["dur"] >= 0 and ev["cat"] == "repro"
    # exactly one counter sample, carrying the run's counters verbatim
    csamples = [e for e in events if e["ph"] == "C"]
    assert len(csamples) == 1
    assert csamples[0]["args"] == dict(res.telemetry.counters)
    # phase spans present as top-level X events
    names = {e["name"] for e in events}
    assert {"coreset", "solve", "value"} <= names
    json.dumps(doc)                       # must be JSON-serializable


def test_chrome_trace_disabled_synthesizes_phases():
    res = _run(_pts(), kprime=32, b=1, trace=False)
    doc = to_chrome_trace(res.telemetry)
    names = [e["name"] for e in doc["traceEvents"]]
    assert names == ["coreset", "solve", "value"]
    # contiguous: each event starts where the previous ended
    ts = [e["ts"] for e in doc["traceEvents"]]
    assert ts == sorted(ts) and ts[0] == 0.0


def test_jsonl_schema():
    res = _run(_pts(), kprime=32, b=1)
    lines = to_jsonl(res.telemetry).strip().split("\n")
    rows = [json.loads(ln) for ln in lines]
    kinds = [r["type"] for r in rows]
    assert kinds[0] == "meta" and kinds[1] == "counters"
    assert {"phase", "span"} <= set(kinds)
    meta = rows[0]
    assert meta["enabled"] is True and meta["mode"] == "batch"
    counters = {k: v for k, v in rows[1].items() if k != "type"}
    assert counters == dict(res.telemetry.counters)
    for r in rows:
        if r["type"] == "phase":
            assert {"name", "seconds"} <= set(r)
        if r["type"] == "span":
            assert {"name", "seconds", "depth"} <= set(r)
            assert "children" not in r    # flattened depth-first


def test_summary_markdown_tables():
    res = _run(_pts(), kprime=32, b=1)
    md = summary_markdown(res.telemetry, title="smoke")
    assert "### smoke" in md and "mode: `batch`" in md
    assert "| phase | seconds | share |" in md
    assert "| counter | value |" in md
    assert "| distance_evals |" in md


# --------------------------------------------------------------------------
# counter correctness
# --------------------------------------------------------------------------

def test_batch_b1_distance_evals_exact():
    # plain GMM sweeps the n points once per selected center: exactly n*k'
    # point-to-center distance evaluations, in one device dispatch.
    n, kprime = 2048, 32
    res = _run(_pts(n), kprime=kprime, b=1)
    c = res.telemetry.counters
    assert c["distance_evals"] == n * kprime
    assert c["device_dispatches"] == 1
    assert c["host_syncs"] == 0
    assert c["bytes_swept"] == T.sweep_bytes(n, 8, sweeps=kprime)


def test_batch_blocked_distance_evals_match_fold_sizes():
    # lookahead-b blocking folds centers in groups; schedule_fold_sizes is
    # the exact per-sweep fold count, so n * sum(folds) is the eval count.
    from repro.core.gmm import schedule_fold_sizes
    n, kprime, b = 2048, 32, 8
    res = _run(_pts(n), kprime=kprime, b=b)
    folds = schedule_fold_sizes(((b, kprime // b),))
    assert res.telemetry.counters["distance_evals"] == n * sum(folds)


def test_schedule_fold_sizes_degenerate():
    from repro.core.gmm import schedule_fold_sizes
    # b=1 single-phase schedule folds 1 center k times = plain GMM
    assert sum(schedule_fold_sizes(((1, 16),))) == 16
    # blocked: seed fold 1, then b per round, final fold b
    assert schedule_fold_sizes(((4, 4),)) == (1, 4, 4, 4, 4)


def test_adaptive_host_syncs_match_spans():
    # the adaptive controller's host round-trips are exactly its spans:
    # every adaptive.block / adaptive.fold / adaptive.resume wraps one
    # blocking readback barrier, so host_syncs == span count.
    res = _run(_pts(4096), kprime=16, b="auto")
    tr = res.telemetry

    def adaptive_spans(spans):
        out = 0
        for s in spans:
            out += s.name.startswith("adaptive.")
            out += adaptive_spans(s.children)
        return out

    n_spans = adaptive_spans(tr.spans)
    assert n_spans > 0
    assert tr.counters["host_syncs"] == n_spans
    assert tr.counters["device_dispatches"] == n_spans


def test_mapreduce_counters_and_reducer_spans():
    n, reducers, kprime = 4096, 4, 16
    res = _run(_pts(n), mode="mapreduce", num_reducers=reducers,
               kprime=kprime, b=1, trace="reducers")
    tr = res.telemetry
    # round 1 runs GMM(k') on each reducer's n/reducers points
    assert tr.counters["distance_evals"] >= n * kprime
    names = []

    def walk(spans):
        for s in spans:
            names.append(s.name)
            walk(s.children)

    walk(tr.spans)
    for i in range(reducers):
        assert f"mr.reducer[{i}]" in names
    assert "mr_stragglers" in tr.extras


@pytest.mark.parametrize("trace", [True, "reducers"])
def test_mapreduce_dispatches_one_per_reducer(trace):
    # the simulated reducers run one dispatch each, fenced or not
    res = _run(_pts(2048), mode="mapreduce", num_reducers=4, kprime=16,
               b=1, trace=trace)
    assert res.telemetry.counters["device_dispatches"] == 4


def test_compile_probe_counts_compiles_and_seconds():
    import jax
    import jax.numpy as jnp

    tr = RunTrace(enabled=True)
    with T.activate(tr):
        jax.jit(lambda x: jnp.sin(x) * 3.0 + 1.0)(jnp.ones((7, 5)))
    assert tr.counters["jit_recompiles"] >= 1
    assert tr.compile_seconds > 0.0


def test_pallas_compiled_counts_only_compiled_kernels():
    from repro.kernels.gmm_update import staged_interpret

    tr = RunTrace(enabled=True)
    with T.activate(tr):
        assert staged_interpret(True) is True
        assert tr.counters["pallas_compiled"] == 0
        assert staged_interpret(False) is False
    assert tr.counters["pallas_compiled"] == 1


def test_streaming_counters_chunk_invariant():
    # the SMM state evolution is a function of the point order, not of how
    # the stream is chunked: work counters and the result must agree.
    pts = _pts(4096)
    runs = {c: _run(pts, mode="streaming", kprime=32, chunk=c)
            for c in (256, 1024)}
    invariant = ("distance_evals", "bytes_swept", "points_absorbed", "merges")
    a, b = (runs[c].telemetry.counters for c in (256, 1024))
    for key in invariant:
        assert a[key] == b[key], key
    assert a["points_absorbed"] == pts.shape[0]
    assert runs[256].value == runs[1024].value


def test_legacy_telemetry_dict_view():
    res = _run(_pts(), kprime=32, b=1)
    tr = res.telemetry
    assert isinstance(tr, RunTrace)
    # Mapping protocol: the legacy dict contract
    assert [p["name"] for p in tr["phases"]] == ["coreset", "solve", "value"]
    assert tr["mode"] == "batch"
    assert dict(tr)["counters"] == dict(tr.counters)
    # disabled runs keep the phase rows but carry no counters key
    off = _run(_pts(), kprime=32, b=1, trace=False).telemetry
    assert "counters" not in dict(off)
    assert [p["name"] for p in off["phases"]] == ["coreset", "solve", "value"]


def test_explain_actual_renders_measured():
    res = _run(_pts(), kprime=32, b=1)
    text = res.plan.explain(actual=True)
    assert "measured:" in text and "x" in text


# --------------------------------------------------------------------------
# overhead guarantees
# --------------------------------------------------------------------------

def test_disabled_mode_is_allocation_free():
    # with no active trace, count()/counting()/span() are a global load +
    # None test; the hot loops can carry them with zero allocation.
    assert T.active() is None
    count, counting, span = T.count, T.counting, T.span
    loop = (None,) * 1000
    count("distance_evals", 3)            # warm everything up
    counting()
    span("phase")
    # tracemalloc is process-wide: JAX's background dispatch threads can
    # allocate inside the window, so take the cleanest of a few attempts.
    best_cur, best_peak = None, None
    for _ in range(5):
        tracemalloc.start()
        tracemalloc.clear_traces()
        for _ in loop:
            count("distance_evals", 3)
            counting()
            span("phase")
        current, peak = tracemalloc.get_traced_memory()
        tracemalloc.stop()
        if best_cur is None or current < best_cur:
            best_cur, best_peak = current, peak
        if best_cur == 0:
            break
    assert best_cur == 0
    assert best_peak < 1024               # transient frame churn only


def test_enabled_overhead_small():
    # budget: <3% on real workloads; the gate is looser (15%) because the
    # tier-1 box timing granularity is ~1ms on a ~15ms run.
    import time

    pts = _pts(20000, 16)

    def once(trace):
        t0 = time.perf_counter()
        _run(pts, kprime=64, b=1, trace=trace)
        return time.perf_counter() - t0

    once(False), once(True)               # compile both variants
    off = min(once(False) for _ in range(5))
    on = min(once(True) for _ in range(5))
    assert on <= off * 1.15 + 2e-3, (on, off)


def test_trace_env_var(monkeypatch):
    monkeypatch.setenv(T.ENV_VAR, "1")
    assert T.trace_from_spec("auto").enabled
    monkeypatch.setenv(T.ENV_VAR, "reducers")
    tr = T.trace_from_spec("auto")
    assert tr.enabled and tr.reducers
    monkeypatch.delenv(T.ENV_VAR)
    assert not T.trace_from_spec("auto").enabled
