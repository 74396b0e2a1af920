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
    invariant = ("distance_evals", "bytes_swept", "points_absorbed", "merges",
                 "h2d_bytes")
    a, b = (runs[c].telemetry.counters for c in (256, 1024))
    for key in invariant:
        assert a[key] == b[key], key
    assert a["points_absorbed"] == pts.shape[0]
    assert a["h2d_bytes"] == pts.size * 4           # n * d * 4, every row once
    assert runs[256].value == runs[1024].value


def _growing(n=2048, d=8):
    # a stream whose spread keeps growing: far points, sequential inserts
    # and threshold doublings all through it
    rng = np.random.default_rng(0)
    return (rng.normal(size=(n, d))
            * np.geomspace(1, 1e4, n)[:, None]).astype(np.float32)


@pytest.mark.parametrize("chunk,syncs,steps", [(256, 65, 114),
                                               (512, 59, 116)])
def test_streaming_host_syncs_pinned(chunk, syncs, steps):
    # every blocking read of the stream counts once: the counts the
    # program read before its readbacks were spanned.  The insert loop
    # visits only the rows far from the centres its call started with, and
    # its count rides on the insert's one read-back.
    c = _run(_growing(), mode="streaming", kprime=16,
             chunk=chunk).telemetry.counters
    assert c["merges"] == 14
    assert c["host_syncs"] == syncs
    assert c["insert_steps"] == steps


@pytest.mark.parametrize("far", [[(0, 100)],
                                 [(0, 100), (0, 101), (0, 200), (0, 300)]],
                         ids=["one", "four"])
def test_insert_steps_counts_rows_far_from_start(far):
    from repro.core import StreamingCoreset

    # boot: (0..8, 0) merges to centres (0,0), (3,0), (6,0) at d_1 = 1;
    # every other row lies within 4 d_1 of one of them.  (0,101) is far
    # from those but near (0,100): visited, not inserted.
    smm = StreamingCoreset(k=2, kprime=8, dim=2)
    smm.update(np.asarray([(i, 0) for i in range(9)], np.float32))
    chunk = np.asarray([(i % 9 + 0.5, 0.5) for i in range(40)], np.float32)
    chunk[[5, 13, 21, 30][:len(far)]] = far
    tr = RunTrace(enabled=True)
    with T.activate(tr):
        smm.update(chunk)
    assert tr.counters["insert_steps"] == len(far)
    assert tr.counters["host_syncs"] == 2      # classify, insert; no merge
    assert int(np.asarray(smm.state.t_valid).sum()) == 3 + len(
        {round(y, -2) for _, y in far})


def test_batch_h2d_bytes_counts_the_host_input():
    pts = _pts(2048)
    c = _run(pts, kprime=32, b=1).telemetry.counters
    assert c["h2d_bytes"] == pts.nbytes


def _profiled(tmp_path, fn):
    """Run ``fn`` under a CPU profiler session; the host annotations whose
    name starts with ``repro.``, as (name, start_ns, duration_ns)."""
    import glob

    import jax
    from jax.profiler import ProfileData

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        out = fn()
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(str(tmp_path / "plugins" / "profile" / "*"
                          / "*.xplane.pb"))
    events = [(e.name, e.start_ns, e.duration_ns)
              for plane in ProfileData.from_file(path).planes
              if plane.name.startswith("/host:")
              for line in plane.lines for e in line.events
              if e.name.startswith(T.PROFILE_PREFIX)]
    return out, events


def test_profiler_session_gets_unfenced_program_spans(tmp_path,
                                                      monkeypatch):
    # tracing off, a profiler on: every span is a bare repro.* annotation.
    # No enabled span exists to fence (the facade's phase rows still fence
    # through RunTrace.phase, as they always do).
    def fenced(*a, **k):
        raise AssertionError("an enabled span was opened")

    monkeypatch.setattr(T, "_SpanCtx", fenced)
    pts = _growing()
    res, events = _profiled(tmp_path, lambda: _run(
        pts, mode="streaming", kprime=16, chunk=512, trace=False))
    names = [n for n, _, _ in events]
    assert names.count("repro.smm.update") == pts.shape[0] // 512
    for name in ("upload", "classify", "insert", "readback", "merge"):
        assert f"repro.smm.{name}" in names, name
    assert "counters" not in dict(res.telemetry)
    assert T.span("smm.update") is T._NULL_SPAN       # session over


def test_enabled_span_is_prefixed_in_profile(tmp_path):
    # an enabled trace keeps the bare name and writes repro.<name>
    tr = RunTrace(enabled=True)

    def traced():
        with T.activate(tr):
            with T.span("smm.update", n=3):
                pass

    _, events = _profiled(tmp_path, traced)
    assert [n for n, _, _ in events] == ["repro.smm.update"]
    assert [s.name for s in tr.spans] == ["smm.update"]


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
