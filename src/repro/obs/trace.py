"""RunTrace — the one observability dialect for every execution path.

The paper's experimental story lives on measured quantities (core-set radius
vs. rounds, points swept, per-round work), but the repo grew four mutually
incompatible instruments: ``api._Phases`` wall-clocks, ``smm.phase_log``,
the adaptive controller's trajectory and ``fault_tolerance``'s straggler
timers.  This module unifies them:

* a ``RunTrace`` holds nested ``Span``s (phase -> sweep -> block) and
  monotonic counters (``distance_evals``, ``bytes_swept``, ``host_syncs``,
  ``device_dispatches``, ``pool_widenings``, ``jit_recompiles``,
  ``points_absorbed``, ``merges``);
* spans are JAX-aware: an optional ``sync=`` target is fenced with
  ``jax.block_until_ready`` so spans measure execution, not async dispatch,
  and enabled spans emit ``jax.profiler.TraceAnnotation`` +
  ``jax.named_scope`` so they line up with device profiles;
* every span is written into the JAX profiler's trace as ``repro.<name>``
  whenever a profiler session runs, enabled trace or not.  With no enabled
  trace it is a bare ``TraceAnnotation``: no fence, no ``Span`` record, so
  a profile of an untraced run shows the program's own host steps on the
  device's clock without moving them.  Only an enabled trace ever fences;
* instrumented call-sites talk to the *active* trace through module-level
  ``count()`` / ``span()`` / ``counting()`` — when no enabled trace is
  active and no profiler runs these are a global load, an ``is None`` check
  and (``span()``) one ``TraceAnnotation.is_enabled()`` call (no
  allocation, measured by the disabled-mode test), so the engines carry
  their probes permanently at near-zero cost;
* ``jit_recompiles`` (and ``compile_seconds``, the cache hits/misses) come
  from ``jax.monitoring`` listeners on backend-compile and compile-cache
  events (installed once, forwarding to the active trace).

``RunTrace`` is also a ``Mapping`` so the legacy telemetry dict contract
(``res.telemetry["phases"]`` -> ``[{"name", "seconds"}, ...]``) keeps
working unchanged; see ``repro.obs`` for the user-facing tour.
"""
from __future__ import annotations

import collections
import contextlib
import os
import time
from collections.abc import Mapping
from typing import Any, Dict, List, Optional, Tuple

from jax.profiler import TraceAnnotation as _Annotation

# Counter glossary (see docs/architecture.md "Observability"):
#   distance_evals    point-to-center distance evaluations (n x centers folded)
#   bytes_swept       modeled HBM traffic of the field sweeps (fp32 model
#                     shared with benchmarks/bench_gmm.py)
#   host_syncs        blocking device->host transfers (each one stalls the
#                     dispatch pipeline — the pacing metric sprint mode
#                     collapses from O(k'/b) to O(#segments))
#   h2d_bytes         bytes of the host arrays the program puts on the
#                     device (stream chunks, the boot prefix, a host batch
#                     input); Python scalars passed as jit arguments are not
#                     counted
#   device_dispatches jitted computations launched by a host loop (the
#                     simulated MapReduce path launches one per reducer)
#   pool_widenings    adaptive-controller oversampling-pool doublings
#   sprint_segments   device-resident adaptive segments (one fused
#                     while_loop dispatch each; see core.adaptive sprint)
#   jit_recompiles    backend compiles observed while the trace was active
#                     (their seconds accumulate in RunTrace.compile_seconds)
#   compile_cache_hits / compile_cache_misses
#                     persistent compile-cache lookups that hit / missed
#   pallas_compiled   Pallas kernels staged for the device compiler (not the
#                     interpreter); counted at trace time, so a program
#                     served from the jit cache adds none
#   points_absorbed   stream points folded into the SMM state
#   merges            SMM merge/restructure events (threshold doublings)
#   retries           work units (reducers/chunks/rounds/steps) re-run after
#                     a failure under ResiliencePolicy(on_failure="retry")
#   failures_injected InjectedFailure events raised by a FailureInjector
#                     (chaos drills / fault-injection matrix)
#   checkpoints_written  CheckpointManager saves issued by a resilient run
#   reducers_recovered   reducers that failed then succeeded on a retry
#   sessions_active   rerank sessions opened in the serving SessionStore
#                     (monotone opens; the live gauge is ``store.active``)
#   rerank_batched    requests whose diverse slate came from a fused
#                     multi-tenant batched dispatch (serving layer)
#   coreset_reuses    rerank requests answered from a cached session slate
#                     because absorbing the request's candidates left the
#                     session core-set generation unchanged (no re-solve)
#   inserts_absorbed  points folded into the dynamic index's leveled cover
#                     (repro.dynamic, one per inserted row)
#   deletes_absorbed  points tombstoned out of the dynamic index (deletion
#                     repair reassigns/promotes their orphans)
#   level_rebuilds    dynamic-index levels (re)built from scratch (boot and
#                     every RebuildPolicy-triggered rebuild count each
#                     level they construct)
COUNTER_NAMES = ("distance_evals", "bytes_swept", "host_syncs", "h2d_bytes",
                 "device_dispatches", "pool_widenings", "sprint_segments",
                 "jit_recompiles", "compile_cache_hits",
                 "compile_cache_misses", "pallas_compiled",
                 "points_absorbed", "merges", "retries", "failures_injected", "checkpoints_written",
                 "reducers_recovered", "sessions_active", "rerank_batched",
                 "coreset_reuses", "inserts_absorbed", "deletes_absorbed",
                 "level_rebuilds")

ENV_VAR = "REPRO_TRACE"
PROFILE_PREFIX = "repro."     # every span's name in a profiler trace


def sweep_bytes(n: int, d: int, sweeps: int = 1, m: int = 1) -> int:
    """Modeled traffic of ``sweeps`` field sweeps: point slab (n*d fp32) read
    once plus m running-min fields read+written (+mask) per sweep — the same
    model ``benchmarks/bench_gmm.py`` reports as ``bytes_swept_gb``."""
    return sweeps * (n * d * 4 + 3 * m * n * 4)


def _block(x) -> None:
    """Fence: wait for every jax array in ``x`` (non-array leaves pass).  A
    device error surfacing here propagates."""
    if x is not None:
        import jax
        jax.block_until_ready(x)


class Span:
    """One timed region.  ``seconds`` is wall-clock between enter and exit,
    with the exit fenced on ``sync`` when one was given."""
    __slots__ = ("name", "t0", "t1", "attrs", "children")

    def __init__(self, name: str, t0: float, attrs: Optional[dict] = None):
        self.name = name
        self.t0 = t0
        self.t1 = t0
        self.attrs = attrs or {}
        self.children: List["Span"] = []

    @property
    def seconds(self) -> float:
        return self.t1 - self.t0

    def to_dict(self) -> dict:
        out = {"name": self.name, "seconds": self.seconds}
        if self.attrs:
            out["attrs"] = dict(self.attrs)
        if self.children:
            out["children"] = [c.to_dict() for c in self.children]
        return out


class _SpanCtx:
    """Context manager for one enabled span (profiler-annotated)."""
    __slots__ = ("_trace", "_span", "_sync", "_jax")

    def __init__(self, trace: "RunTrace", name: str, sync, attrs):
        self._trace = trace
        self._span = Span(name, 0.0, attrs)
        self._sync = sync
        self._jax = None

    def __enter__(self) -> Span:
        import jax
        stack = contextlib.ExitStack()
        stack.enter_context(_Annotation(PROFILE_PREFIX + self._span.name))
        stack.enter_context(jax.named_scope(self._span.name))
        self._jax = stack
        self._trace._push(self._span)
        self._span.t0 = time.perf_counter()
        return self._span

    def __exit__(self, *exc):
        _block(self._sync)
        self._span.t1 = time.perf_counter()
        if self._jax is not None:
            self._jax.close()
        self._trace._pop(self._span)
        return False


class _NullSpanCtx:
    """Shared no-op context manager for the disabled path."""
    __slots__ = ()

    def __enter__(self):
        return None

    def __exit__(self, *exc):
        return False


_NULL_SPAN = _NullSpanCtx()


class RunTrace(Mapping):
    """Spans + counters of one execution, with a legacy-compatible dict view.

    ``enabled=False`` (the default everywhere) records only the top-level
    phase rows — the fenced replacement of the old ``_Phases`` wall-clocks —
    and the extras the run paths annotate (``mode``, ``coreset_size``, ...).
    ``enabled=True`` additionally activates the counters, nested spans and
    profiler annotations; ``reducers=True`` asks the simulated MapReduce
    path to run its reducers sequentially so each gets a real span (an
    observability mode — slower, but the per-reducer wall-clocks feed
    ``distributed.fault_tolerance.StragglerPolicy``).

    As a ``Mapping`` it exposes exactly the keys the legacy telemetry dict
    had (``phases`` plus per-mode extras) plus ``counters`` when enabled,
    so ``res.telemetry["phases"]`` keeps working.
    """

    def __init__(self, enabled: bool = False, reducers: bool = False):
        self.enabled = bool(enabled) or bool(reducers)
        self.reducers = bool(reducers)
        self.phases: List[dict] = []
        # Counter: unread names are 0 without being stored, so exporters only
        # see the counters the run actually touched.
        self.counters: Dict[str, int] = collections.Counter()
        self.spans: List[Span] = []
        self.extras: Dict[str, Any] = {}
        self.compile_seconds = 0.0      # backend compile time while active
        self.t_start = time.perf_counter()
        self._stack: List[Span] = []

    # -- recording ---------------------------------------------------------
    def phase(self, name: str, t0: float, sync=None) -> float:
        """Close phase ``name`` opened at ``t0``: fence ``sync`` so the row
        measures execution (not async dispatch), record, return the fenced
        now (= the next phase's t0)."""
        _block(sync)
        t1 = time.perf_counter()
        self.phases.append({"name": name, "seconds": t1 - t0})
        if self.enabled:
            sp = Span(name, t0)
            sp.t1 = t1
            # adopt nested spans recorded during this phase as children
            root, keep = [], []
            for s in self.spans:
                (root if s.t0 >= t0 else keep).append(s)
            sp.children = root
            self.spans = keep + [sp]
        return t1

    def span(self, name: str, sync=None, **attrs):
        """Nested span context manager (no-op unless enabled)."""
        if not self.enabled:
            return _NULL_SPAN
        return _SpanCtx(self, name, sync, attrs or None)

    def count(self, name: str, n: int = 1) -> None:
        if self.enabled:
            self.counters[name] += n

    def annotate(self, **extras) -> "RunTrace":
        """Attach per-mode extras (``mode``, ``coreset_size``, ``n_seen``,
        ...) — the non-phase keys of the legacy telemetry dict."""
        self.extras.update(extras)
        return self

    def _push(self, span: Span) -> None:
        if self._stack:
            self._stack[-1].children.append(span)
        else:
            self.spans.append(span)
        self._stack.append(span)

    def _pop(self, span: Span) -> None:
        if self._stack and self._stack[-1] is span:
            self._stack.pop()

    # -- views -------------------------------------------------------------
    def as_dict(self) -> dict:
        """The legacy telemetry dict view (plus ``counters`` when enabled)."""
        out: Dict[str, Any] = {"phases": list(self.phases)}
        out.update(self.extras)
        if self.enabled:
            out["counters"] = dict(self.counters)
        return out

    def total_seconds(self) -> float:
        return sum(p["seconds"] for p in self.phases)

    # Mapping protocol — the backward-compatible telemetry dict.
    def __getitem__(self, key):
        return self.as_dict()[key]

    def __iter__(self):
        return iter(self.as_dict())

    def __len__(self):
        return len(self.as_dict())

    def __repr__(self):
        cs = ", ".join(f"{k}={v}" for k, v in sorted(self.counters.items()))
        ph = ", ".join(f"{p['name']}={p['seconds']:.3g}s" for p in self.phases)
        return (f"RunTrace(enabled={self.enabled}, phases=[{ph}]"
                + (f", counters=[{cs}]" if cs else "") + ")")


# --------------------------------------------------------------------------
# the active trace (module-global; the disabled fast path is one load+test)
# --------------------------------------------------------------------------

_ACTIVE: Optional[RunTrace] = None


def active() -> Optional[RunTrace]:
    """The trace instrumented call-sites report to (None = disabled)."""
    return _ACTIVE


def counting() -> bool:
    """True when an enabled trace is active — hot loops hoist this check."""
    t = _ACTIVE
    return t is not None and t.enabled


def count(name: str, n: int = 1) -> None:
    """Bump counter ``name`` on the active trace; no-op (and allocation-free)
    when tracing is disabled."""
    t = _ACTIVE
    if t is not None and t.enabled:
        t.counters[name] += n


def span(name: str, sync=None, **attrs):
    """Open a nested span on the active trace.  With tracing disabled it is
    an unfenced profiler annotation ``repro.<name>`` while a profiler
    session runs, else a no-op context manager."""
    t = _ACTIVE
    if t is not None and t.enabled:
        return _SpanCtx(t, name, sync, attrs or None)
    if _Annotation.is_enabled():
        return _Annotation(PROFILE_PREFIX + name, **attrs)
    return _NULL_SPAN


def reducer_detail() -> bool:
    """True when the active trace asked for per-reducer spans (the simulated
    MR paths then run reducers sequentially to time each one)."""
    t = _ACTIVE
    return t is not None and t.reducers


@contextlib.contextmanager
def activate(trace: Optional[RunTrace]):
    """Make ``trace`` the active trace for the enclosed block (re-entrant:
    the previous active trace is restored)."""
    global _ACTIVE
    prev = _ACTIVE
    if trace is not None and trace.enabled:
        _install_recompile_probe()
    _ACTIVE = trace
    try:
        yield trace
    finally:
        _ACTIVE = prev


def trace_from_spec(knob) -> RunTrace:
    """Resolve the ``ExecutionSpec(trace=...)`` knob (or the ``REPRO_TRACE``
    env var when ``"auto"``) into a ``RunTrace``.  Accepted values: ``False``
    / ``True`` / ``"auto"`` / ``"reducers"`` / an existing ``RunTrace`` (to
    aggregate several runs into one trace)."""
    if isinstance(knob, RunTrace):
        return knob
    if knob == "auto" or knob is None:
        env = os.environ.get(ENV_VAR, "").strip().lower()
        knob = ("reducers" if env == "reducers"
                else env in ("1", "true", "on", "yes"))
    if knob == "reducers":
        return RunTrace(enabled=True, reducers=True)
    return RunTrace(enabled=bool(knob))


# --------------------------------------------------------------------------
# compile probe (jax.monitoring event listeners, installed once)
# --------------------------------------------------------------------------

_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_CACHE_EVENTS = {"/jax/compilation_cache/cache_hits": "compile_cache_hits",
                 "/jax/compilation_cache/cache_misses": "compile_cache_misses"}
_PROBE_INSTALLED = False


def _on_compile_event(event, duration=None, **kw):   # pragma: no cover - cb
    if event != _COMPILE_EVENT:
        return
    t = _ACTIVE
    if t is not None and t.enabled:
        t.counters["jit_recompiles"] += 1
        t.compile_seconds += duration or 0.0


def _on_cache_event(event, **kw):                    # pragma: no cover - cb
    t = _ACTIVE
    if event in _CACHE_EVENTS and t is not None and t.enabled:
        t.counters[_CACHE_EVENTS[event]] += 1


def _install_recompile_probe() -> None:
    """Register the backend-compile and compile-cache listeners
    (idempotent)."""
    global _PROBE_INSTALLED
    if not _PROBE_INSTALLED:
        import jax.monitoring as jm
        jm.register_event_duration_secs_listener(_on_compile_event)
        jm.register_event_listener(_on_cache_event)
        _PROBE_INSTALLED = True
