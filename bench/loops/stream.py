"""Closed-loop feed load: each request is one
``repro.diversify(mode="streaming")`` call over a whole feed of chunks, and
the next starts as soon as it returns.

Set-up makes ``ring_feeds`` feeds on the device from (seed, feed), pulls
them to the host and keeps them there: the engine is handed host arrays,
as a stream source gives them, and nothing else uses the device during the
window.  Request ``i`` replays feed ``i % ring_feeds``.  Set-up also runs
``warmup_feeds`` whole requests, so every program the window runs is
compiled before it starts: every feed has the same chunk shapes and the
same arrivals, so the same programs.  The window ends with the first
request to return after ``seconds``.
"""
from __future__ import annotations

import dataclasses
import time

import jax
import numpy as np

from bench import check
from bench.ref import data


@dataclasses.dataclass
class Answer:
    """What the timed path produced, read back to the host."""
    solution: np.ndarray
    value: float
    radius: float
    coreset: np.ndarray
    n_seen: int


def program_engine(chunks, cfg, trace=False):
    import repro

    res = repro.diversify(
        repro.ProblemSpec(points=chunks, k=cfg["k"], measure=cfg["measure"],
                          metric=cfg["metric"], dim=cfg["data"]["dim"]),
        repro.ExecutionSpec(mode="streaming", kprime=cfg["kprime"],
                            trace=trace))
    with jax.profiler.TraceAnnotation("bench.readback"):
        return Answer(solution=np.asarray(res.solution),
                      value=float(res.value),
                      radius=float(res.cert.radius),
                      coreset=np.asarray(res.coreset.compact()),
                      n_seen=int(res.telemetry["n_seen"]))


def reference_engine(precision):
    """The plain reference in the program's place (the control)."""
    def run(chunks, cfg, trace=False):
        from bench.ref import reference as ref

        smm = ref.RefStream(cfg["k"], cfg["kprime"], cfg["metric"],
                            precision)
        for c in chunks:
            smm.update(c)
        cen, radius = smm.finalize()
        dm = smm.dist(cen, cen)
        sol = cen[ref.greedy(dm, cfg["k"])]
        return Answer(solution=sol.astype(np.float32),
                      value=ref.remote_edge(smm.dist(sol, sol)),
                      radius=radius, coreset=cen.astype(np.float32),
                      n_seen=smm.n_seen)
    return run


class Loop:
    def __init__(self, cfg, traffic, seed, devices, engine=None):
        self.cfg, self.traffic, self.seed = cfg, traffic, seed
        self.engine = engine or program_engine
        self.rows = traffic["rows_per_chunk"]
        self.chunks = traffic["feed_chunks"]
        m, kp = traffic["new_per_chunk"], cfg["kprime"]
        if kp % m or kp // m >= self.chunks:
            raise ValueError("new_per_chunk must divide k' into fewer "
                             "chunks than a feed has")
        # the arrival that fills the k'+1 slots is a chunk's last row, so a
        # merge never leaves a tail of a new length to compile
        self.shape = dict(rows=self.rows, new_per_chunk=m,
                          insert_chunks=kp // m, skip=kp + 1,
                          topic_spread=cfg["data"]["topic_spread"])
        self.ring = []
        self.answers = []

    def feed(self, f):
        """Feed ``f``'s chunks, on the device, one at a time."""
        dc = self.cfg["data"]
        key = data.key_of(self.seed, f)
        centres = data.topic_centres(
            key, 1 + self.cfg["kprime"], dc["dim"], dc["per_domain"],
            dc["sibling_spread"])
        for j in range(self.chunks):
            yield data.feed_chunk(key, centres, j, **self.shape)

    def _request(self, f, lat):
        """One streaming call over feed ``f``.  ``lat`` gets, per chunk, the
        seconds from hand-over until the engine asks for the next one."""
        def chunks():
            for arr in self.ring[f]:
                t0 = time.perf_counter()
                ann = jax.profiler.TraceAnnotation("bench.engine")
                ann.__enter__()
                try:
                    yield arr
                finally:
                    ann.__exit__(None, None, None)
                lat.append(time.perf_counter() - t0)

        with jax.profiler.TraceAnnotation("bench.request"):
            return self.engine(chunks(), self.cfg)

    def setup(self):
        self.ring = [[np.asarray(c) for c in self.feed(f)]
                     for f in range(self.traffic["ring_feeds"])]
        for i in range(self.traffic["warmup_feeds"]):
            self._request(i % len(self.ring), [])

    def window(self, seconds):
        lat, answers, req = [], [], []
        t0 = time.perf_counter()
        while not answers or time.perf_counter() - t0 < seconds:
            f = len(answers) % len(self.ring)
            r0 = time.perf_counter()
            answers.append((f, self._request(f, lat)))
            req.append(time.perf_counter() - r0)
        elapsed = time.perf_counter() - t0
        self.answers = answers
        n = len(answers)
        lat = np.asarray(lat) * 1e3
        return {"attempted": n, "failed": 0,
                "e2e": {"points_per_s": n * self.chunks * self.rows
                        / elapsed,
                        "update_p95_ms": float(np.percentile(lat, 95))},
                "notes": {"feeds": n, "chunks": int(lat.size),
                          "elapsed_s": elapsed,
                          "update_p50_ms": float(np.percentile(lat, 50)),
                          "update_max_ms": float(lat.max()),
                          "request_p50_ms": float(np.median(req) * 1e3),
                          "request_max_ms": float(np.max(req) * 1e3)}}

    def traced_request(self):
        """One more feed under an enabled RunTrace."""
        from repro.obs.trace import RunTrace

        tr = RunTrace(enabled=True)
        self.engine(iter(self.ring[0]), self.cfg, tr)
        return {"runtrace": tr, "units": self.chunks}

    def check(self):
        return check.check_stream(self)
