"""The comparison that decides ``correct``.

Each ``check_*`` returns {name: number}; ``judge`` holds every number to
its limit from ``limits/<cell>.json``.  The numbers:

* ``replay_differs`` chunks that the check's replay of a feed makes other
                     than the window was handed (stream; exact);
* ``seen_gap``       points the engine says it saw, less the points fed
                     (stream; exact);
* ``centres_differ`` rows in one core-set and not the other: the engine's
                     against the plain streaming reference's, in float64
                     over every point fed (stream; exact);
* ``value_err``      relative gap of the reported value to the float64
                     value of the returned solution;
* ``radius_err``     relative gap of the certified radius to the
                     reference stream's ``4d``;
* ``quality_gap``    how far the float64 value of the returned solution
                     falls short of the reference solver's value on the
                     reference core-set (negative: it is better);
* ``coverage``       largest distance from a point fed to the returned
                     core-set, over the certified radius: the certificate's
                     guarantee.

Each number is the worst over every answer of the window.
"""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from bench.ref import reference as ref


def judge(numbers: dict, limits: dict):
    """(correct, {name: {"value", "limit"}}): each number must not exceed
    its limit; a missing or non-finite number fails."""
    out, ok = {}, True
    for name, limit in limits.items():
        v = numbers.get(name)
        good = v is not None and np.isfinite(v) and v <= limit
        ok &= bool(good)
        out[name] = {"value": None if v is None else float(v),
                     "limit": limit}
    return ok, out


def _rel(a, b):
    return abs(float(a) - float(b)) / abs(float(b))


def _value64(sol, metric):
    return ref.remote_edge(ref.ref_pairwise(sol, sol, metric))


def _rows_differ(a, b) -> int:
    sa = {r.tobytes() for r in np.asarray(a, np.float32)}
    sb = {r.tobytes() for r in np.asarray(b, np.float32)}
    return len(sa ^ sb)


# --------------------------------------------------------------------------
# stream
# --------------------------------------------------------------------------

def check_stream(drv) -> dict:
    """Every answer of the window against the plain reference stream, in
    float64, over its feed made again from the seed; and the coverage of
    each distinct returned core-set over every point of its feed."""
    cfg, metric, k = drv.cfg, drv.cfg["metric"], drv.cfg["k"]
    out = dict.fromkeys(("replay_differs", "seen_gap", "centres_differ",
                         "value_err", "radius_err", "coverage"), 0.0)
    out["quality_gap"] = -np.inf
    for f, fed in enumerate(drv.ring):
        mine = [a for g, a in drv.answers if g == f]
        if not mine:
            continue
        cores = {a.coreset.tobytes(): jnp.asarray(a.coreset) for a in mine}
        far = dict.fromkeys(cores, 0.0)
        sm = ref.RefStream(k, cfg["kprime"], metric, "f64")
        for j, x in enumerate(drv.feed(f)):
            h = np.asarray(x)
            out["replay_differs"] += not np.array_equal(h, fed[j])
            sm.update(h)
            for key, cs in cores.items():
                far[key] = max(far[key],
                               float(jnp.max(ref._near(x, cs, metric))))
        cen, radius = sm.finalize()
        dm = ref.ref_pairwise(cen, cen, metric)
        best = ref.remote_edge(dm[np.ix_(*[ref.greedy(dm, k)] * 2)])
        for a in mine:
            sol64 = _value64(a.solution, metric)
            for name, v in (
                    ("seen_gap", abs(a.n_seen - sm.n_seen)),
                    ("centres_differ", _rows_differ(a.coreset, cen)),
                    ("value_err", _rel(a.value, sol64)),
                    ("radius_err", _rel(a.radius, radius)),
                    ("quality_gap", (best - sol64) / best),
                    ("coverage", far[a.coreset.tobytes()] / a.radius)):
                out[name] = max(out[name], v)
    return out
