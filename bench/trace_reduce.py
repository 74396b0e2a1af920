"""Profiler trace -> device busy and idle time, top device ops and idle gaps.

``load`` reads the ``.xplane.pb`` that ``jax.profiler`` writes into a plain
dict of events; ``reduce`` works on that dict alone, so the reduction is
tested on a small recorded trace (``tests/data/trace_small.json``).

* Device ops are the events of the ``XLA Ops`` line of each device plane
  (``/device:TPU:0``, ...).  A device is busy where at least one of its ops
  runs: the union of their intervals, clipped to the window.
* The window is the harness's ``bench.window`` annotation on the host.
* Each idle gap is labelled by the innermost other ``bench.*`` annotation
  that covers its midpoint (``other`` where none does).
"""
from __future__ import annotations

import bisect
import collections
import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:[A-Z]+:\d+$")
OPS_LINE = "XLA Ops"
PREFIX = "bench."
WINDOW = "bench.window"


def find_xplane(logdir: str) -> str:
    paths = sorted(glob.glob(os.path.join(logdir, "plugins", "profile", "*",
                                          "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {logdir}")
    return paths[-1]


def _op_name(text: str) -> str:
    """``%fusion.7 = f32[...] fusion(...)`` -> ``fusion.7``."""
    return text.split(" = ", 1)[0].lstrip("%")


def load(path: str) -> dict:
    """{"devices": {plane: [[name, start_ns, dur_ns], ...]},
        "host": [[name, start_ns, dur_ns], ...]} -- host events are the
    ``bench.*`` annotations only."""
    from jax.profiler import ProfileData

    devices, host = {}, []
    for plane in ProfileData.from_file(path).planes:
        if DEVICE_PLANE.match(plane.name):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    devices[plane.name] = [
                        [_op_name(e.name), float(e.start_ns),
                         float(e.duration_ns)] for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend([e.name, float(e.start_ns), float(e.duration_ns)]
                            for e in line.events
                            if e.name.startswith(PREFIX))
    return {"devices": devices, "host": host}


def _union(intervals, lo, hi):
    """Merged, clipped [start, end) intervals."""
    out = []
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


class _Labeller:
    """Innermost ``bench.*`` annotation (other than the window) covering a
    time: one sorted list of starts per annotation name."""

    def __init__(self, host):
        by = collections.defaultdict(list)
        for name, s, d in host:
            if name != WINDOW:
                by[name].append((s, d))
        self.by = {k: sorted(v) for k, v in by.items()}
        self.starts = {k: [s for s, _ in v] for k, v in self.by.items()}

    def __call__(self, t):
        best = None
        for name, evs in self.by.items():
            i = bisect.bisect_right(self.starts[name], t) - 1
            if i >= 0 and t <= evs[i][0] + evs[i][1] and (
                    best is None or evs[i][1] < best[1]):
                best = (name, evs[i][1])
        return best[0] if best else "other"


def reduce(trace: dict, top: int = 10) -> dict:
    """Busy seconds per device and their mean, the window, the idle share
    (percent, mean over devices), the ``top`` device ops by seconds per
    device, and idle seconds per device by label."""
    devices = {k: v for k, v in trace["devices"].items() if v}
    if not devices:
        raise ValueError("the trace holds no device op")
    win = [h for h in trace["host"] if h[0] == WINDOW]
    if win:
        lo, hi = win[-1][1], win[-1][1] + win[-1][2]
    else:
        evs = [e for v in devices.values() for e in v]
        lo = min(e[1] for e in evs)
        hi = max(e[1] + e[2] for e in evs)
    n = len(devices)
    label = _Labeller(trace["host"])
    busy, ops, gaps = {}, collections.Counter(), collections.Counter()
    for plane, evs in devices.items():
        merged = _union(((s, s + d) for _, s, d in evs), lo, hi)
        busy[plane] = sum(e - s for s, e in merged) / 1e9
        for name, s, d in evs:
            ops[name] += max(0.0, min(s + d, hi) - max(s, lo)) / 1e9 / n
        edges = [lo] + [x for iv in merged for x in iv] + [hi]
        for s, e in zip(edges[::2], edges[1::2]):
            if e > s:
                gaps[label((s + e) / 2)] += (e - s) / 1e9 / n
    window_s = (hi - lo) / 1e9
    busy_s = sum(busy.values()) / n
    return {"window_s": window_s, "busy_s": busy_s, "busy_per_device": busy,
            "idle_pct": 100.0 * (1.0 - busy_s / window_s),
            "idle_pct_per_device": {k: 100.0 * (1.0 - b / window_s)
                                    for k, b in busy.items()},
            "device_ops": [[k, v] for k, v in ops.most_common(top)],
            "idle_gaps": [[k, v] for k, v in gaps.most_common(top)]}
