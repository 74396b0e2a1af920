"""Shared benchmark utilities."""
from __future__ import annotations

import time
from typing import Callable, Dict, List

import numpy as np


def timed(fn: Callable, *args, repeats: int = 1, **kw):
    best = np.inf
    out = None
    for _ in range(repeats):
        t0 = time.perf_counter()
        out = fn(*args, **kw)
        best = min(best, time.perf_counter() - t0)
    return out, best


def table(rows: List[Dict], columns: List[str], title: str = "") -> str:
    lines = []
    if title:
        lines.append(f"\n### {title}")
    lines.append("| " + " | ".join(columns) + " |")
    lines.append("|" + "|".join(["---"] * len(columns)) + "|")
    for r in rows:
        lines.append("| " + " | ".join(str(r.get(c, "")) for c in columns)
                     + " |")
    return "\n".join(lines)


def fmt(x, nd=4):
    if isinstance(x, float):
        return f"{x:.{nd}f}"
    return str(x)


#: counters the bench artifacts carry per row (benchmarks/compare.py gates
#: host_syncs / bytes_swept at +10%, and sprint rows' host_syncs exactly);
#: see repro.obs COUNTER_NAMES.
COUNTER_KEYS = ("distance_evals", "bytes_swept", "host_syncs",
                "device_dispatches", "sprint_segments")


def counters_of(fn: Callable, keys=COUNTER_KEYS) -> Dict[str, int]:
    """Run ``fn`` once under an enabled ``RunTrace`` and return its work
    counters — the untraced timing passes stay untraced, so the counters
    ride in the artifact without perturbing the wall-clock rows."""
    import jax
    from repro.obs.trace import RunTrace, activate

    tr = RunTrace(enabled=True)
    with activate(tr):
        jax.block_until_ready(fn())
    return {k: int(tr.counters[k]) for k in keys}
