"""Faults planted under the timed path, and tiny cell sizes for the CPU.

Each fault is a context manager that breaks one step of the program where
it produces its result; the benchmark's check has to come out false."""
from __future__ import annotations

import contextlib

import numpy as np


def tiny_stream(c):
    """embed-stream at a size the CPU test run holds: 33 topics in a feed
    of 12 chunks of 256 rows, at the full width."""
    c["config"].update(k=8, kprime=32)
    c["traffic"].update(rows_per_chunk=256, feed_chunks=12, new_per_chunk=4,
                        ring_feeds=2, trace_seconds=1)
    return c


@contextlib.contextmanager
def patched(obj, name, new):
    old = getattr(obj, name)
    setattr(obj, name, new)
    try:
        yield
    finally:
        setattr(obj, name, old)


# -- streaming --------------------------------------------------------------

def stream_state_unchanged():
    """Every chunk after a feed's first leaves the state as it was."""
    from repro.core.smm import StreamingCoreset
    orig = StreamingCoreset._consume

    def consume(self, chunk, base=0):
        if not getattr(self, "_fault_seen", False):
            self._fault_seen = True
            orig(self, chunk, base)
    return patched(StreamingCoreset, "_consume", consume)


def stream_merge_skipped():
    """The merge step hands back the state it was given."""
    from repro.core.smm import StreamingCoreset
    return patched(StreamingCoreset, "_merge_until_room",
                   lambda self, state: state)


def stream_half_chunk():
    """Half of every chunk never reaches the engine's state."""
    from repro.core.smm import StreamingCoreset
    orig = StreamingCoreset.update

    def update(self, chunk):
        chunk = np.asarray(chunk)
        return orig(self, chunk[len(chunk) // 2:])
    return patched(StreamingCoreset, "update", update)


def value_altered():
    """The reported value is off by a part in a thousand."""
    import repro.api as api
    orig = api._value_of
    return patched(api, "_value_of",
                   lambda *a, **k: orig(*a, **k) * (1 + 1e-3))


def solution_first_k():
    """The solver returns the core-set's first k rows instead of its
    greedy selection."""
    import repro.core.sequential as seq
    return patched(seq, "solve_on_coreset",
                   lambda cs, k, measure, metric="euclidean":
                   cs.compact()[:k])


STREAM_FAULTS = {"state_unchanged": stream_state_unchanged,
                 "merge_skipped": stream_merge_skipped,
                 "half_chunk": stream_half_chunk,
                 "answer_altered": value_altered,
                 "solution_first_k": solution_first_k}
