"""The ``insert_steps_per_chunk`` reader: ``None`` whenever its input is
absent (a program without the counter included), the counter over the
request's chunks otherwise."""
import importlib.util
import os
import types

import pytest

METRIC = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "metrics", "insert_steps_per_chunk.py")


def _read(ctx):
    spec = importlib.util.spec_from_file_location(
        "bench_metric_insert_steps_per_chunk", METRIC)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read(ctx)


def _runtrace(**counters):
    return types.SimpleNamespace(counters=dict(counters))


@pytest.mark.parametrize("ctx,want", [
    ({}, None),
    ({"runtrace": _runtrace(insert_steps=256), "units": 0}, None),
    ({"runtrace": _runtrace(host_syncs=69, h2d_bytes=1), "units": 48}, None),
    ({"runtrace": _runtrace(insert_steps=256), "units": 48}, 256 / 48),
], ids=["no-trace", "no-units", "no-counter", "per-chunk"])
def test_insert_steps_per_chunk(ctx, want):
    assert _read(ctx) == want
