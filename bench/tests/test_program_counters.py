"""Per-layer readers of the program's own ``RunTrace`` counters: ``None``
whenever their input is absent (a program without the counter included),
the counter over the request's units otherwise."""
import importlib.util
import os
import types

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
METRICS = os.path.join(os.path.dirname(HERE), "metrics")


def _reader(name):
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name, os.path.join(METRICS, name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _runtrace(**counters):
    return types.SimpleNamespace(counters=dict(counters))


@pytest.mark.parametrize("ctx,want", [
    ({}, None),
    ({"runtrace": _runtrace(h2d_bytes=100), "units": 0}, None),
    ({"runtrace": _runtrace(host_syncs=3), "units": 2}, None),
    ({"runtrace": _runtrace(h2d_bytes=48 * 12582912), "units": 48},
     12582912.0),
], ids=["no-trace", "no-units", "no-counter", "per-chunk"])
def test_h2d_bytes_per_chunk(ctx, want):
    assert _reader("h2d_bytes_per_chunk").read(ctx) == want
