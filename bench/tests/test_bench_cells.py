"""The cell's request path at a tiny size on the CPU: a sound run passes
the check, and each planted fault fails it."""
import pytest

from bench import run
from bench.loops import stream
from bench.tests import faults

SEED = 2 ** 33 + 12345          # wider than 32 bits, as real seeds may be


def _stream(engine=None, fault=None, trace=False):
    import jax

    c = faults.tiny_stream(run.load_cell("embed-stream"))
    devices = jax.devices()[:1]
    if fault is None:
        return run.run_cell(c, SEED, 0.5, trace, devices, engine=engine)
    with fault():
        return run.run_cell(c, SEED, 0.5, trace, devices)


def test_stream_sound_run_is_correct():
    out = _stream()
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert set(out["metrics"]) == {"points_per_s", "update_p95_ms",
                                   "peak_hbm_gb", "setup_s"}
    assert list(out)[-1] == "checks"
    assert out["checks"]["seen_gap"]["value"] == 0
    assert out["checks"]["centres_differ"]["value"] == 0


@pytest.mark.parametrize("fault", sorted(faults.STREAM_FAULTS))
def test_stream_fault_is_caught(fault):
    out = _stream(fault=faults.STREAM_FAULTS[fault])
    assert not out["correct"], out["checks"]


def test_stream_reference_engine_matches_itself():
    """The plain reference at HIGHEST in the program's place passes: the
    check does not fail an engine for being the reference."""
    out = _stream(engine=stream.reference_engine("highest"))
    assert out["correct"], out["checks"]


def test_stream_high_reference_passes():
    """At HIGH, the step below the stated precision, the reference still
    passes: here it rounds no worse than f32 does, so it cannot be the
    control."""
    out = _stream(engine=stream.reference_engine("high"))
    assert out["correct"], out["checks"]


def test_stream_control_is_caught():
    """The control, the reference at one bf16 pass a distance, fails on
    the certified radius."""
    out = _stream(engine=stream.reference_engine("default"))
    assert not out["correct"], out["checks"]
    assert out["checks"]["radius_err"]["value"] > \
        out["checks"]["radius_err"]["limit"]
