# The tests run on the CPU backend (JAX_PLATFORMS=cpu), with the Pallas
# kernels interpreted.  Deliberately NO XLA_FLAGS device-count override here:
# tests see the one CPU device; only launch/dryrun.py and the explicit
# subprocess tests fake 512/8/4 devices.  TPU compiles happen ahead of time
# against a described topology (tests/test_tpu_compile.py); the chip itself
# is exercised by chip_smoke.py.
import importlib.util
import os
import sys

import numpy as np
import pytest

# Environment shared by every subprocess test: strip to the essentials but
# pin the jax platform — without JAX_PLATFORMS the subprocess probes for a
# TPU, which stalls for minutes on CPU-only boxes.
SUBPROC_ENV = {"PYTHONPATH": "src", "PATH": "/usr/bin:/bin",
               "JAX_PLATFORMS": os.environ.get("JAX_PLATFORMS", "cpu")}

# The runtime image has no ``hypothesis``; install a deterministic fallback
# (same given/settings/strategies surface) so the property tests still run
# instead of failing at collection.  The real package ALWAYS wins when
# importable — the shim only fills a missing dependency, it never shadows.
# ``REPRO_NO_HYPOTHESIS_FALLBACK=1`` turns the silent shim into a hard error
# (CI images that are supposed to bake the real package in set it so a
# regressed image fails loudly).  Documented in README "Development"; drop
# the whole block once the runtime image bakes ``hypothesis`` in.
if importlib.util.find_spec("hypothesis") is None:
    if os.environ.get("REPRO_NO_HYPOTHESIS_FALLBACK") == "1":
        raise ImportError(
            "hypothesis is not installed and REPRO_NO_HYPOTHESIS_FALLBACK=1 "
            "forbids the deterministic fallback shim "
            "(tests/_hypothesis_fallback.py); pip install hypothesis")
    # import by path: ``tests`` is not a package, and the repo root is only
    # on sys.path under ``python -m pytest``, not the bare ``pytest`` entry
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    import _hypothesis_fallback as _hf

    _mod = type(sys)("hypothesis")
    _mod.given = _hf.given
    _mod.settings = _hf.settings
    _mod.assume = _hf.assume
    _mod.strategies = _hf
    _mod.__repro_fallback__ = True   # lets tests detect shim vs real package
    sys.modules["hypothesis"] = _mod
    sys.modules["hypothesis.strategies"] = _hf


@pytest.fixture(scope="session")
def rng():
    return np.random.default_rng(0)
