"""``chip_smoke.py`` at tiny sizes on the CPU backend (Pallas kernels
interpreted): every phase runs through ``repro.diversify()`` and passes its
reference checks, and ``main()`` refuses to run without a TPU."""
import importlib.util
import json
import os
import subprocess
import sys

import pytest

from conftest import SUBPROC_ENV

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TINY = dict(sphere_n=1 << 13, catalog_n=1 << 11, catalog_d=32,
            grouped_n=1 << 13, stream_chunks=4, stream_chunk=1 << 11,
            mesh_n=1 << 13)


def _smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(ROOT, "chip_smoke.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_main_refuses_cpu(capsys):
    assert _smoke().main([]) != 0
    out = capsys.readouterr()
    assert '"ok"' not in out.out
    assert "needs a TPU" in out.err


@pytest.mark.parametrize("phase", ["sphere", "catalog", "constrained",
                                   "streaming", "mapreduce"])
def test_phase_passes_its_checks(phase):
    cs = _smoke()
    rep = cs.Report()
    cs.run(rep, 0, TINY, phases=(phase,))
    checks = {(ph, name): ok for ph, name, ok in rep.checks}
    assert checks and ("ran" not in {name for _, name in checks})
    # on the CPU the kernels are interpreted, and the smoke must say so
    compiled = checks.pop(("catalog", "pallas_compiled"), None)
    assert compiled is (False if phase == "catalog" else None)
    failed = [k for k, ok in checks.items() if not ok]
    assert not failed, failed
    if phase == "catalog":
        assert checks[("catalog", "pallas_vs_jnp")]


@pytest.mark.parametrize("env_dir", [None, "from-env"])
def test_compile_cache_location(monkeypatch, tmp_path, env_dir):
    """The entry points' cache: ``$JAX_COMPILATION_CACHE_DIR`` untouched
    when set, else the fixed ``<checkout>/.jax_cache``."""
    import jax

    from repro.compile_cache import ENV_VAR, enable_compile_cache

    prev = jax.config.jax_compilation_cache_dir
    if env_dir is None:
        monkeypatch.delenv(ENV_VAR, raising=False)
    else:
        monkeypatch.setenv(ENV_VAR, str(tmp_path / env_dir))
    try:
        got = enable_compile_cache(str(tmp_path))
        if env_dir is None:
            assert got == str(tmp_path / ".jax_cache")
            assert jax.config.jax_compilation_cache_dir == got
        else:
            assert got == str(tmp_path / env_dir)
            assert jax.config.jax_compilation_cache_dir == prev
    finally:
        jax.config.update("jax_compilation_cache_dir", prev)


def test_mesh4_on_four_virtual_devices():
    """The --chips 4 path (shard_map over a 4-device mesh vs 4 simulated
    reducers) on four virtual CPU devices."""
    code = (
        "import json, sys; sys.path.insert(0, %r)\n"
        "import jax, chip_smoke as cs\n"
        "rep = cs.Report()\n"
        "cs.run(rep, 0, %r, devices=jax.devices())\n"
        "print(json.dumps(rep.checks))\n" % (ROOT, TINY))
    env = dict(SUBPROC_ENV,
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    out = subprocess.run([sys.executable, "-c", code], env=env, cwd=ROOT,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    checks = json.loads(out.stdout.strip().splitlines()[-1])
    names = {name for _, name, _ in checks}
    assert {"input_sharded", "mesh_vs_sim", "value", "radius"} <= names
    assert all(ok for _, _, ok in checks), checks
