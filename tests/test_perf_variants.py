"""Tests for the §Perf beyond-paper variants: batched/chunked GMM quality,
pad-heads attention equivalence, split local/global cache, int8-EF psum on a
real multi-device mesh (subprocess)."""
import dataclasses
import json
import subprocess
import sys
import textwrap

from conftest import SUBPROC_ENV as _SUBPROC_ENV

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core.gmm import gmm, gmm_batched


@pytest.mark.parametrize("b", [4, 8, 16])
def test_batched_gmm_quality(b):
    """Lookahead-b selection: distinct centers, anticover radius within 10%
    of exact (measured ~0.3–2.5% on these distributions)."""
    pts = np.random.default_rng(1).normal(size=(50_000, 8)).astype(np.float32)
    exact = gmm(pts, 64)
    idx, radius, _ = gmm_batched(pts, 64, b=b)
    assert len(set(np.asarray(idx).tolist())) == 64
    assert float(radius) <= 1.10 * float(exact.radius)


def test_chunked_batched_gmm_matches_unchunked_topb():
    """Chunk-local top-b + merge is an exact global top-b: the chunked path
    must select the same radius class as the unchunked batched path."""
    pts = np.random.default_rng(2).normal(size=(32_768, 8)).astype(np.float32)
    exact = gmm(pts, 32)
    _, r_unchunked, _ = gmm_batched(pts, 32, b=8)
    _, r_chunked, _ = gmm_batched(pts, 32, b=8, chunk=4096)
    assert float(r_chunked) <= 1.10 * float(exact.radius)
    assert float(r_unchunked) <= 1.10 * float(exact.radius)


@pytest.mark.slow   # model-zoo scaffolding, not the selection engine
def test_pad_heads_equivalence_all_affected_archs():
    """pad_heads must be numerically identical to the head_dim baseline
    (padding is activation-level; softmax over repeated KV is unchanged)."""
    import repro.models as M
    from repro.configs import get_config
    from repro.models.common import ShardingRules

    rules = ShardingRules(batch=(), heads=None, kv_heads=None, d_ff=None,
                          vocab=None, experts=None, fsdp=None, head_dim=None,
                          state=None, act_heads=None)
    rng = np.random.default_rng(3)
    for arch in ("internlm2-1.8b", "starcoder2-15b"):
        cfg0 = get_config(arch, reduced=True)
        pad_to = cfg0.num_heads * 2
        cfg1 = dataclasses.replace(cfg0, attn_shard="pad_heads",
                                   attn_pad_to=pad_to)
        params = M.init_params(cfg0, jax.random.PRNGKey(0))
        batch = {"tokens": jnp.asarray(rng.integers(0, cfg0.vocab_size,
                                                    (2, 16)), jnp.int32),
                 "labels": jnp.asarray(rng.integers(0, cfg0.vocab_size,
                                                    (2, 16)), jnp.int32)}
        l0 = float(M.loss_fn(params, cfg0, rules, batch))
        l1 = float(M.loss_fn(params, cfg1, rules, batch))
        assert abs(l0 - l1) < 2e-3, (arch, l0, l1)


_EF_SUBPROC = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import json
    import numpy as np
    import jax, jax.numpy as jnp
    from jax import shard_map
    from jax.sharding import PartitionSpec as P
    from repro.distributed import psum_bf16, psum_int8_ef, init_error_feedback

    mesh = jax.make_mesh((8,), ("data",))
    rng = np.random.default_rng(0)
    g = jnp.asarray(rng.normal(size=(8, 256)), jnp.float32)  # per-replica grads
    exact = np.asarray(g).mean(axis=0)

    def body_bf16(gl):
        return psum_bf16({"w": gl[0]}, "data")["w"]

    out16 = shard_map(body_bf16, mesh=mesh, in_specs=P("data"),
                      out_specs=P(), check_vma=False)(g)
    err16 = float(jnp.max(jnp.abs(out16 - exact)))

    def body_i8(gl, el):
        mean, new_e = psum_int8_ef({"w": gl[0]}, {"w": el[0]}, "data")
        return mean["w"], new_e["w"]

    e0 = jnp.zeros((8, 256), jnp.float32)
    out8, new_e = shard_map(body_i8, mesh=mesh,
                            in_specs=(P("data"), P("data")),
                            out_specs=(P(), P("data")),
                            check_vma=False)(g, e0)
    err8 = float(jnp.max(jnp.abs(out8 - exact)))
    resid = float(jnp.max(jnp.abs(new_e)))
    print(json.dumps({"err16": err16, "err8": err8, "resid": resid}))
""")


@pytest.mark.slow   # subprocess 8-device mesh
def test_compressed_psum_on_mesh():
    out = subprocess.run([sys.executable, "-c", _EF_SUBPROC],
                         capture_output=True, text=True, timeout=600,
                         env=_SUBPROC_ENV)
    assert out.returncode == 0, out.stderr[-2000:]
    data = json.loads(out.stdout.strip().splitlines()[-1])
    assert data["err16"] < 2e-2          # bf16 mean close to exact
    assert data["err8"] < 5e-2           # int8 mean close to exact
    assert 0 < data["resid"] < 0.1       # EF residual captured, bounded
