"""GPipe-style pipeline parallelism over a mesh axis (DESIGN.md §4).

``pipeline_apply`` runs a stack of per-stage functions over the ``stage``
mesh axis with microbatched 1F schedule: each device holds its stage's
params; activations flow stage-to-stage via ``jax.lax.ppermute``.  The
classic (num_stages + num_micro − 1)-slot schedule is expressed as a scan
over slots inside shard_map — deterministic, jit-compatible, and the
boundary transfers show up as collective-permutes in the dry-run roofline.

This is the pod-axis pipelining option for the multi-pod mesh (stage axis =
"pod", 2 stages); tests/test_pipeline.py proves numerical equivalence with
the unpipelined stack on an 8-device subprocess mesh.
"""
from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P


def pipeline_apply(stage_fn: Callable, stage_params, x, mesh: Mesh,
                   axis: str = "pod", num_micro: int = 4):
    """Run ``y = stage_{S-1}(...stage_0(x))`` pipelined over ``axis``.

    stage_fn(params_slice, xb) -> yb — one stage's computation on one
    microbatch (all stages share this callable; per-stage behaviour comes
    from ``stage_params``, whose leaves carry a leading stage dim sharded
    over ``axis``).

    x: (B, ...) with B % num_micro == 0; returns same shape.
    """
    S = mesh.shape[axis]
    B = x.shape[0]
    assert B % num_micro == 0, (B, num_micro)
    mb = B // num_micro
    n_slots = num_micro + S - 1

    def body(params, xx):
        # each device holds its stage's slice: (1, ...) -> (...)
        params = jax.tree.map(lambda p: p[0], params)
        sid = jax.lax.axis_index(axis)
        micro = xx.reshape((num_micro, mb) + xx.shape[1:])
        out = jnp.zeros_like(micro)
        # carry: the activation entering this stage for the current slot
        carry = jnp.zeros((mb,) + xx.shape[1:], xx.dtype)

        def slot(state, t):
            carry, out = state
            # stage 0 ingests microbatch t (when in range)
            feed = micro[jnp.clip(t, 0, num_micro - 1)]
            xin = jnp.where(sid == 0, feed, carry)
            active = (t - sid >= 0) & (t - sid < num_micro)
            y = stage_fn(params, xin)
            y = jnp.where(active, y, carry)
            # last stage banks its finished microbatch
            done_idx = jnp.clip(t - (S - 1), 0, num_micro - 1)
            bank = (sid == S - 1) & (t - (S - 1) >= 0)
            out = jnp.where(bank, out.at[done_idx].set(y), out)
            # ring-shift activations to the next stage
            carry = jax.lax.ppermute(
                y, axis, perm=[(i, (i + 1) % S) for i in range(S)])
            return (carry, out), None

        (carry, out), _ = jax.lax.scan(slot, (carry, out),
                                       jnp.arange(n_slots))
        # only the last stage holds real outputs; broadcast them
        out = jax.lax.psum(
            jnp.where(sid == S - 1, out, jnp.zeros_like(out)), axis)
        return out.reshape(xx.shape)

    fn = shard_map(body, mesh=mesh,
                   in_specs=(P(axis), P()), out_specs=P(),
                   check_vma=False)
    return fn(stage_params, x)
