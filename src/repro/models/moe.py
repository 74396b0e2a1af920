"""Token-choice top-k MoE with capacity-based dispatch (expert parallelism).

Sort-based dropped-token dispatch (the MaxText/GShard shape): route each of
the N·topk (token, expert) assignments to a per-expert buffer of capacity
C = ceil(cf · N · topk / E); assignments whose within-expert rank exceeds C
are dropped (standard capacity dropping).  The expert matmuls are batched
einsums over the expert axis, which is sharded over the ``model`` mesh axis —
GSPMD materializes the token shuffle as all-to-alls, which the roofline's
collective term accounts for.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .common import ModelConfig, ShardingRules, shard, _act


def moe_mlp(x, router_w, w_gate, w_up, w_down, cfg: ModelConfig,
            rules: ShardingRules):
    """Entry point: explicit shard_map dispatch when a mesh is active
    (EXPERIMENTS.md §Perf hillclimb #2 — the GSPMD-inferred scatter
    replicates the (E, C, D) buffer on every device; the shard_map version
    keeps tokens in their data shard and experts in their model shard, with
    one psum for the combine), else the single-device GSPMD path."""
    from .common import current_mesh
    mesh = current_mesh()
    if mesh is not None and "model" in getattr(mesh, "axis_names", ()):
        return _moe_mlp_shard_map(x, router_w, w_gate, w_up, w_down, cfg,
                                  rules, mesh)
    return _moe_mlp_gspmd(x, router_w, w_gate, w_up, w_down, cfg, rules)


def _dispatch_local(xf, logits, E_range, cfg: ModelConfig):
    """Capacity-dispatch the local tokens to the experts in ``E_range``.

    Returns (buf (E_loc, C, D), combine metadata).  Pure function of local
    data — used by both the shard_map body (E_range = this rank's experts)
    and the single-device path (E_range = all experts)."""
    N, D = xf.shape
    E, topk = cfg.num_experts, cfg.num_experts_per_tok
    e0, e_loc = E_range
    top_v, top_i = jax.lax.top_k(logits, topk)
    gates = jax.nn.softmax(top_v, axis=-1).astype(xf.dtype)

    C = max(int(cfg.capacity_factor * N * topk / E), min(N, 4) * topk)
    Nk = N * topk
    flat_e = top_i.reshape(Nk) - e0                 # local expert ids
    local = (flat_e >= 0) & (flat_e < e_loc)
    key = jnp.where(local, flat_e, e_loc) * Nk + jnp.arange(Nk)
    order = jnp.argsort(key)
    sorted_e = jnp.where(local, flat_e, e_loc)[order]
    starts = jnp.searchsorted(sorted_e, jnp.arange(e_loc))
    rank_sorted = jnp.arange(Nk) - starts[jnp.clip(sorted_e, 0, e_loc - 1)]
    rank = jnp.zeros((Nk,), jnp.int32).at[order].set(
        rank_sorted.astype(jnp.int32))
    keep = local & (rank < C)
    dest_e = jnp.where(keep, flat_e, e_loc)
    dest_c = jnp.where(keep, rank, C)
    x_rep = jnp.repeat(xf, topk, axis=0)
    # unique_indices: each kept assignment owns its (e, c) slot by
    # construction — lets XLA lower the scatter natively instead of a
    # one-hot matmul (§Perf hillclimb #2, iteration 5)
    buf = jnp.zeros((e_loc, C, D), xf.dtype).at[dest_e, dest_c].set(
        x_rep, mode="drop", unique_indices=True)
    return buf, (keep, dest_e, dest_c, gates, C)


def _combine_local(y, meta, N, topk, D):
    keep, dest_e, dest_c, gates, C = meta
    e_loc = y.shape[0]
    y_tok = y.at[dest_e, dest_c].get(mode="fill", fill_value=0,
                                     unique_indices=True)
    y_tok = jnp.where(keep[:, None], y_tok, 0.0)
    return jnp.sum(y_tok.reshape(N, topk, D) * gates[..., None], axis=1)


def _expert_ffn(buf, w_gate, w_up, w_down, cfg: ModelConfig):
    act = _act(cfg.mlp_act)
    h = act(jnp.einsum("ecd,edf->ecf", buf, w_gate)) * jnp.einsum(
        "ecd,edf->ecf", buf, w_up)
    return jnp.einsum("ecf,efd->ecd", h, w_down)


def _moe_mlp_shard_map(x, router_w, w_gate, w_up, w_down, cfg: ModelConfig,
                       rules: ShardingRules, mesh):
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    B, S, D = x.shape
    E, topk = cfg.num_experts, cfg.num_experts_per_tok
    bt = rules.resolve("batch")
    tp = E and "model"
    n_model = mesh.shape["model"]
    e_loc = E // n_model

    def body(xl, rw, wg, wu, wd):
        # xl (B_loc, S, D) — replicated over 'model'; w* (e_loc, D, F)
        Bl = xl.shape[0]
        N = Bl * S
        xf = xl.reshape(N, D)
        logits = xf.astype(jnp.float32) @ rw.astype(jnp.float32)  # (N, E)
        e0 = jax.lax.axis_index("model") * e_loc
        buf, meta = _dispatch_local(xf, logits, (e0, e_loc), cfg)
        y = _expert_ffn(buf, wg, wu, wd, cfg)
        out = _combine_local(y, meta, N, topk, D)
        out = jax.lax.psum(out, "model")              # combine across experts
        return out.reshape(Bl, S, D)

    fn = shard_map(body, mesh=mesh,
                   in_specs=(P(bt, None, None), P(None, None),
                             P("model", None, None), P("model", None, None),
                             P("model", None, None)),
                   out_specs=P(bt, None, None), check_vma=False)
    return fn(x, router_w, w_gate, w_up, w_down)


def _moe_mlp_gspmd(x, router_w, w_gate, w_up, w_down, cfg: ModelConfig,
                   rules: ShardingRules):
    """x (B, S, D) -> (B, S, D)."""
    B, S, D = x.shape
    N = B * S
    E, topk = cfg.num_experts, cfg.num_experts_per_tok
    xf = x.reshape(N, D)

    logits = (xf.astype(jnp.float32) @ router_w.astype(jnp.float32))  # (N, E)
    top_v, top_i = jax.lax.top_k(logits, topk)
    gates = jax.nn.softmax(top_v, axis=-1).astype(x.dtype)            # (N, topk)

    # capacity: cf-scaled expected load; floored at min(N, 4)·topk so that
    # tiny-N (decode) batches never drop assignments — decode must reproduce
    # teacher-forced logits exactly (tests/test_models.py)
    C = max(int(cfg.capacity_factor * N * topk / E), min(N, 4) * topk)
    Nk = N * topk
    flat_e = top_i.reshape(Nk)
    # within-expert rank in (token, slot) order
    order = jnp.argsort(flat_e * Nk + jnp.arange(Nk), stable=True)
    sorted_e = flat_e[order]
    starts = jnp.searchsorted(sorted_e, jnp.arange(E))
    rank_sorted = jnp.arange(Nk) - starts[sorted_e]
    rank = jnp.zeros((Nk,), jnp.int32).at[order].set(rank_sorted.astype(jnp.int32))
    keep = rank < C

    dest_e = jnp.where(keep, flat_e, E)            # OOB rows dropped
    dest_c = jnp.where(keep, rank, C)
    x_rep = jnp.repeat(xf, topk, axis=0)           # (Nk, D) token per assignment
    buf = jnp.zeros((E, C, D), x.dtype).at[dest_e, dest_c].set(x_rep, mode="drop")
    buf = shard(buf, rules, "experts", None, "d_model")

    act = _act(cfg.mlp_act)
    h = act(jnp.einsum("ecd,edf->ecf", buf, w_gate)) * jnp.einsum(
        "ecd,edf->ecf", buf, w_up)
    h = shard(h, rules, "experts", None, None)
    y = jnp.einsum("ecf,efd->ecd", h, w_down)      # (E, C, D)
    y = shard(y, rules, "experts", None, "d_model")

    y_tok = y.at[jnp.clip(dest_e, 0, E - 1), jnp.clip(dest_c, 0, C - 1)].get()
    y_tok = jnp.where(keep[:, None], y_tok, 0.0)   # (Nk, D)
    out = jnp.sum(y_tok.reshape(N, topk, D) * gates[..., None], axis=1)
    out = out.reshape(B, S, D)
    return shard(out, rules, "batch", "seq", "d_model")


def moe_aux_loss(router_logits, top_i, cfg: ModelConfig):
    """Switch-style load-balance auxiliary loss (fraction × probability)."""
    E = cfg.num_experts
    probs = jax.nn.softmax(router_logits.astype(jnp.float32), axis=-1)
    me = jnp.mean(probs, axis=0)                          # (E,)
    one_hot = jax.nn.one_hot(top_i[..., 0], E)            # top-1 occupancy
    ce = jnp.mean(one_hot, axis=0)
    return E * jnp.sum(me * ce)
