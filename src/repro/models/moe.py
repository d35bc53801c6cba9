"""Mixture-of-experts FFN with *sparse* (gather/scatter) dispatch.

Top-k routing with a fixed per-expert capacity (MaxText/Switch style):
assignments are sorted by expert, each token-expert pair gets a slot
``(expert, position-within-expert)``; overflow beyond the capacity is
dropped (weight mass renormalized by what survives).  Dispatch/combine
are gathers + scatter-adds — *not* one-hot einsums — so compiled FLOPs
stay ≈ top_k/E of the dense-dispatch formulation (this is what keeps
MODEL_FLOPS/HLO_FLOPs honest in the roofline table; see DESIGN.md).

Experts are sharded over the ``expert`` logical axis (EP) when the
expert count divides the mesh axis (kimi: 384/16 ✓, jamba: 16/16 ✓);
otherwise the per-expert FF dim shards as TP (mixtral: 8 experts on a
16-way model axis).  The dispatch buffer resharding (data-sharded
tokens → expert-sharded slots) is GSPMD's all-to-all.
"""
from __future__ import annotations

import jax
from functools import partial
import jax.numpy as jnp

from repro.config import ModelConfig
from repro.models.layers import _normal
from repro.sharding import shard


def init_moe(key, cfg: ModelConfig, dtype) -> dict:
    d, f, e = cfg.d_model, cfg.d_ff, cfg.n_experts
    ks = jax.random.split(key, 4)
    si, so = d ** -0.5, f ** -0.5
    p = {"wg": _normal(ks[0], (d, e), si, jnp.float32),
         "w_up": _normal(ks[1], (e, d, f), si, dtype),
         "w_down": _normal(ks[2], (e, f, d), so, dtype)}
    if cfg.mlp_kind in ("swiglu", "geglu"):
        p["w_gate"] = _normal(ks[3], (e, d, f), si, dtype)
    return p


def capacity(cfg: ModelConfig, n_tokens: int) -> int:
    c = int(cfg.capacity_factor * cfg.top_k * n_tokens / cfg.n_experts)
    return max(8, ((c + 7) // 8) * 8)  # pad to vreg-friendly multiple


def apply_moe(p: dict, x: jax.Array, cfg: ModelConfig) -> jax.Array:
    """x: [B, S, d] → [B, S, d].

    On a mesh, dispatch runs under shard_map (local scatter + EP-sliced
    expert compute + psum combine) — see ``apply_moe_sharded``.  The
    data-dependent token→slot scatter cannot be sharded by GSPMD
    (it replicates the dispatch buffer, which at kimi-k2 scale is a
    ~150 GB tensor and dominated the baseline collective term); doing
    the scatter shard-locally under shard_map removes that entirely.
    """
    import os
    from repro.sharding import _mesh_axis_sizes
    if _mesh_axis_sizes() and not os.environ.get("REPRO_MOE_DENSE"):
        return apply_moe_sharded(p, x, cfg)
    return _apply_moe_dense(p, x, cfg)


def _apply_moe_dense(p: dict, x: jax.Array, cfg: ModelConfig) -> jax.Array:
    """Single-device / GSPMD-auto path."""
    b, s, d = x.shape
    t = b * s
    e, k = cfg.n_experts, cfg.top_k
    xt = x.reshape(t, d)

    # --- routing ---
    logits = (xt.astype(jnp.float32) @ p["wg"])            # [T, E]
    topv, topi = jax.lax.top_k(logits, k)                  # [T, k]
    weights = jax.nn.softmax(topv, axis=-1)                # renormalized

    # --- slot assignment: sort (token, choice) pairs by expert ---
    e_flat = topi.reshape(-1)                              # [T·k]
    order = jnp.argsort(e_flat, stable=True)
    e_sorted = e_flat[order]
    tok_sorted = order // k
    w_sorted = weights.reshape(-1)[order]
    seg_start = jnp.searchsorted(e_sorted, jnp.arange(e))
    pos_in_e = jnp.arange(t * k) - seg_start[e_sorted]
    cap = capacity(cfg, t)
    keep = pos_in_e < cap
    slot = jnp.where(keep, e_sorted * cap + pos_in_e, e * cap)

    # --- dispatch (scatter into [E·C, d], one overflow row) ---
    buf = jnp.zeros((e * cap + 1, d), x.dtype)
    buf = buf.at[slot].set(xt[tok_sorted] *
                           keep[:, None].astype(x.dtype))
    he = buf[:e * cap].reshape(e, cap, d)
    he = shard(he, "expert", "moe_cap", None)

    # --- expert FFN (batched over experts) ---
    up = jnp.einsum("ecd,edf->ecf", he, p["w_up"])
    if "w_gate" in p:
        g = jnp.einsum("ecd,edf->ecf", he, p["w_gate"])
        act = jax.nn.silu(g) if cfg.mlp_kind == "swiglu" else jax.nn.gelu(g)
        up = act * up
    else:
        up = jax.nn.gelu(up)
    out_e = jnp.einsum("ecf,efd->ecd", up, p["w_down"])
    out_e = shard(out_e, "expert", "moe_cap", None)

    # --- combine (gather + weighted scatter-add back to tokens) ---
    flat = out_e.reshape(e * cap, d)
    flat = jnp.concatenate([flat, jnp.zeros((1, d), flat.dtype)])
    contrib = flat[slot] * (w_sorted * keep).astype(x.dtype)[:, None]
    out = jnp.zeros((t, d), x.dtype).at[tok_sorted].add(contrib)
    return out.reshape(b, s, d)


def _local_moe(x_loc, wg, w_up, w_gate, w_down, *, cfg: ModelConfig,
               e_loc: int, ep_axes: tuple, red_axes: tuple):
    """Shard-local MoE: route local tokens, scatter into a local
    dispatch buffer, compute the locally-owned expert slice, combine
    with a psum over the expert/ff axes.  Runs inside shard_map."""
    t_loc, d = x_loc.shape
    e, k = cfg.n_experts, cfg.top_k

    logits = x_loc.astype(jnp.float32) @ wg                 # [T_loc, E]
    topv, topi = jax.lax.top_k(logits, k)
    weights = jax.nn.softmax(topv, axis=-1)

    e_flat = topi.reshape(-1)
    order = jnp.argsort(e_flat, stable=True)
    e_sorted = e_flat[order]
    tok_sorted = order // k
    w_sorted = weights.reshape(-1)[order]
    seg_start = jnp.searchsorted(e_sorted, jnp.arange(e))
    pos_in_e = jnp.arange(t_loc * k) - seg_start[e_sorted]
    cap = capacity(cfg, t_loc)
    keep = pos_in_e < cap

    # which experts this (expert-parallel) rank owns
    if ep_axes:
        idx = jnp.int32(0)
        for ax in ep_axes:
            idx = idx * jax.lax.axis_size(ax) + jax.lax.axis_index(ax)
        e0 = idx * e_loc
    else:
        e0 = jnp.int32(0)

    mine = keep & (e_sorted >= e0) & (e_sorted < e0 + e_loc)
    lslot = jnp.where(mine, (e_sorted - e0) * cap + pos_in_e,
                      e_loc * cap)
    buf = jnp.zeros((e_loc * cap + 1, d), x_loc.dtype)
    buf = buf.at[lslot].set(x_loc[tok_sorted]
                            * mine[:, None].astype(x_loc.dtype))
    he = buf[:e_loc * cap].reshape(e_loc, cap, d)

    up = jnp.einsum("ecd,edf->ecf", he, w_up)
    if w_gate is not None:
        g = jnp.einsum("ecd,edf->ecf", he, w_gate)
        act = jax.nn.silu(g) if cfg.mlp_kind == "swiglu" \
            else jax.nn.gelu(g)
        up = act * up
    else:
        up = jax.nn.gelu(up)
    out_e = jnp.einsum("ecf,efd->ecd", up, w_down)

    flat = jnp.concatenate(
        [out_e.reshape(e_loc * cap, d),
         jnp.zeros((1, d), out_e.dtype)])
    contrib = flat[lslot] * (w_sorted * mine).astype(x_loc.dtype)[:, None]
    out = jnp.zeros((t_loc, d), x_loc.dtype).at[tok_sorted].add(contrib)
    if red_axes:
        out = jax.lax.psum(out, red_axes)
    return out


def apply_moe_sharded(p: dict, x: jax.Array, cfg: ModelConfig):
    """shard_map MoE over the current mesh (DESIGN.md §7 / EXPERIMENTS
    §Perf): tokens stay batch-sharded, expert weights stay EP/TP-sharded
    (never gathered), dispatch is shard-local, combine is one psum of
    [T_loc, d]."""
    from jax.sharding import PartitionSpec as P
    from repro.sharding import _mesh_axis_sizes, current_mesh, resolve

    mesh = current_mesh()
    sizes = _mesh_axis_sizes()
    b, s, d = x.shape
    e = cfg.n_experts

    def as_tuple(r):
        if r is None:
            return ()
        return r if isinstance(r, tuple) else (r,)

    dp = as_tuple(resolve("batch", b * s))
    ep = tuple(a for a in as_tuple(resolve("expert", e)) if a not in dp)
    e_loc = e
    for a in ep:
        e_loc //= sizes[a]
    ff = tuple(a for a in as_tuple(resolve("moe_ff", cfg.d_ff))
               if a not in dp and a not in ep)
    red = ep + ff

    w_gate = p.get("w_gate")
    in_specs = (P(dp if dp else None, None),        # x [T, d]
                P(None, None),                      # wg
                P(ep if ep else None, None, ff if ff else None),
                (P(ep if ep else None, None, ff if ff else None)
                 if w_gate is not None else None),
                P(ep if ep else None, ff if ff else None, None))
    fn = partial(_local_moe, cfg=cfg, e_loc=e_loc, ep_axes=ep,
                 red_axes=red)
    out_specs = P(dp if dp else None, None)
    sm = jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                       out_specs=out_specs, check_vma=False)
    out = sm(x.reshape(b * s, d), p["wg"], p["w_up"], w_gate, p["w_down"])
    return out.reshape(b, s, d)


def moe_flops_per_token(cfg: ModelConfig) -> int:
    """Active-param matmul FLOPs per token (fwd), for roofline ratios."""
    n_mats = 3 if cfg.mlp_kind in ("swiglu", "geglu") else 2
    return 2 * cfg.top_k * n_mats * cfg.d_model * cfg.d_ff
