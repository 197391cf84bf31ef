"""Mixture-of-Experts FFN with expert-parallel all-to-all dispatch.

Routing (top-k, softmax-normalized over the selected experts, GShard-
style capacity with drop) is computed in GSPMD-land; the token dispatch
+ expert FFN + combine run inside ``shard_map`` so the expert-parallel
``all_to_all`` over the model axis is explicit — this is the collective
the roofline must see for MoE architectures.

Two dispatch paths:
* **a2a** — batch sharded over data axes: sort-based local dispatch
  into per-expert capacity buffers, ``all_to_all`` over the expert
  (model) axis, per-expert SwiGLU, ``all_to_all`` back, weighted
  combine.
* **replicated** — no mesh / batch-1 decode: every device computes its
  local experts' outputs and a ``psum`` over the expert axis combines
  (no mesh at all -> plain local computation, used as the oracle).

Experts are padded to ``num_experts_padded`` for mesh divisibility;
padding experts get -inf router logits and are never selected.
"""
from __future__ import annotations

import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from .config import ModelConfig
from .layers import dense_init, init_mlp, mlp_apply
from .sharding_ctx import (_manual_axes, current_mesh, current_rules,
                           shard, shard_map)


def _inner_mesh(mesh):
    """Mesh argument for a shard_map that may be nested inside a
    partial-manual region: the context's AbstractMesh when one is
    active (required for nesting), else the concrete mesh."""
    if jax.sharding.get_abstract_mesh().axis_names:
        return None                # infer from context
    return mesh


def init_moe(key, cfg: ModelConfig) -> dict:
    d, E, ff = cfg.d_model, cfg.num_experts_padded, cfg.moe_d_ff
    ks = jax.random.split(key, 5)
    p = {
        "router": dense_init(ks[0], d, E),
        "w_gate": jax.vmap(lambda k: dense_init(k, d, ff))(
            jax.random.split(ks[1], E)),
        "w_up": jax.vmap(lambda k: dense_init(k, d, ff))(
            jax.random.split(ks[2], E)),
        "w_down": jax.vmap(lambda k: dense_init(k, ff, d))(
            jax.random.split(ks[3], E)),
    }
    if cfg.num_shared_experts:
        shared_ff = cfg.moe_d_ff * cfg.num_shared_experts
        p["shared"] = init_mlp(ks[4], d, shared_ff)
    return p


def _route(params: dict, x: jnp.ndarray, cfg: ModelConfig
           ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Router: top-k indices, normalized weights, aux load-balance loss."""
    dt = x.dtype
    E, Ep, k = cfg.num_experts, cfg.num_experts_padded, cfg.top_k
    logits = (x @ params["router"].astype(dt)).astype(jnp.float32)
    if Ep > E:
        pad_mask = jnp.arange(Ep) >= E
        logits = jnp.where(pad_mask, -jnp.inf, logits)
    probs = jax.nn.softmax(logits, axis=-1)                 # [B,S,Ep]
    top_w, top_idx = jax.lax.top_k(probs, k)                # [B,S,k]
    top_w = top_w / jnp.sum(top_w, axis=-1, keepdims=True)
    # Switch-style aux loss: E * sum_e f_e * p_e
    f = jnp.mean(jax.nn.one_hot(top_idx, Ep, dtype=jnp.float32),
                 axis=(0, 1, 2))
    p_mean = jnp.mean(probs, axis=(0, 1))
    aux = E * jnp.sum(f * p_mean) * k
    return top_idx, top_w.astype(dt), aux


def _local_dispatch(x_flat, top_idx, top_w, Ep: int, C: int):
    """Sort-based capacity dispatch of local tokens.

    Returns (buffer [Ep, C, d], combine info) with static shapes; tokens
    beyond capacity are dropped (contribute zero, weight renormalized is
    NOT applied — standard GShard drop semantics)."""
    T, d = x_flat.shape
    k = top_idx.shape[-1]
    e_flat = top_idx.reshape(-1)                    # [T*k]
    w_flat = top_w.reshape(-1)
    tok_flat = jnp.repeat(jnp.arange(T), k)
    order = jnp.argsort(e_flat)
    e_s, w_s, tok_s = e_flat[order], w_flat[order], tok_flat[order]
    counts = jnp.zeros((Ep,), jnp.int32).at[e_s].add(1)
    starts = jnp.cumsum(counts) - counts            # exclusive cumsum
    pos = jnp.arange(T * k) - starts[e_s]           # rank within expert
    keep = pos < C
    pos_sc = jnp.where(keep, pos, C)                # OOB -> dropped
    buf = jnp.zeros((Ep, C, d), x_flat.dtype)
    buf = buf.at[e_s, pos_sc].set(x_flat[tok_s], mode="drop")
    return buf, (e_s, pos_sc, tok_s, w_s)


def _local_combine(y_buf, info, T: int, d: int):
    e_s, pos_sc, tok_s, w_s = info
    gathered = y_buf.at[e_s, pos_sc].get(mode="fill", fill_value=0.0)
    out = jnp.zeros((T, d), y_buf.dtype)
    return out.at[tok_s].add(gathered * w_s[:, None])


def _expert_ffn(w_gate, w_up, w_down, xe, dtype):
    """xe: [E_local, C', d] -> per-expert SwiGLU."""
    h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", xe, w_gate.astype(dtype)))
    h = h * jnp.einsum("ecd,edf->ecf", xe, w_up.astype(dtype))
    return jnp.einsum("ecf,efd->ecd", h, w_down.astype(dtype))


def moe_apply(params: dict, x: jnp.ndarray, cfg: ModelConfig
              ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """x: [B, S, d] -> (y, aux_loss)."""
    dt = x.dtype
    B, S, d = x.shape
    Ep, k = cfg.num_experts_padded, cfg.top_k
    top_idx, top_w, aux = _route(params, x, cfg)

    mesh = current_mesh()
    rules = current_rules()
    expert_axis = rules.get("expert") if mesh is not None else None

    if expert_axis is None:
        # oracle / single-device path: all experts local
        x_flat = x.reshape(B * S, d)
        C = max(4, math.ceil(B * S * k / Ep * cfg.capacity_factor))
        buf, info = _local_dispatch(x_flat, top_idx.reshape(B * S, k),
                                    top_w.reshape(B * S, k), Ep, C)
        y_buf = _expert_ffn(params["w_gate"], params["w_up"],
                            params["w_down"], buf, dt)
        y = _local_combine(y_buf, info, B * S, d).reshape(B, S, d)
    else:
        y = _moe_shard_map(params, x, top_idx, top_w, cfg, mesh, rules)

    if cfg.num_shared_experts:
        y = y + _shared_expert(params["shared"], x, dt, mesh, rules)
    return shard(y, "batch", "seq", None), aux


def _shared_expert(sp: dict, x: jnp.ndarray, dt, mesh, rules) -> jnp.ndarray:
    """Always-active shared expert path — plain SwiGLU; GSPMD shards the
    hidden dim over the model axis via the ffn logical axis."""
    h = jax.nn.silu(x @ sp["w_gate"].astype(dt)) * (x @ sp["w_up"].astype(dt))
    h = shard(h, "batch", "seq", "ffn")
    return h @ sp["w_down"].astype(dt)


def _moe_shard_map(params, x, top_idx, top_w, cfg: ModelConfig, mesh, rules):
    """Expert-parallel dispatch with explicit all_to_all."""
    dt = x.dtype
    B, S, d = x.shape
    Ep, k = cfg.num_experts_padded, cfg.top_k
    expert_axis = rules["expert"]                  # e.g. "model"
    batch_axes = rules.get("batch")                # e.g. ("pod","data")
    ea_size = mesh.shape[expert_axis]
    if isinstance(batch_axes, str):
        batch_axes = (batch_axes,)
    # axes already manual (we are nested inside a shard_map over them,
    # e.g. the per-replica training region): x is already local there.
    manual = _manual_axes()
    batch_axes = tuple(a for a in (batch_axes or ())
                       if a not in manual) or None
    bs_size = 1
    if batch_axes:
        for a in batch_axes:
            bs_size *= mesh.shape[a]

    seq_shardable = (S % ea_size == 0) and S > 1
    replicated_batch = (not batch_axes or (B % bs_size != 0)) \
        and not seq_shardable
    if replicated_batch:
        # batch-1 decode: tokens replicated; each device computes its
        # local experts and a psum over the expert axis combines.
        def repl_fn(wg, wu, wd, xl, ti, tw):
            E_loc = wg.shape[0]
            ax_idx = jax.lax.axis_index(expert_axis)
            e_off = ax_idx * E_loc
            T = xl.shape[0] * xl.shape[1]
            x_flat = xl.reshape(T, d)
            til = ti.reshape(T, k) - e_off         # local expert ids
            twl = tw.reshape(T, k)
            valid = (til >= 0) & (til < E_loc)
            twl = jnp.where(valid, twl, 0.0)
            til = jnp.clip(til, 0, E_loc - 1)
            C = max(4, math.ceil(T * k / Ep * cfg.capacity_factor) * 4)
            buf, info = _local_dispatch(x_flat, til, twl, E_loc, C)
            y_buf = _expert_ffn(wg, wu, wd, buf, dt)
            y = _local_combine(y_buf, info, T, d)
            y = jax.lax.psum(y, expert_axis)
            return y.reshape(xl.shape)

        return shard_map(
            repl_fn, mesh=_inner_mesh(mesh),
            in_specs=(P(expert_axis), P(expert_axis), P(expert_axis),
                      P(), P(), P()),
            out_specs=P(),
            check_vma=False,
        )(params["w_gate"].astype(dt), params["w_up"].astype(dt),
          params["w_down"].astype(dt), x, top_idx, top_w)

    # ---- a2a path: batch sharded over data axes ----
    # x is replicated along the expert (model) axis, so we additionally
    # shard the SEQUENCE dim over it inside the shard_map (free local
    # slice on entry; GSPMD all-gathers the output back) — otherwise
    # every model-peer would dispatch identical tokens and the experts
    # would compute W redundant copies.  Falls back to the redundant
    # layout when S is not divisible (S == 1 decode: negligible waste).
    seq_sharded = (S % ea_size == 0) and S > 1
    S_l = S // ea_size if seq_sharded else S
    T_l = (B // bs_size) * S_l
    C_l = max(4, math.ceil(T_l * k / Ep * cfg.capacity_factor))

    def a2a_fn(wg, wu, wd, xl, ti, tw):
        Bl = xl.shape[0]
        x_flat = xl.reshape(Bl * S_l, d)
        buf, info = _local_dispatch(x_flat, ti.reshape(-1, k),
                                    tw.reshape(-1, k), Ep, C_l)
        # [Ep, C_l, d] -> [Ep/W, W*C_l, d]: tokens for my local experts
        xe = jax.lax.all_to_all(buf, expert_axis, split_axis=0,
                                concat_axis=1, tiled=True)
        ye = _expert_ffn(wg, wu, wd, xe, dt)
        y_buf = jax.lax.all_to_all(ye, expert_axis, split_axis=1,
                                   concat_axis=0, tiled=True)
        y = _local_combine(y_buf, info, Bl * S_l, d)
        return y.reshape(Bl, S_l, d)

    if batch_axes:
        batuple = batch_axes if len(batch_axes) > 1 else batch_axes[0]
    else:
        batuple = None
    seq_ax = expert_axis if seq_sharded else None
    bspec = P(batuple, seq_ax)
    return shard_map(
        a2a_fn, mesh=_inner_mesh(mesh),
        in_specs=(P(expert_axis), P(expert_axis), P(expert_axis),
                  bspec, bspec, bspec),
        out_specs=bspec,
        check_vma=False,
    )(params["w_gate"].astype(dt), params["w_up"].astype(dt),
      params["w_down"].astype(dt), x, top_idx, top_w)
