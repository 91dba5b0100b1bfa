"""Sort-based (scatter/gather) Mixture-of-Experts layer.

Classic one-hot dispatch einsum needs a (T, E, C) tensor which is infeasible
for Kimi-K2-scale expert counts (E=384); instead we sort token->expert
assignments and scatter into an (E, C, D) buffer (the standard
expert-parallel layout: the E axis shards over the `model` mesh axis, so
GSPMD lowers the scatter/gather to an all-to-all pair).

Overflowed tokens (expert over capacity) are dropped — they pass through on
the residual stream, matching capacity-factor semantics.
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

import os

from repro.models.layers import _dense_init
from repro.sharding.specs import constrain, current_mesh


def moe_init(key, cfg, dtype) -> dict:
    d, f, e = cfg.d_model, cfg.moe_d_ff_eff, cfg.n_experts
    ks = jax.random.split(key, 4)
    return {
        "router": _dense_init(ks[0], (d, e), jnp.float32),
        "we_gate": _dense_init(ks[1], (e, d, f), dtype),
        "we_up": _dense_init(ks[2], (e, d, f), dtype),
        "we_down": _dense_init(ks[3], (e, f, d), dtype),
    }


def _capacity(n_tokens: int, cfg) -> int:
    c = int(n_tokens * cfg.experts_per_token * cfg.capacity_factor
            / cfg.n_experts) + 1
    # MXU-friendly rounding
    return max(8, -(-c // 8) * 8)


def moe_apply(params: dict, x: jnp.ndarray, cfg) -> Tuple[jnp.ndarray, dict]:
    """x: (..., T, D) -> (..., T, D), aux metrics.

    Works on the flattened token axis.  With REPRO_MOE_SHARDMAP=1 and an
    expert-divisible mesh, dispatch goes through the shard_map
    slice-dispatch path (§Perf iteration M2) instead of GSPMD.
    """
    mesh = current_mesh()
    if (os.environ.get("REPRO_MOE_SHARDMAP") and mesh is not None
            and "model" in mesh.axis_names and x.ndim == 3):
        if cfg.n_experts % mesh.shape["model"] == 0:
            return moe_apply_sharded(params, x, cfg, mesh)
        return moe_apply_capsharded(params, x, cfg, mesh)
    orig_shape = x.shape
    d = orig_shape[-1]
    xt = x.reshape(-1, d)
    t = xt.shape[0]
    e, k = cfg.n_experts, cfg.experts_per_token
    cap = _capacity(t, cfg)

    logits = jnp.einsum("td,de->te", xt.astype(jnp.float32), params["router"])
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, expert_idx = jax.lax.top_k(probs, k)  # (T, k)
    gate_vals = gate_vals / jnp.sum(gate_vals, axis=-1, keepdims=True)

    # --- position of each assignment within its expert ------------------
    flat_e = expert_idx.reshape(-1)  # (T*k,)
    order = jnp.argsort(flat_e, stable=True)
    sorted_e = flat_e[order]
    counts = jnp.bincount(flat_e, length=e)  # (E,)
    starts = jnp.cumsum(counts) - counts
    pos_sorted = jnp.arange(t * k) - starts[sorted_e]
    pos = jnp.zeros((t * k,), jnp.int32).at[order].set(pos_sorted.astype(jnp.int32))

    keep = pos < cap
    slot = jnp.where(keep, flat_e * cap + pos, e * cap)  # overflow -> dropped

    # --- dispatch --------------------------------------------------------
    x_rep = jnp.repeat(xt, k, axis=0)  # (T*k, D) token order: t0k0 t0k1 ...
    buf = jnp.zeros((e * cap, d), xt.dtype)
    buf = buf.at[slot].set(x_rep, mode="drop")
    buf = constrain(buf.reshape(e, cap, d), "moe_buf")

    # --- expert computation (E, C, D) x (E, D, F) ------------------------
    g = jnp.einsum("ecd,edf->ecf", buf, params["we_gate"])
    u = jnp.einsum("ecd,edf->ecf", buf, params["we_up"])
    h = jax.nn.silu(g) * u
    out_buf = jnp.einsum("ecf,efd->ecd", h, params["we_down"])
    out_buf = constrain(out_buf, "moe_buf").reshape(e * cap, d)

    # --- combine ----------------------------------------------------------
    gathered = jnp.where(keep[:, None], out_buf.at[slot].get(mode="fill",
                                                             fill_value=0), 0)
    gathered = gathered.reshape(t, k, d)
    y = jnp.sum(gathered * gate_vals[..., None].astype(gathered.dtype), axis=1)

    # --- aux: load-balance loss (Switch-style) ---------------------------
    me = jnp.mean(probs, axis=0)  # mean router prob per expert
    ce = jnp.mean(
        jax.nn.one_hot(expert_idx[:, 0], e, dtype=jnp.float32), axis=0)
    aux_loss = e * jnp.sum(me * ce) * cfg.router_aux_weight
    dropped = jnp.sum(~keep) / (t * k)
    aux = {"moe_aux_loss": aux_loss, "moe_drop_frac": dropped}
    return y.reshape(orig_shape).astype(x.dtype), aux


# ----------------------------------------------------------------------
# §Perf iteration M2: shard_map slice-dispatch MoE
# ----------------------------------------------------------------------
def moe_apply_sharded(params: dict, x: jnp.ndarray, cfg,
                      mesh) -> Tuple[jnp.ndarray, dict]:
    """Expert-parallel MoE with an explicit communication schedule.

    The GSPMD path pays a giant collective because position-in-expert
    needs a *global* argsort over tokens (the partitioner all-gathers the
    assignment arrays).  Here every (data, model) device:

      1. routes its LOCAL tokens (router weights replicated — identical
         compute across the model axis, zero wire bytes);
      2. scatters them into a local (E, C_loc, D) buffer and *slices* the
         expert range it owns (dispatch = free);
      3. runs its E/n_model experts;
      4. gathers its experts' outputs back to token order and psums over
         the model axis — O(T_loc * D) bytes, the only collective.

    Wire bytes per layer: T_loc * D * 4 (one psum) vs the sort path's
    multi-GB gathers — see EXPERIMENTS.md §Perf.
    """
    import jax
    from jax.sharding import PartitionSpec as P

    n_model = mesh.shape["model"]
    b_axes = ("pod", "data") if "pod" in mesh.axis_names else ("data",)
    e, k = cfg.n_experts, cfg.experts_per_token
    e_loc = e // n_model
    d = x.shape[-1]

    def body(xl, router, wg, wu, wd):
        bl, sl, _ = xl.shape
        xt = xl.reshape(-1, d)
        t = xt.shape[0]
        cap = _capacity(t, cfg)

        logits = jnp.einsum("td,de->te", xt.astype(jnp.float32), router)
        probs = jax.nn.softmax(logits, axis=-1)
        gate_vals, expert_idx = jax.lax.top_k(probs, k)
        gate_vals = gate_vals / jnp.sum(gate_vals, axis=-1, keepdims=True)

        flat_e = expert_idx.reshape(-1)
        order = jnp.argsort(flat_e, stable=True)        # local sort only
        sorted_e = flat_e[order]
        counts = jnp.bincount(flat_e, length=e)
        starts = jnp.cumsum(counts) - counts
        pos_sorted = jnp.arange(t * k) - starts[sorted_e]
        pos = jnp.zeros((t * k,), jnp.int32).at[order].set(
            pos_sorted.astype(jnp.int32))
        keep = pos < cap
        slot = jnp.where(keep, flat_e * cap + pos, e * cap)

        x_rep = jnp.repeat(xt, k, axis=0)
        buf = jnp.zeros((e * cap, d), xt.dtype)
        buf = buf.at[slot].set(x_rep, mode="drop").reshape(e, cap, d)

        # 2) slice my expert range (weights arrive pre-sliced: (E_loc,..))
        r = jax.lax.axis_index("model")
        my = jax.lax.dynamic_slice_in_dim(buf, r * e_loc, e_loc, axis=0)

        # 3) local expert compute
        g = jnp.einsum("ecd,edf->ecf", my, wg)
        u = jnp.einsum("ecd,edf->ecf", my, wu)
        h = jax.nn.silu(g) * u
        out_buf = jnp.einsum("ecf,efd->ecd", h, wd).reshape(e_loc * cap, d)

        # 4) token-order gather of MY experts' outputs, then psum
        mine = keep & (flat_e >= r * e_loc) & (flat_e < (r + 1) * e_loc)
        slot_mine = jnp.where(mine, (flat_e - r * e_loc) * cap + pos, 0)
        gathered = jnp.where(
            mine[:, None],
            out_buf.at[slot_mine].get(mode="fill", fill_value=0), 0)
        y = jnp.sum(gathered.reshape(t, k, d)
                    * gate_vals[..., None].astype(gathered.dtype), axis=1)
        y = jax.lax.psum(y, "model")

        # aux (identical across model ranks; psum-average over data later
        # is unnecessary — scalars are consistent estimators per shard)
        me = jnp.mean(probs, axis=0)
        ce = jnp.mean(jax.nn.one_hot(expert_idx[:, 0], e,
                                     dtype=jnp.float32), axis=0)
        aux_loss = e * jnp.sum(me * ce) * cfg.router_aux_weight
        dropped = jnp.sum(~keep) / (t * k)
        return (y.reshape(bl, sl, d).astype(xl.dtype), aux_loss, dropped)

    y, aux_loss, dropped = jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(b_axes, None, None), P(None, None),
                  P("model", None, None), P("model", None, None),
                  P("model", None, None)),
        out_specs=(P(b_axes, None, None), P(), P()),
        check_vma=False,
    )(x, params["router"], params["we_gate"], params["we_up"],
      params["we_down"])
    return y, {"moe_aux_loss": aux_loss, "moe_drop_frac": dropped}


def moe_apply_capsharded(params: dict, x: jnp.ndarray, cfg,
                         mesh) -> Tuple[jnp.ndarray, dict]:
    """§Perf iteration M3: capacity-sharded shard_map MoE for E < n_model
    (mixtral: 8 experts on a 16-wide model axis).

    Every model rank keeps FULL expert weights (8x3 small matrices) but
    processes only its 1/n_model slice of every expert's capacity;
    the single collective is the final output psum (O(T_loc * D)).
    Expert FLOPs per device drop n_model-fold vs. the GSPMD fallback,
    which could not shard an 8-long expert dim over 16 ranks.
    """
    import jax
    from jax.sharding import PartitionSpec as P

    n_model = mesh.shape["model"]
    b_axes = ("pod", "data") if "pod" in mesh.axis_names else ("data",)
    e, k = cfg.n_experts, cfg.experts_per_token
    d = x.shape[-1]

    def body(xl, router, wg, wu, wd):
        bl, sl, _ = xl.shape
        xt = xl.reshape(-1, d)
        t = xt.shape[0]
        cap = _capacity(t, cfg)
        cap_loc = -(-cap // n_model)

        logits = jnp.einsum("td,de->te", xt.astype(jnp.float32), router)
        probs = jax.nn.softmax(logits, axis=-1)
        gate_vals, expert_idx = jax.lax.top_k(probs, k)
        gate_vals = gate_vals / jnp.sum(gate_vals, axis=-1, keepdims=True)

        flat_e = expert_idx.reshape(-1)
        order = jnp.argsort(flat_e, stable=True)
        sorted_e = flat_e[order]
        counts = jnp.bincount(flat_e, length=e)
        starts = jnp.cumsum(counts) - counts
        pos_sorted = jnp.arange(t * k) - starts[sorted_e]
        pos = jnp.zeros((t * k,), jnp.int32).at[order].set(
            pos_sorted.astype(jnp.int32))
        keep = pos < cap

        # my capacity window of every expert
        r = jax.lax.axis_index("model")
        lo = r * cap_loc
        mine = keep & (pos >= lo) & (pos < lo + cap_loc)
        slot = jnp.where(mine, flat_e * cap_loc + (pos - lo), e * cap_loc)

        x_rep = jnp.repeat(xt, k, axis=0)
        buf = jnp.zeros((e * cap_loc, d), xt.dtype)
        buf = buf.at[slot].set(x_rep, mode="drop").reshape(e, cap_loc, d)

        g = jnp.einsum("ecd,edf->ecf", buf, wg)
        u = jnp.einsum("ecd,edf->ecf", buf, wu)
        h = jax.nn.silu(g) * u
        out_buf = jnp.einsum("ecf,efd->ecd", h, wd).reshape(
            e * cap_loc, d)

        gathered = jnp.where(
            mine[:, None],
            out_buf.at[jnp.where(mine, slot, 0)].get(
                mode="fill", fill_value=0), 0)
        y = jnp.sum(gathered.reshape(t, k, d)
                    * gate_vals[..., None].astype(gathered.dtype), axis=1)
        y = jax.lax.psum(y, "model")

        me = jnp.mean(probs, axis=0)
        ce = jnp.mean(jax.nn.one_hot(expert_idx[:, 0], e,
                                     dtype=jnp.float32), axis=0)
        aux_loss = e * jnp.sum(me * ce) * cfg.router_aux_weight
        dropped = jnp.sum(~keep) / (t * k)
        return (y.reshape(bl, sl, d).astype(xl.dtype), aux_loss, dropped)

    y, aux_loss, dropped = jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(b_axes, None, None), P(None, None),
                  P(None, None, None), P(None, None, None),
                  P(None, None, None)),
        out_specs=(P(b_axes, None, None), P(), P()),
        check_vma=False,
    )(x, params["router"], params["we_gate"], params["we_up"],
      params["we_down"])
    return y, {"moe_aux_loss": aux_loss, "moe_drop_frac": dropped}
