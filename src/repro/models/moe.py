"""Mixture-of-Experts layer — sort-based token dispatch with capacity drop
(the TPU-native dense-dispatch pattern: grouped expert GEMMs on the MXU,
all-to-all materialized by GSPMD when experts are sharded over the model
axis).

Supports DeepSeek-style shared experts and the aux-loss-free balancing bias
(a router logit bias that is *updated outside the gradient* — here kept as a
parameter updated by the training loop's balance callback).

The token->expert assignment is itself a bipartite graph-cut problem; the
paper's vertex-cut balance objective (imbalance -> 1) is exactly what
capacity-limited top-k dispatch enforces per batch — see DESIGN.md §6.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from repro.models.layers import _dense_init, init_mlp, mlp_apply, mlp_specs
from repro.sharding.rules import maybe_constrain


def init_moe(key, cfg, dtype):
    m = cfg.moe
    d = cfg.d_model
    d_ffe = m.d_ff_expert or cfg.d_ff
    ks = jax.random.split(key, 5)
    params = {
        "router": _dense_init(ks[0], (d, m.n_experts), d, jnp.float32),
        "router_bias": jnp.zeros((m.n_experts,), jnp.float32),
        "w_gate": _dense_init(ks[1], (m.n_experts, d, d_ffe), d, dtype),
        "w_up": _dense_init(ks[2], (m.n_experts, d, d_ffe), d, dtype),
        "w_down": _dense_init(ks[3], (m.n_experts, d_ffe, d), d_ffe, dtype),
    }
    if m.n_shared:
        params["shared"] = init_mlp(ks[4], d, d_ffe * m.n_shared, cfg.act,
                                    dtype)
    return params


def moe_specs(cfg):
    m = cfg.moe
    specs = {"router": ("embed", "experts"), "router_bias": ("experts",),
             "w_gate": ("experts", "embed", "ff_expert"),
             "w_up": ("experts", "embed", "ff_expert"),
             "w_down": ("experts", "ff_expert", "embed")}
    if m.n_shared:
        specs["shared"] = mlp_specs(cfg.act)
    return specs


def _dp_groups(total_tokens: int) -> int:
    """Number of DP shards in the ambient mesh that divide the token count
    (hierarchical dispatch group count; 1 when unsharded/CPU)."""
    am = jax.sharding.get_abstract_mesh()
    if am.empty:
        return 1
    g = 1
    for ax in ("pod", "data"):
        if ax in am.axis_names:
            g *= am.shape[ax]
    return g if (g > 1 and total_tokens % g == 0) else 1


def moe_apply(params, x, cfg):
    """x [B, S, d] -> [B, S, d]."""
    m = cfg.moe
    if m.dispatch == "hierarchical":
        return moe_apply_hierarchical(params, x, cfg)
    B, S, d = x.shape
    T = B * S
    E, k = m.n_experts, m.top_k
    xt = x.reshape(T, d)

    logits = jnp.einsum("td,de->te", xt.astype(jnp.float32), params["router"])
    probs = jax.nn.softmax(logits, -1)
    sel_basis = probs + params["router_bias"][None, :] \
        if m.router_aux_free_bias else probs
    gate, expert_idx = jax.lax.top_k(sel_basis, k)              # [T, k]
    gate = jnp.take_along_axis(probs, expert_idx, axis=-1)
    gate = gate / jnp.maximum(gate.sum(-1, keepdims=True), 1e-9)

    # ---- sort-based dispatch with capacity drop ------------------------- #
    cap = int(math.ceil(m.capacity_factor * T * k / E / 8.0) * 8)
    cap = min(cap, T * k)   # dropless ceiling
    flat_e = expert_idx.reshape(-1)                              # [T*k]
    order = jnp.argsort(flat_e)                                  # stable
    se = flat_e[order]
    seg_start = jnp.searchsorted(se, jnp.arange(E))              # [E]
    pos = jnp.arange(T * k) - seg_start[se]
    keep = pos < cap
    slot = jnp.where(keep, se * cap + pos, E * cap)              # drop row
    tok = order // k

    buf = jnp.zeros((E * cap, d), x.dtype)
    buf = buf.at[slot].set(jnp.where(keep[:, None], xt[tok], 0), mode="drop")
    h = buf.reshape(E, cap, d)
    # expert parallelism: expert dim over 'model', token slots over DP axes
    h = maybe_constrain(h, "model", ("pod", "data"), None)

    g = jnp.einsum("ecd,edf->ecf", h, params["w_gate"])
    u = jnp.einsum("ecd,edf->ecf", h, params["w_up"])
    y = jnp.einsum("ecf,efd->ecd", jax.nn.silu(g) * u, params["w_down"])
    y = maybe_constrain(y, "model", ("pod", "data"), None)

    y_flat = y.reshape(E * cap, d)
    y_tok = jnp.take(y_flat, slot, axis=0, mode="fill", fill_value=0)
    w = jnp.where(keep, gate.reshape(-1)[order], 0.0).astype(y_tok.dtype)
    out = jnp.zeros((T, d), x.dtype).at[tok].add(y_tok * w[:, None])

    if m.n_shared:
        out = out + mlp_apply(params["shared"], xt, cfg.act)

    # load-balance stats (consumed by the aux-free bias update / metrics)
    load = jnp.zeros((E,), jnp.float32).at[flat_e].add(1.0) / (T * k)
    return out.reshape(B, S, d), {"load": load,
                                  "dropped": 1.0 - keep.mean()}


def moe_apply_hierarchical(params, x, cfg):
    """Hierarchical (per-DP-shard) dispatch — the §Perf optimization for the
    MoE architectures.

    The baseline global argsort-dispatch makes GSPMD all-reduce the full
    [E*cap, d] buffers (every shard contributes masked rows to every slot).
    Here tokens are grouped by DP shard: the sort, capacity drop and scatter
    are *local* to each group (leading G axis sharded over (pod, data)), and
    the only cross-device movement is the [G, E, capG, d] -> [E, G*capG, d]
    transpose — a true all-to-all, exactly the paper's SBS-style exchange
    and what real TPU MoE systems emit. Capacity is enforced per shard
    (standard practice; slightly different drop semantics than the global
    sort, both capacity-faithful)."""
    m = cfg.moe
    B, S, d = x.shape
    T = B * S
    E, k = m.n_experts, m.top_k
    G = _dp_groups(T)
    Tg = T // G
    xt = x.reshape(G, Tg, d)
    xt = maybe_constrain(xt, ("pod", "data"), None, None)

    logits = jnp.einsum("gtd,de->gte", xt.astype(jnp.float32),
                        params["router"])
    probs = jax.nn.softmax(logits, -1)
    sel_basis = probs + params["router_bias"] if m.router_aux_free_bias \
        else probs
    gate, expert_idx = jax.lax.top_k(sel_basis, k)            # [G, Tg, k]
    gate = jnp.take_along_axis(probs, expert_idx, axis=-1)
    gate = gate / jnp.maximum(gate.sum(-1, keepdims=True), 1e-9)

    cap = int(math.ceil(m.capacity_factor * Tg * k / E / 8.0) * 8)
    cap = min(cap, Tg * k)

    def dispatch_one(xg, eg, gg):
        flat_e = eg.reshape(-1)                               # [Tg*k]
        order = jnp.argsort(flat_e)
        se = flat_e[order]
        seg_start = jnp.searchsorted(se, jnp.arange(E))
        pos = jnp.arange(Tg * k) - seg_start[se]
        keep = pos < cap
        slot = jnp.where(keep, se * cap + pos, E * cap)
        tok = order // k
        buf = jnp.zeros((E * cap, xg.shape[-1]), xg.dtype)
        buf = buf.at[slot].set(jnp.where(keep[:, None], xg[tok], 0),
                               mode="drop")
        w = jnp.where(keep, gg.reshape(-1)[order], 0.0)
        return buf.reshape(E, cap, xg.shape[-1]), slot, tok, w

    buf, slot, tok, w = jax.vmap(dispatch_one)(xt, expert_idx, gate)
    # [G, E, cap, d] -> [E, G, cap, d]: the all-to-all
    h = buf.transpose(1, 0, 2, 3).reshape(E, G * cap, d)
    h = maybe_constrain(h, "model", ("pod", "data"), None)

    g = jnp.einsum("ecd,edf->ecf", h, params["w_gate"])
    u = jnp.einsum("ecd,edf->ecf", h, params["w_up"])
    y = jnp.einsum("ecf,efd->ecd", jax.nn.silu(g) * u, params["w_down"])
    y = maybe_constrain(y, "model", ("pod", "data"), None)

    yg = y.reshape(E, G, cap, d).transpose(1, 0, 2, 3)        # all-to-all back
    yg = maybe_constrain(yg, ("pod", "data"), "model", None, None)

    def combine_one(yb, slot_g, tok_g, w_g):
        y_flat = yb.reshape(E * cap, d)
        y_tok = jnp.take(y_flat, slot_g, axis=0, mode="fill", fill_value=0)
        out = jnp.zeros((Tg, d), y_tok.dtype)
        return out.at[tok_g].add(y_tok * w_g[:, None].astype(y_tok.dtype))

    out = jax.vmap(combine_one)(yg, slot, tok, w)
    out = maybe_constrain(out, ("pod", "data"), None, None).reshape(T, d)

    if m.n_shared:
        out = out + mlp_apply(params["shared"], xt.reshape(T, d), cfg.act)

    load = jnp.zeros((E,), jnp.float32).at[expert_idx.reshape(-1)].add(1.0) \
        / (T * k)
    return out.reshape(B, S, d), {"load": load, "dropped": 0.0}


def update_router_bias(params, load, *, rate=1e-3):
    """DeepSeek aux-loss-free balancing: nudge under-loaded experts up,
    over-loaded down (applied outside the gradient by the train loop)."""
    target = 1.0 / load.shape[-1]
    bias = params["router_bias"] + rate * jnp.sign(target - load)
    return dict(params, router_bias=bias)
