"""Logical-axis -> mesh-axis sharding rules for the production meshes.

Single pod  (data=16, model=16):
  - 'model' carries tensor parallelism: attention heads, FFN hidden, vocab,
    experts (expert parallelism), mamba inner channels;
  - 'data' carries batch DP + FSDP (ZeRO-3 parameter sharding on the embed
    dim of every weight matrix) — grads reduce-scatter over 'data'.
Multi pod  (pod=2, data=16, model=16):
  - batch and FSDP extend over ('pod', 'data') — 32-way ZeRO-3, which is
    what makes llama3-405b's optimizer state fit per chip (DESIGN.md §2);
  - the pod axis only ever carries DP/FSDP traffic (DCN-friendly), never TP.

KV caches: batch over DP axes, sequence over 'model' (flash-decoding style
partial-KV attention; XLA inserts the softmax partial reductions).
"""
from __future__ import annotations

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

LOGICAL_RULES = {
    "vocab": "model",
    "heads": "model",
    "kv_heads": None,        # kv heads (8) don't divide model=16: replicate
    "ff": "model",
    "ff_expert": None,
    "experts": "model",
    "inner": "model",        # mamba expanded channels
    "embed": "data",         # FSDP / ZeRO-3
    "lora": None,
    "qkv": None,
    "frontend": None,
    "layers": None,
    "batch": "data",
    "kv_seq": "model",
    "seq": None,
}

MULTIPOD_RULES = dict(LOGICAL_RULES, embed=("pod", "data"),
                      batch=("pod", "data"))


def rules_for(mesh: Mesh) -> dict:
    return MULTIPOD_RULES if "pod" in mesh.axis_names else LOGICAL_RULES


def logical_to_spec(axes, rules, shape=None) -> P:
    """Map a tuple of logical axis names to a PartitionSpec. A mesh axis may
    appear at most once per spec: repeats (e.g. ('embed','embed') weights)
    keep only the first occurrence and replicate the rest."""
    spec, used = [], set()
    for ax in axes:
        m = rules.get(ax) if ax is not None else None
        names = (m,) if isinstance(m, str) else tuple(m or ())
        if any(n in used for n in names):
            m = None
            names = ()
        used.update(names)
        spec.append(m)
    return P(*spec)


def _divides(shape_dim: int, mesh: Mesh, mesh_axes) -> bool:
    if mesh_axes is None:
        return True
    if isinstance(mesh_axes, str):
        mesh_axes = (mesh_axes,)
    n = 1
    for a in mesh_axes:
        n *= mesh.shape[a]
    return shape_dim % n == 0


def spec_tree(logical_tree, rules):
    """Pytree of logical-axis tuples -> pytree of PartitionSpec."""
    return jax.tree.map(lambda axes: logical_to_spec(axes, rules),
                        logical_tree,
                        is_leaf=lambda x: isinstance(x, tuple))


def param_shardings(mesh: Mesh, logical_tree, shape_tree=None):
    """NamedShardings for a logical-axes pytree. If shape_tree (of
    ShapeDtypeStruct/arrays) is given, any mesh axis that does not divide
    its dim is dropped (replicated) — e.g. kv_heads=8 on model=16, or odd
    vocab sizes stay safely shardable via jit's auto-padding for the last
    dim only when divisible; otherwise replicate."""
    specs = spec_tree(logical_tree, rules_for(mesh))
    if shape_tree is not None:
        def fix(spec, leaf):
            parts = []
            for i, m in enumerate(spec):
                ok = i < len(leaf.shape) and _divides(leaf.shape[i], mesh, m)
                parts.append(m if ok else None)
            return P(*parts)
        specs = jax.tree.map(fix, specs, shape_tree,
                             is_leaf=lambda x: isinstance(x, P))
    return jax.tree.map(lambda s: NamedSharding(mesh, s), specs,
                        is_leaf=lambda x: isinstance(x, P))


def batch_spec(mesh: Mesh, *, with_frontend=False, enc_dec=False) -> dict:
    rules = rules_for(mesh)
    b = rules["batch"]
    out = {"tokens": P(b, None), "labels": P(b, None)}
    if with_frontend:
        out["frontend"] = P(b, None, None)
    if enc_dec:
        out["memory"] = P(b, None, None)
    return out


def cache_shardings(mesh: Mesh, cache_logical, cache_shapes):
    return param_shardings(mesh, cache_logical, cache_shapes)


def constrain_gathered(params_tree, logical_tree):
    """with_sharding_constraint that keeps tensor-parallel axes but drops the
    FSDP ('embed') mapping — materializes the per-layer weight all-gather
    (the FSDP dataflow) instead of GSPMD's activation-partial all-reduces."""
    am = jax.sharding.get_abstract_mesh()
    if am.empty:
        return params_tree
    rules = dict(MULTIPOD_RULES if "pod" in am.axis_names else LOGICAL_RULES)
    rules["embed"] = None

    def fix(p, axes):
        spec = logical_to_spec(axes, rules)
        parts = []
        for dim, m in zip(p.shape, spec):
            ms = (m,) if isinstance(m, str) else tuple(m or ())
            ms = tuple(a for a in ms if a in am.axis_names)
            n = 1
            for a in ms:
                n *= am.shape[a]
            parts.append(m if (ms and n > 1 and dim % n == 0) else None)
        if len(parts) < p.ndim:
            parts += [None] * (p.ndim - len(parts))
        return jax.lax.with_sharding_constraint(p, P(*parts[:p.ndim]))

    # params' array leaves pair with logical_tree's tuple "subtrees" via the
    # tree-prefix rule, so each fix() call sees (array, axes-tuple)
    return jax.tree.map(fix, params_tree, logical_tree)


def maybe_constrain(x, *mesh_axes):
    """with_sharding_constraint that degrades to a no-op when no ambient
    mesh is set (CPU tests) or an axis doesn't exist / divide.

    mesh_axes: one mesh-axis name (or tuple of names, or None) per dim.
    """
    am = jax.sharding.get_abstract_mesh()
    if am.empty:
        return x
    spec = []
    for dim, ax in zip(x.shape, mesh_axes):
        axes = (ax,) if isinstance(ax, str) else (ax or ())
        # drop axes absent from the ambient mesh (e.g. 'pod' on single-pod)
        axes = tuple(a for a in axes if a in am.axis_names)
        n = 1
        for a in axes:
            n *= am.shape[a]
        ok = n > 1 and dim % n == 0
        spec.append((axes if len(axes) > 1 else axes[0]) if ok else None)
    if all(s is None for s in spec):
        return x
    return jax.lax.with_sharding_constraint(x, P(*spec))
