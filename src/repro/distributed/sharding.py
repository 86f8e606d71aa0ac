"""Sharding plans (NamedSharding PartitionSpec trees) per architecture family.

Axis roles on the production mesh (launch/mesh.py):
  "pod"   — outermost data parallelism across pods (multi-pod mesh only)
  "data"  — data parallelism + FSDP/ZeRO shard axis within a pod
  "model" — tensor parallelism (attention heads / FFN width / experts /
            embedding-table rows / KV-cache sequence for decode)

LM plan (Megatron TP x FSDP hybrid):
  activations:   batch over (pod, data)
  attn weights:  [L, D, H*hd] -> (None, data, model); wo transposed
  mlp weights:   w1/w3 (None, data, model); w2 (None, model, data)
  MoE experts:   [L, E, D, F] -> (None, model, data, None)  (EP + FSDP)
  embed/head:    d_model or vocab over model; replicated over data
  optimizer m/v: same specs as their parameters (ZeRO: the FSDP axis already
                 shards them with the weights)
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


def flat_axes(multi_pod: bool):
    """All mesh axes, for flattened node/edge sharding (GNN/pagerank)."""
    return ("pod", "data", "model") if multi_pod else ("data", "model")


def batch_axes(multi_pod: bool):
    return ("pod", "data") if multi_pod else ("data",)


def _current_mesh():
    """The mesh set by `jax.set_mesh`, or None outside one."""
    am = jax.sharding.get_abstract_mesh()
    return None if am.empty else am


def maybe_shard(x, *spec):
    """with_sharding_constraint iff a mesh with these axes is active
    (no-op in single-device smoke tests)."""
    mesh = _current_mesh()
    if mesh is None:
        return x
    names = set(mesh.axis_names)
    flat = [a for s in spec for a in ((s,) if not isinstance(s, tuple) else s)
            if s is not None]
    if not all(a in names for a in flat):
        return x
    return jax.lax.with_sharding_constraint(x, P(*spec))


def shard_activation(x, *roles):
    """Role-based activation constraint; resolves axis names from whatever
    mesh is active, so model code stays mesh-shape agnostic.

    roles per dim: "batch" -> (pod, data) axes; "tp" -> model axis;
    "flat" -> every mesh axis (node/edge sharding); None -> unsharded.
    No-op without a mesh (smoke tests) or when the dim size does not divide
    the axis size (e.g. 24 heads on a 16-way axis is left to the partitioner
    rather than forcing padding).
    """
    mesh = _current_mesh()
    if mesh is None:
        return x
    names = mesh.axis_names
    sizes = dict(zip(names, mesh.axis_sizes))
    batch = tuple(a for a in ("pod", "data") if a in names)
    flat = tuple(a for a in ("pod", "data", "model") if a in names)
    spec = []
    for dim, role in enumerate(roles):
        if role == "batch" and batch:
            k = 1
            for a in batch:
                k *= sizes[a]
            spec.append(batch if x.shape[dim] % k == 0 else None)
        elif role == "flat" and flat:
            k = 1
            for a in flat:
                k *= sizes[a]
            spec.append(flat if x.shape[dim] % k == 0 else None)
        elif role == "tp" and "model" in names:
            spec.append("model" if x.shape[dim] % sizes["model"] == 0 else None)
        else:
            spec.append(None)
    if all(s is None for s in spec):
        return x
    return jax.lax.with_sharding_constraint(x, P(*spec))


# ------------------------------------------------------------------- LM ----

def lm_param_specs(cfg, multi_pod: bool):
    """PartitionSpec tree matching models.transformer.init_params(cfg)."""
    fsdp = "data"
    tp = "model"
    layer = {
        "ln1": P(None, None),
        "ln2": P(None, None),
        "wq": P(None, fsdp, tp),
        "wk": P(None, fsdp, tp),
        "wv": P(None, fsdp, tp),
        "wo": P(None, tp, fsdp),
    }
    if cfg.qkv_bias:
        layer["bq"] = P(None, tp)
        layer["bk"] = P(None, tp)
        layer["bv"] = P(None, tp)
    if cfg.moe:
        if cfg.moe.n_experts % 16 == 0:
            # expert parallelism: experts tile the model axis
            layer["moe"] = {
                "router": P(None, fsdp, None),
                "w1": P(None, tp, fsdp, None),
                "w3": P(None, tp, fsdp, None),
                "w2": P(None, tp, None, fsdp),
            }
        else:
            # expert count does not tile the 16-way axis (granite: 40e) ->
            # intra-expert tensor parallelism over d_ff instead
            layer["moe"] = {
                "router": P(None, fsdp, None),
                "w1": P(None, None, fsdp, tp),
                "w3": P(None, None, fsdp, tp),
                "w2": P(None, None, tp, fsdp),
            }
    else:
        layer["w1"] = P(None, fsdp, tp)
        layer["w3"] = P(None, fsdp, tp)
        layer["w2"] = P(None, tp, fsdp)
    return {
        # vocab-sharded: GSPMD lowers the token gather to masked local
        # lookups + all-reduce (Megatron vocab-parallel embedding); sharding
        # d_model instead trips an XLA repartition bug inside the microbatch
        # loop (b/433785288) — see EXPERIMENTS.md §Perf iteration log.
        "embed": P(tp, None),
        "layers": layer,
        "final_ln": P(None),
        "lm_head": P(None, tp),
    }


def lm_opt_specs(param_specs):
    """AdamW state: m and v mirror the parameter sharding; step replicated."""
    return {
        "step": P(),
        "m": jax.tree.map(lambda s: s, param_specs),
        "v": jax.tree.map(lambda s: s, param_specs),
    }


def lm_batch_specs(multi_pod: bool):
    return {"tokens": P(batch_axes(multi_pod), None)}


def lm_cache_spec(multi_pod: bool):
    """KV cache [L, B, S, Hkv, Dh]: batch over data, sequence over model.
    Sequence sharding makes decode attention sequence-parallel: XLA lowers
    the softmax over the sharded S axis to the two-pass max/sum all-reduce
    and psums the weighted-value contraction — flash-decoding's split-K on
    the mesh."""
    return P(None, batch_axes(multi_pod), "model", None, None)


# ------------------------------------------------------------------ GNN ----

def gnn_batch_specs(batch_tree, multi_pod: bool):
    """Node/edge arrays sharded over all axes on dim 0 when the size tiles
    the mesh; small non-divisible arrays (e.g. the 40,962-node icosphere)
    stay replicated. Scalars replicated."""
    ax = flat_axes(multi_pod)
    n_dev = 512 if multi_pod else 256

    def spec_for(leaf):
        if leaf.ndim == 0 or leaf.shape[0] % n_dev:
            return P(*([None] * leaf.ndim))
        return P(ax, *([None] * (leaf.ndim - 1)))

    return jax.tree.map(spec_for, batch_tree)


def replicated_specs(tree):
    return jax.tree.map(lambda leaf: P(*([None] * getattr(leaf, "ndim", 0))),
                        tree)


# ----------------------------------------------------------------- DLRM ----

def dlrm_param_specs(abstract_params, multi_pod: bool):
    """Combined embedding table row-sharded over model (the RM2 layout);
    MLPs replicated (they are tiny)."""
    def spec(path, leaf):
        if any(getattr(p, "key", None) == "table" for p in path):
            return P("model", None)
        return P(*([None] * leaf.ndim))
    return jax.tree_util.tree_map_with_path(spec, abstract_params)
