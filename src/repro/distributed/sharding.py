"""Sharding rules: map every param/activation/optimizer leaf to a
PartitionSpec on the production mesh.

Strategy (DESIGN.md §5) — 2-D "FSDP × TP" layout:
  * Each weight matrix shards its LARGEST dim over ``model`` (tensor
    parallelism) and its second-largest over ``data`` (ZeRO-3/FSDP),
    subject to divisibility; non-divisible dims fall back to replication
    on that axis.
  * Vectors (norm scales, biases) replicate.
  * Embedding / unembedding shard vocab over ``model``, d_model over
    ``data`` (vocab is always the largest dim).
  * MoE expert tensors (E, d, f): experts over ``model`` when divisible
    (DeepSeek 160/16), else the f/d dims take the 2-D layout.
  * The ``pod`` axis is pure data parallelism: batch shards over
    ("pod", "data"); params never shard over ``pod``.
  * Activations: batch over ("pod", "data") [or ``data`` single-pod];
    d_model replicated; for long-context decode with batch=1, the KV cache /
    recurrent state shards sequence/heads instead (see kv_cache_spec).

Everything returns ``jax.sharding.PartitionSpec`` trees aligned with the
params pytree, so ``jax.jit(in_shardings=...)`` consumes them directly.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Optional, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import ArchConfig


@dataclasses.dataclass(frozen=True)
class ShardingRules:
    """Axis names on the mesh."""
    data: str = "data"
    model: str = "model"
    pod: Optional[str] = None        # present on multi-pod meshes

    @property
    def batch_axes(self):
        return (self.pod, self.data) if self.pod else self.data


def mesh_axis_size(mesh: Mesh, name) -> int:
    if name is None:
        return 1
    if isinstance(name, tuple):
        out = 1
        for n in name:
            out *= mesh_axis_size(mesh, n)
        return out
    return mesh.shape[name]


def _divisible(dim: int, n: int) -> bool:
    return n > 0 and dim % n == 0


def param_spec(path: Tuple[str, ...], shape: Tuple[int, ...],
               rules: ShardingRules, mesh: Mesh,
               fsdp: bool = True, attn_tp: bool = True) -> P:
    """2-D FSDP×TP spec for one parameter leaf.

    ``path`` is the flattened dict path (used for embedding special-casing);
    ``shape`` EXCLUDES the stacked layer axis (callers strip it).
    """
    n_model = mesh_axis_size(mesh, rules.model)
    n_data = mesh_axis_size(mesh, rules.data)
    name = "/".join(str(p) for p in path)

    if len(shape) == 0 or max(shape) == 1:
        return P()
    if len(shape) == 1:
        # vectors: shard over model when large & divisible (e.g. MoE biases)
        if shape[0] >= 8192 and _divisible(shape[0], n_model):
            return P(rules.model)
        return P()

    # embedding tables: vocab dim -> model (column-parallel unembed), d
    # replicated.  FSDP-sharding d over `data` makes XLA partial-sum the
    # LOGITS over the data axis (GBs per microbatch) instead of gathering
    # the 10s-of-MB weight shard — measured 2.5GB/mb on qwen1.5-4b.
    if "embed" in name or "unembed" in name:
        spec = [None] * len(shape)
        vocab_dim = int(np.argmax(shape))
        if _divisible(shape[vocab_dim], n_model):
            spec[vocab_dim] = rules.model
        return P(*spec)

    # MoE expert stacks: (E, d_in, d_out)
    if len(shape) == 3 and ("mlp" in name or "expert" in name):
        E = shape[0]
        spec = [None, None, None]
        leaf = str(path[-1]) if path else ""
        if _divisible(E, n_model):
            spec[0] = rules.model      # expert parallelism
            if fsdp:
                big = 1 + int(shape[2] > shape[1])
                if _divisible(shape[big], n_data):
                    spec[big] = rules.data
        else:
            # Megatron pairing inside each expert (E too ragged to shard):
            # in-projections column-parallel (f on model), out-projection
            # row-parallel — otherwise the up-matmul contracts the model-
            # sharded d and all-reduces (b,s,E,f) activations (§Perf G2).
            out_dim = 1 if leaf in ("w_down", "w_out") else 2
            in_dim = 3 - out_dim
            if _divisible(shape[out_dim], n_model):
                spec[out_dim] = rules.model
            if fsdp and _divisible(shape[in_dim], n_data):
                spec[in_dim] = rules.data
        return P(*spec)

    # other ≥3-D tensors (LoRA stacks, conv filters): largest divisible dim
    # on model, second on data
    if len(shape) != 2:
        spec = [None] * len(shape)
        order = sorted(range(len(shape)), key=lambda i: -shape[i])
        if _divisible(shape[order[0]], n_model) and shape[order[0]] >= 128:
            spec[order[0]] = rules.model
        if fsdp and len(order) > 1 and \
                _divisible(shape[order[1]], n_data) and \
                shape[order[1]] >= 128:
            spec[order[1]] = rules.data
        return P(*spec)

    # generic matrices — Megatron pairing: project-in weights are
    # column-parallel (output dim on `model`), project-out weights are
    # row-parallel (input dim on `model`), so each attention/MLP block costs
    # ONE activation all-reduce instead of one per matmul.
    leaf = str(path[-1]) if path else ""
    attn_leaf = ("attn" in name) and leaf in ("w_q", "w_k", "w_v", "w_o")
    if attn_leaf and not attn_tp:
        # heads don't divide the model axis: TP would split head_dim and
        # partial-sum the attention logits over `model` (§Perf G2) — use
        # FSDP-only sharding for the attention projections instead.
        spec = [None, None]
        if fsdp:
            io_dim = 0 if leaf != "w_o" else 1    # the d_model side
            if _divisible(shape[io_dim], n_data):
                spec[io_dim] = rules.data
        return P(*spec)
    if leaf in ("w_o", "w_down", "w_out", "w_v" if "cm" in name else "_"):
        big = 0        # row-parallel: contract dim on model
    elif leaf in ("w_q", "w_k", "w_up", "w_gate", "w_r", "w_g", "w_in",
                  "w_uq", "w_uk", "w_uv", "w_dq", "w_dkv") or \
            leaf == "w_v":
        big = 1        # column-parallel: output dim on model
    else:
        big = int(np.argmax(shape))
    small = 1 - big
    spec = [None, None]
    if _divisible(shape[big], n_model):
        spec[big] = rules.model
    if fsdp and _divisible(shape[small], n_data):
        spec[small] = rules.data
    return P(*spec)


def params_specs(params_shape: Any, rules: ShardingRules, mesh: Mesh,
                 stacked_layers: bool = True, fsdp: bool = True,
                 attn_tp: bool = True) -> Any:
    """PartitionSpec tree for the whole params pytree.

    ``params_shape`` is a pytree of ShapeDtypeStructs (or arrays); the
    leading stacked-layer axis of ``blocks/**`` leaves is never sharded.
    """
    flat, treedef = jax.tree_util.tree_flatten_with_path(params_shape)
    specs = []
    for path, leaf in flat:
        keys = tuple(getattr(k, "key", getattr(k, "idx", None))
                     for k in path)
        shape = tuple(leaf.shape)
        if stacked_layers and keys and keys[0] == "blocks" and shape:
            inner = param_spec(keys, shape[1:], rules, mesh, fsdp, attn_tp)
            specs.append(P(None, *inner))
        else:
            specs.append(param_spec(keys, shape, rules, mesh, fsdp,
                                    attn_tp))
    return jax.tree_util.tree_unflatten(treedef, specs)


def batch_spec(rules: ShardingRules) -> P:
    """Token batches: (B, S) or (B, S, d) — batch over (pod, data)."""
    return P(rules.batch_axes)


def activation_spec(rules: ShardingRules) -> P:
    return P(rules.batch_axes, None, None)


def kv_cache_spec(rules: ShardingRules, cfg: ArchConfig, mesh: Mesh,
                  batch: int, seq_shard: bool = False) -> P:
    """KV caches (L, B, S, H, d): batch over data, heads over model.
    ``seq_shard=True`` (long_500k, batch=1): shard S over data instead —
    sequence parallelism for the cache."""
    n_model = mesh_axis_size(mesh, rules.model)
    heads_ok = _divisible(cfg.num_kv_heads, n_model)
    if seq_shard:
        return P(None, None, rules.data, rules.model if heads_ok else None,
                 None)
    return P(None, rules.batch_axes, None,
             rules.model if heads_ok else None, None)


def decode_state_specs(state_shape: Any, rules: ShardingRules,
                       cfg: ArchConfig, mesh: Mesh,
                       seq_shard: bool = False) -> Any:
    """Specs for a DecodeState pytree (stacked caches + scalar index)."""
    n_model = mesh_axis_size(mesh, rules.model)
    n_data = mesh_axis_size(mesh, rules.data)

    def spec_for(leaf):
        shape = tuple(leaf.shape)
        if len(shape) == 0:
            return P()
        # all caches have a leading stacked-layer axis
        spec = [None] * len(shape)
        if len(shape) >= 2:
            batch_dim = 1
            if seq_shard and len(shape) >= 3:
                # shard the longest non-layer dim (the sequence) over data
                seq_dim = int(np.argmax(shape[1:])) + 1
                if _divisible(shape[seq_dim], n_data):
                    spec[seq_dim] = rules.data
            elif _divisible(shape[batch_dim],
                            mesh_axis_size(mesh, rules.data)
                            * mesh_axis_size(mesh, rules.pod)):
                spec[batch_dim] = rules.batch_axes
            # shard the LARGEST remaining divisible dim over model — for
            # 32k/500k KV caches that is the sequence dim (GQA kv=8 heads
            # cannot split 16 ways; sequence-parallel caches can)
            cand = sorted(range(2, len(shape)),
                          key=lambda i: -shape[i])
            for dim in cand:
                if spec[dim] is None and _divisible(shape[dim], n_model) \
                        and shape[dim] >= n_model:
                    spec[dim] = rules.model
                    break
        return P(*spec)

    return jax.tree_util.tree_map(spec_for, state_shape)


def named(mesh: Mesh, spec_tree: Any) -> Any:
    """Bind a tree of ``PartitionSpec``s to ``mesh`` as ``NamedSharding``s."""
    return jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s), spec_tree,
        is_leaf=lambda x: isinstance(x, P))
