"""Logical-axis -> mesh-axis mapping with divisibility fallback.

Baseline scheme ("fsdp2d"): parameters are 2-D sharded — d_model-like dims
over the "data" axis (ZeRO-3 style, weights allgathered per layer by XLA)
and output-feature dims (heads/ff/vocab/experts) over the "model" axis.
Activations shard batch over ("pod","data"); decode KV caches shard the
sequence dim over "model" (flash-decoding style partial softmax).

A dim is only sharded if divisible by the mesh-axis size — otherwise it
falls back to replication (`maybe_shard`), which keeps every one of the 10
assigned archs compilable on the fixed 16x16 production mesh.
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

# logical axis -> preferred mesh axis (baseline)
BASE_RULES: Dict[str, Any] = {
    "embed": "data",
    "vocab": "model",
    "heads": "model",
    "heads_flat": "model",   # baseline: shard the flat dim anyway
    "kv": "model",
    "kv_flat": "model",
    "ff": "model",
    "experts": "model",
    "layers": None,
    "state": None,
}

# Megatron-style tensor-parallel COMPUTE rules (perf hillclimb SPerf-A):
# weights are not sharded on the contraction ("embed") dim during compute,
# so forward/backward are local column/row-parallel matmuls with ONE psum
# per attn/mlp block. Optimizer state stays 2-D sharded ("storage" rules);
# the train step gathers bf16 weights once per step (the transpose of that
# gather is a reduce-scatter, which is exactly ZeRO-3 gradient flow).
TP_RULES = dict(BASE_RULES)
TP_RULES["embed"] = None
# head counts not divisible by the model axis: replicate the attention
# weights during compute (local attention, zero resharding) instead of
# flat-dim sharding them (SPerf iteration 2)
TP_RULES["heads_flat"] = None
TP_RULES["kv_flat"] = None

CP_RULES = dict(TP_RULES)
CP_RULES["heads_flat"] = "model"   # shard projections; CP handles attention
CP_RULES["kv_flat"] = "model"

PRESETS = {
    "baseline": {"storage": BASE_RULES, "compute": None},
    "tp": {"storage": BASE_RULES, "compute": TP_RULES},
    # SPerf-B: TP compute + int8 KV cache for memory-bound decode
    "serve8": {"storage": BASE_RULES, "compute": TP_RULES, "kv_int8": True},
    # SPerf-B iteration 2: int8 KV cache alone (baseline sharding) — the
    # TP-compute serve preset regressed decode collectives (see SPerf log)
    "kv8": {"storage": BASE_RULES, "compute": None, "kv_int8": True},
    # SPerf-A iteration 3: flat-sharded attention weights + context-parallel
    # attention activations (GQA KV allgather instead of reshape psums)
    "cp": {"storage": BASE_RULES, "compute": CP_RULES,
           "context_parallel": True},
}


def axis_size(mesh: Mesh, name: Optional[str]) -> int:
    if name is None:
        return 1
    if isinstance(name, tuple):
        return int(np.prod([mesh.shape[n] for n in name]))
    return mesh.shape[name]


def maybe(mesh: Mesh, dim: int, name) -> Optional[Any]:
    """Return the mesh axis if `dim` divides evenly, else None."""
    if name is None or dim <= 1:
        return None
    if dim % axis_size(mesh, name) == 0:
        return name
    return None


# Flat (n_heads*head_dim)-style logical dims. Sharding one of these is only
# safe when every device slice covers WHOLE heads: if the shard boundary
# falls inside a head, the rotary embedding's half-split (slice + concat on
# the head_dim axis of the reshaped (…, H, D) tensor) is miscompiled by the
# XLA SPMD partitioner (observed on jax 0.4.37 CPU: k values off by O(1)
# and einsum reductions inflated by exactly the model-axis size — see
# tests/test_sharding.py::test_flat_head_sharding_alignment for the
# minimal reproducer). `spec_for(..., head_dim=…)` therefore falls back to
# replication when (dim // axis_size) % head_dim != 0.
HEAD_FLAT_AXES = ("heads", "heads_flat", "kv", "kv_flat")


def spec_for(mesh: Mesh, shape: Tuple[int, ...], axes: Tuple,
             rules: Dict[str, Any] = BASE_RULES,
             head_dim: Optional[int] = None) -> P:
    used = set()
    out = []
    for dim, logical in zip(shape, axes):
        want = rules.get(logical) if logical else None
        got = maybe(mesh, dim, want)
        if (got is not None and head_dim and logical in HEAD_FLAT_AXES
                and (dim // axis_size(mesh, got)) % head_dim != 0):
            got = None          # shard would split a head: replicate
        if got is not None:
            flat = got if isinstance(got, tuple) else (got,)
            if any(a in used for a in flat):
                got = None
            else:
                used.update(flat)
        out.append(got)
    return P(*out)


def param_shardings(mesh: Mesh, logical_tree, shape_tree,
                    rules: Dict[str, Any] = BASE_RULES,
                    head_dim: Optional[int] = None):
    """Map ParamTable.logical_axes() + shapes() -> NamedSharding pytree."""
    def one(axes, sds):
        return NamedSharding(mesh, spec_for(mesh, sds.shape, axes, rules,
                                            head_dim=head_dim))
    return jax.tree.map(one, logical_tree, shape_tree,
                        is_leaf=lambda x: isinstance(x, tuple))


def batch_axes(mesh: Mesh) -> Tuple[str, ...]:
    return tuple(a for a in ("pod", "data") if a in mesh.shape)


def data_sharding(mesh: Mesh, batch: int, ndim: int,
                  seq_axis_dim: Optional[int] = None,
                  seq_len: int = 0) -> NamedSharding:
    """Batch-sharded activation/input sharding with divisibility fallback."""
    ba = batch_axes(mesh)
    first = ba if batch % axis_size(mesh, ba) == 0 else (
        ("data",) if batch % mesh.shape.get("data", 1) == 0 else None)
    spec = [first if first else None] + [None] * (ndim - 1)
    if seq_axis_dim is not None and seq_len and \
            seq_len % mesh.shape.get("model", 1) == 0:
        spec[seq_axis_dim] = "model"
    return NamedSharding(mesh, P(*spec))


def cache_shardings(mesh: Mesh, cache_tree):
    """Decode-cache shardings: batch over (pod,data); seq dim over model."""
    def one(sds):
        shp = sds.shape
        if len(shp) == 5:      # (L,B,W,KV,D) stacked kv cache
            b = maybe(mesh, shp[1], batch_axes(mesh)) or \
                maybe(mesh, shp[1], "data")
            s = maybe(mesh, shp[2], "model")
            return NamedSharding(mesh, P(None, b, s, None, None))
        if len(shp) == 4:      # per-layer (B,W,KV,D) hybrid cache
            b = maybe(mesh, shp[0], batch_axes(mesh)) or \
                maybe(mesh, shp[0], "data")
            s = maybe(mesh, shp[1], "model")
            return NamedSharding(mesh, P(b, s, None, None))
        if len(shp) == 2:      # (B,W) pos
            b = maybe(mesh, shp[0], batch_axes(mesh)) or \
                maybe(mesh, shp[0], "data")
            s = maybe(mesh, shp[1], "model")
            return NamedSharding(mesh, P(b, s))
        if len(shp) == 3:      # (L,B,d) rwkv shift carries
            b = maybe(mesh, shp[1], batch_axes(mesh)) or \
                maybe(mesh, shp[1], "data")
            return NamedSharding(mesh, P(None, b, None))
        # (L,B,H,D,N) recurrent states
        b = maybe(mesh, shp[1], batch_axes(mesh)) or \
            maybe(mesh, shp[1], "data")
        return NamedSharding(mesh, P(None, b, *([None] * (len(shp) - 2))))
    return jax.tree.map(one, cache_tree)


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


def shard_leading_axis(tree, n_leading: int, axis_name: str = "shard",
                       max_devices: Optional[int] = None):
    """SPMD-shard the leading axis of every array in `tree` over devices.

    For programs whose leading-axis slices are fully independent (ensemble
    members in `training.fit_ensemble`, islands in `islands.run_islands`)
    sharding the leading axis runs the slices in parallel with ZERO
    cross-device communication. Integer programs (the island rank kernel)
    stay bit-identical to the unsharded run; a float program's slices are
    compiled at the per-device shape, and on a TPU their last bits can
    depend on that shape. Uses the largest device prefix whose size divides
    `n_leading` (capped at `max_devices` when given); returns `tree`
    unchanged when that prefix is a single device.
    """
    devs = jax.devices()
    if max_devices is not None:
        devs = devs[:max(1, int(max_devices))]
    k = 0
    for d in range(min(len(devs), n_leading), 0, -1):
        if n_leading % d == 0:
            k = d
            break
    if k <= 1:
        return tree
    mesh = Mesh(np.asarray(devs[:k]), (axis_name,))

    def one(a):
        spec = P(*((axis_name,) + (None,) * (a.ndim - 1)))
        return jax.device_put(a, NamedSharding(mesh, spec))
    return jax.tree.map(one, tree)


def data_parallel_mesh(min_devices: int = 1) -> Optional[Mesh]:
    """1-D ("data",) mesh over all local devices, for batch-axis sharding
    of the GNN training path (repro.core.training). Returns None when
    fewer than `min_devices` devices exist — callers then skip sharding.
    """
    devs = jax.devices()
    if len(devs) < min_devices:
        return None
    return Mesh(np.asarray(devs), ("data",))
