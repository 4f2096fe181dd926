"""Logical-axis sharding rules (MaxText-style).

Model code annotates tensors with *logical* axis names
(``("batch", "seq", "d_model")``); a rules table maps logical names to mesh
axes.  Swapping the table re-shards the whole model — this is how the same
stack serves train (FSDP×TP), prefill (DP×TP) and long-context decode
(SP×TP) without touching model code.

A logical name may map to a single mesh axis, a tuple of mesh axes (the
dimension is sharded over their product), or ``None`` (replicated).
"""
from __future__ import annotations

import threading
from contextlib import contextmanager
from dataclasses import dataclass, field
from typing import Dict, Optional, Sequence, Tuple, Union

import jax
from jax.sharding import PartitionSpec as P

MeshAxes = Union[None, str, Tuple[str, ...]]


@dataclass(frozen=True)
class AxisRules:
    rules: Tuple[Tuple[str, MeshAxes], ...]

    @staticmethod
    def of(**kw: MeshAxes) -> "AxisRules":
        return AxisRules(tuple(kw.items()))

    def lookup(self, name: Optional[str]) -> MeshAxes:
        if name is None:
            return None
        for k, v in self.rules:
            if k == name:
                return v
        return None

    def spec(self, logical_axes: Sequence[Optional[str]]) -> P:
        seen = []
        out = []
        for name in logical_axes:
            axes = self.lookup(name)
            if axes is None:
                out.append(None)
                continue
            axes_t = (axes,) if isinstance(axes, str) else tuple(axes)
            # a mesh axis may appear at most once in a PartitionSpec
            axes_t = tuple(a for a in axes_t if a not in seen)
            seen.extend(axes_t)
            if not axes_t:
                out.append(None)
            elif len(axes_t) == 1:
                out.append(axes_t[0])
            else:
                out.append(axes_t)
        return P(*out)


# Default rules: FSDP over `data`, TP over `model`, DP over `pod`+`data`,
# Megatron-style sequence parallelism: the residual stream (and logits/CE)
# shard `seq` over `model` between blocks; TP regions gather seq internally.
TRAIN_RULES = AxisRules.of(
    batch=("pod", "data"),
    seq="model",
    d_model=None,
    heads="model",
    kv_heads="model",
    head_dim=None,
    ffn="model",
    experts="model",
    expert_ffn=None,
    vocab="model",
    fsdp="data",          # parameter sharding axis (ZeRO-3 style)
    window=None,
    states=None,
    cache_seq=None,
    conv=None,
)

# Decode/prefill: batch over pod+data, heads/experts over model; params keep
# the fsdp axis too — a 671B checkpoint does not fit 256 chips TP-only.
# cache_seq shards over `model`: with kv_heads < model-axis size the cache
# cannot shard by head, and a model-replicated cache made GSPMD re-gather the
# full 32k KV cache EVERY LAYER (29.3 GB/step wire on granite — §Perf Track
# 3); seq-sharding it cuts decode wire 84× and cache memory 16×.
DECODE_RULES = AxisRules.of(
    batch=("pod", "data"),
    seq=None,
    d_model=None,
    heads="model",
    kv_heads="model",
    head_dim=None,
    ffn="model",
    experts="model",
    expert_ffn=None,
    vocab="model",
    fsdp="data",
    window=None,
    states=None,
    cache_seq="model",
    conv=None,
)

# Long-context decode (batch=1): sequence parallelism — the KV/conv caches and
# attention shard their *sequence* axis over `data`, heads over `model`.
LONG_DECODE_RULES = AxisRules.of(
    batch="pod",
    seq=None,
    d_model=None,
    heads="model",
    kv_heads="model",
    head_dim=None,
    ffn="model",
    experts="model",
    expert_ffn=None,
    vocab="model",
    fsdp="data",
    window=None,
    states=None,
    cache_seq="data",
    conv=None,
)

_local = threading.local()


def current_rules() -> AxisRules:
    return getattr(_local, "rules", TRAIN_RULES)


@contextmanager
def set_rules(rules: AxisRules):
    prev = current_rules()
    _local.rules = rules
    try:
        yield
    finally:
        _local.rules = prev


def logical_spec(logical_axes: Sequence[Optional[str]]) -> P:
    return current_rules().spec(logical_axes)


def divisible_spec(spec: P, shape: Tuple[int, ...], axis_sizes: Dict[str, int]
                   ) -> P:
    """Drop mesh axes that do not divide the corresponding dim size.

    GSPMD requires exact divisibility; e.g. kv_heads=8 cannot shard over a
    model axis of 16, so the constraint silently degrades to replication for
    that dim (MaxText does the same with its `sharding_tolerance`).
    """
    entries = tuple(spec) + (None,) * (len(shape) - len(spec))
    out = []
    for dim, entry in zip(shape, entries):
        if entry is None:
            out.append(None)
            continue
        axes = (entry,) if isinstance(entry, str) else tuple(entry)
        kept, tot = [], 1
        for a in axes:
            if a not in axis_sizes:   # axis absent from this mesh (e.g. pod)
                continue
            sz = axis_sizes[a]
            if dim % (tot * sz) == 0:
                kept.append(a)
                tot *= sz
        out.append(tuple(kept) if len(kept) > 1 else
                   (kept[0] if kept else None))
    return P(*out)


def with_logical_constraint(x: jax.Array,
                            logical_axes: Sequence[Optional[str]]) -> jax.Array:
    """Annotate activation sharding; no-op outside a `jax.set_mesh` context."""
    mesh = jax.sharding.get_abstract_mesh()
    if mesh.empty:
        return x
    try:
        # inside shard_map the axes are Manual: layout is already explicit
        if any(t != jax.sharding.AxisType.Auto for t in mesh.axis_types):
            return x
    except Exception:
        return x
    if len(logical_axes) != x.ndim:
        return x
    spec = logical_spec(logical_axes)
    spec = divisible_spec(spec, x.shape, dict(mesh.shape))
    return jax.lax.with_sharding_constraint(x, spec)
