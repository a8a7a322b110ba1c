"""Sharding rules: one declarative object mapping model state onto the mesh.

``ShardingRules`` names the mesh axes each parallelism style uses:

* ``tp``   — tensor parallelism axis (Megatron-style weight sharding)
* ``fsdp`` — fully-sharded data parallel axis(es) for parameter storage
* ``dp``   — pure data-parallel axes (batch dimension)
* ``seq_sharding`` — Megatron-SP: shard the sequence dim of activations
* ``kv_seq_shard`` — flash-decoding: shard KV caches over the length dim

Spec assignment is *shape-driven* and divisibility-guarded so any config /
mesh combination lowers: a dimension is only sharded when the mesh axis
divides it; everything else stays replicated.  Activation constraints are
installed via :func:`activation_context` (a contextvar, so jit-traced model
code calls :func:`shard_activation` unconditionally and it no-ops outside a
context — single-host tests never touch device state).
"""

from __future__ import annotations

import contextlib
import contextvars
import dataclasses

import jax
from jax.sharding import NamedSharding, PartitionSpec as P


def make_auto_mesh(shape, axes, **kwargs):
    """``jax.make_mesh`` with every axis of type ``Auto``."""
    kwargs.setdefault("axis_types", (jax.sharding.AxisType.Auto,) * len(axes))
    return jax.make_mesh(tuple(shape), tuple(axes), **kwargs)


def _as_tuple(axes) -> tuple:
    if axes is None:
        return ()
    if isinstance(axes, (tuple, list)):
        return tuple(a for a in axes if a)
    return (axes,)


@dataclasses.dataclass
class ShardingRules:
    mesh: object
    tp: str | None = None
    fsdp: object = None          # str | tuple | None
    dp: tuple = ()
    seq_sharding: bool = False
    kv_seq_shard: bool = False

    def batch_axes(self) -> tuple:
        return _as_tuple(self.dp)

    def fsdp_axes(self) -> tuple:
        return _as_tuple(self.fsdp)

    def axis_size(self, axes) -> int:
        n = 1
        for a in _as_tuple(axes):
            n *= self.mesh.shape[a]
        return n


def _divides(rules: ShardingRules, axes, dim: int) -> bool:
    axes = _as_tuple(axes)
    return bool(axes) and dim % rules.axis_size(axes) == 0


# --------------------------------------------------------------------------
# parameters
# --------------------------------------------------------------------------

def _param_spec(shape, rules: ShardingRules) -> P:
    """TP on the innermost divisible matmul dim, FSDP on the largest
    remaining one.  Rank<2 leaves (norm scales, counts) stay replicated."""
    if len(shape) < 2:
        return P()
    entries: list = [None] * len(shape)
    if rules.tp is not None:
        for ax in (len(shape) - 1, len(shape) - 2):
            if _divides(rules, rules.tp, shape[ax]):
                entries[ax] = rules.tp
                break
    fs = rules.fsdp_axes()
    if fs:
        free = [ax for ax in range(len(shape)) if entries[ax] is None]
        free.sort(key=lambda ax: -shape[ax])
        for ax in free:
            if _divides(rules, fs, shape[ax]):
                entries[ax] = fs if len(fs) > 1 else fs[0]
                break
    return P(*entries)


def param_specs(cfg, pshapes, rules: ShardingRules):
    """PartitionSpec tree matching the parameter tree structure."""
    return jax.tree.map(lambda s: _param_spec(s.shape, rules), pshapes)


def named_shardings(cfg, params, rules: ShardingRules):
    specs = param_specs(cfg, params, rules)
    return jax.tree.map(lambda s: NamedSharding(rules.mesh, s), specs,
                        is_leaf=lambda s: isinstance(s, P))


# --------------------------------------------------------------------------
# KV caches
# --------------------------------------------------------------------------

def _cache_spec(shape, rules: ShardingRules) -> P:
    """(B, L, H, dh)-shaped entries: batch over dp, heads over tp — or the
    length dim over tp under flash-decoding.  SSM states shard batch only."""
    entries: list = [None] * len(shape)
    if shape and _divides(rules, rules.batch_axes(), shape[0]):
        ba = rules.batch_axes()
        entries[0] = ba if len(ba) > 1 else ba[0]
    if rules.tp is not None and len(shape) >= 3:
        if rules.kv_seq_shard and _divides(rules, rules.tp, shape[1]):
            entries[1] = rules.tp
        elif _divides(rules, rules.tp, shape[-2]):
            entries[-2] = rules.tp
    return P(*entries)


def cache_specs(cfg, cshapes, rules: ShardingRules):
    return jax.tree.map(lambda s: _cache_spec(s.shape, rules), cshapes)


# --------------------------------------------------------------------------
# batches & activations
# --------------------------------------------------------------------------

def batch_sharding(rules: ShardingRules) -> NamedSharding:
    ba = rules.batch_axes()
    spec = P(ba if len(ba) > 1 else (ba[0] if ba else None))
    return NamedSharding(rules.mesh, spec)


_ACTIVE: contextvars.ContextVar = contextvars.ContextVar(
    "repro_sharding_rules", default=None)


@contextlib.contextmanager
def activation_context(rules: ShardingRules | None):
    token = _ACTIVE.set(rules)
    try:
        yield
    finally:
        _ACTIVE.reset(token)


def _activation_spec(shape, kind: str, rules: ShardingRules) -> P | None:
    entries: list = [None] * len(shape)
    changed = False
    if shape and _divides(rules, rules.batch_axes(), shape[0]):
        ba = rules.batch_axes()
        entries[0] = ba if len(ba) > 1 else ba[0]
        changed = True
    if rules.tp is not None:
        if kind == "logits" and shape and _divides(rules, rules.tp, shape[-1]):
            entries[-1] = rules.tp
            changed = True
        elif kind == "residual" and rules.seq_sharding and len(shape) >= 3 \
                and _divides(rules, rules.tp, shape[1]):
            entries[1] = rules.tp       # Megatron-SP: shard the seq dim
            changed = True
        elif kind == "cache" and len(shape) >= 3:
            ax = 1 if rules.kv_seq_shard else len(shape) - 2
            if _divides(rules, rules.tp, shape[ax]):
                entries[ax] = rules.tp
                changed = True
    return P(*entries) if changed else None


def shard_activation(x, kind: str = "residual"):
    """Install a sharding constraint on an activation; no-op outside an
    :func:`activation_context` (so unit tests never need a mesh)."""
    rules = _ACTIVE.get()
    if rules is None or rules.mesh is None:
        return x
    spec = _activation_spec(x.shape, kind, rules)
    if spec is None:
        return x
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(rules.mesh, spec))
