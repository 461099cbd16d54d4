"""Logical-axis sharding policy.

Model code annotates tensors with *logical* axis names; this module maps
them onto whatever mesh is active (``jax.set_mesh``). On a bare CPU (smoke
tests) there is no mesh and every annotation is a no-op, so the exact same
model code runs single-device and on the 512-chip production mesh.

Logical → mesh-axis rules (the baseline layout; §Perf iterates on this):

  batch    → ("pod", "data") if a pod axis exists else ("data",)
  seq      → "model"   (KV-cache sequence sharding for decode / flash-decode)
  heads    → "model"   (query heads, TP)
  kv       → "model"   (KV heads, TP)
  ff       → "model"   (MLP hidden / mamba d_inner, TP)
  vocab    → "model"   (embedding / LM head, TP)
  experts  → "model"   (MoE expert parallelism)
  d / hd / conv / state / None → replicated

An annotation is silently dropped when the tensor dim is not divisible by
the mesh axis size (e.g. 24 query heads on a 16-way model axis) — the
tensor is replicated on that axis instead. This "best divisible effort"
rule is what lets one config system drive 10 heterogeneous architectures.
"""

from __future__ import annotations

import contextlib
import contextvars

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

# Which mesh axes carry the (token) batch. FL training multiplexes clients
# over "pod", so batch spans only "data" there; serving spans both.
_BATCH_AXES: contextvars.ContextVar[tuple[str, ...]] = contextvars.ContextVar(
    "repro_batch_axes", default=("data",)
)


def batch_axes() -> tuple[str, ...]:
    return _BATCH_AXES.get()


# Per-context overrides of the logical->mesh rules. Used by the 2D
# weight-stationary serving layout (§Perf hillclimb B): decode re-gathers
# FSDP-sharded weights for every token, so serving instead keeps weights
# sharded over BOTH ("model", "data") and psums the (tiny) activations.
_RULE_OVERRIDES: contextvars.ContextVar[dict] = contextvars.ContextVar(
    "repro_rule_overrides", default={}
)


@contextlib.contextmanager
def use_rules(**overrides: tuple[str, ...]):
    tok = _RULE_OVERRIDES.set(dict(overrides))
    try:
        yield
    finally:
        _RULE_OVERRIDES.reset(tok)


@contextlib.contextmanager
def use_batch_axes(*axes: str):
    tok = _BATCH_AXES.set(tuple(axes))
    try:
        yield
    finally:
        _BATCH_AXES.reset(tok)

# logical name -> candidate mesh axes (first whose size divides the dim wins
# entirely; mesh axes are not split across logical dims)
_RULES: dict[str, tuple[str, ...]] = {
    "batch": ("data",),
    "batch_pod": ("pod", "data"),  # batch big enough for both axes
    "clients": ("pod",),
    "seq": ("model",),
    "heads": ("model",),
    "kv": ("model",),
    "ff": ("model",),
    "vocab": ("model",),
    "experts": ("model",),
}


def current_mesh():
    """The mesh set by ``jax.set_mesh``, or None outside one."""
    am = jax.sharding.get_abstract_mesh()
    return None if am.empty else am


def _axis_entry(mesh, name: str | None, dim: int, used: set[str] | None = None):
    if name is None or name not in _RULES:
        return None
    sizes = dict(zip(mesh.axis_names, mesh.axis_sizes))
    over = _RULE_OVERRIDES.get()
    if name in over:
        cand: tuple[str, ...] = over[name]
    elif name == "batch":
        cand = batch_axes()
    else:
        cand = _RULES[name]
    axes = [a for a in cand if a in sizes and (used is None or a not in used)]
    if not axes:
        return None
    prod = 1
    for a in axes:
        prod *= sizes[a]
    if dim % prod != 0:
        # try single axes in order
        for a in axes:
            if dim % sizes[a] == 0:
                return a
        return None
    return tuple(axes) if len(axes) > 1 else axes[0]


def spec_for(logical: tuple[str | None, ...], shape: tuple[int, ...]) -> P:
    """PartitionSpec for a tensor given its logical axes and concrete shape."""
    mesh = current_mesh()
    if mesh is None:
        return P()
    entries = []
    used: set[str] = set()
    for name, dim in zip(logical, shape):
        e = _axis_entry(mesh, name, dim, used)
        if e is None:
            entries.append(None)
            continue
        flat = e if isinstance(e, tuple) else (e,)
        used.update(flat)
        entries.append(e)
    return P(*entries)


def shard(x: jax.Array, *logical: str | None) -> jax.Array:
    """Constrain ``x`` to the logical layout (no-op without a mesh)."""
    mesh = current_mesh()
    if mesh is None:
        return x
    assert len(logical) == x.ndim, (logical, x.shape)
    return jax.lax.with_sharding_constraint(x, spec_for(tuple(logical), x.shape))


def named_sharding(mesh: Mesh, logical: tuple[str | None, ...], shape) -> NamedSharding:
    """Concrete NamedSharding for placing inputs / params on a real mesh."""
    with jax.set_mesh(mesh):
        spec = spec_for(logical, tuple(shape))
    return NamedSharding(mesh, spec)
