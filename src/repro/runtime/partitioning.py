"""Logical-axis partitioning (maxtext-style rules, simplified).

Model code annotates activations with *logical* axis names via shard();
the runtime installs a mesh + a logical->mesh mapping. With no mesh
installed (unit tests, single host) every annotation is a no-op, so the
same model code runs anywhere. Rules are also the §Perf hillclimb lever:
the dry-run re-lowers under alternative rule sets.
"""
from __future__ import annotations

import math
from contextlib import contextmanager

import jax
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

# logical axis -> mesh axis (str | tuple | None)
DEFAULT_RULES: dict[str, object] = {
    "batch": ("pod", "data"),
    "seq": None,
    "seq_kv": None,
    "embed": None,
    "act_seq": None,          # residual-stream sequence axis (seq-parallel lever)
    "heads": "model",
    "kv_heads": "model",
    "kv_seq": None,           # decode KV cache length
    "ffn": "model",
    "vocab": "model",
    "experts": None,
    "expert_ffn": "model",
    "lru": "model",
    "ssm_heads": "model",
}

_STATE: dict = {"mesh": None, "rules": dict(DEFAULT_RULES), "off": 0}


def shard_map(f, mesh, in_specs, out_specs):
    """jax.shard_map with replication checking disabled."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs, out_specs=out_specs, check_vma=False)


def in_manual_region() -> bool:
    """True when tracing inside a Manual (shard_map) mesh region, where XLA
    cannot nest another shard_map."""
    ctx = jax.sharding.get_abstract_mesh()
    return not ctx.empty and jax.sharding.AxisType.Manual in ctx.axis_types


@contextmanager
def no_annotation():
    """Disable shard() annotations (e.g. inside shard_map bodies)."""
    _STATE["off"] += 1
    try:
        yield
    finally:
        _STATE["off"] -= 1


def set_mesh(mesh: Mesh | None, rules: dict | None = None) -> None:
    _STATE["mesh"] = mesh
    _STATE["rules"] = dict(DEFAULT_RULES, **(rules or {}))


def get_mesh() -> Mesh | None:
    return _STATE["mesh"]


def get_rules() -> dict:
    return _STATE["rules"]


@contextmanager
def mesh_rules(mesh: Mesh | None, rules: dict | None = None):
    old = (_STATE["mesh"], _STATE["rules"])
    set_mesh(mesh, rules)
    try:
        yield
    finally:
        _STATE["mesh"], _STATE["rules"] = old


def _axis_size(mesh: Mesh, ax) -> int:
    if ax is None:
        return 1
    if isinstance(ax, str):
        return mesh.shape[ax] if ax in mesh.shape else 0
    return math.prod(_axis_size(mesh, a) for a in ax)


def resolve(names: tuple, shape: tuple, mesh: Mesh | None = None, rules: dict | None = None) -> P:
    """Logical names -> PartitionSpec, dropping axes that don't divide."""
    mesh = mesh or _STATE["mesh"]
    rules = rules or _STATE["rules"]
    spec = []
    used: set = set()
    for i, nm in enumerate(names):
        ax = rules.get(nm) if nm else None
        size = _axis_size(mesh, ax) if mesh is not None else 0
        flat = (ax,) if isinstance(ax, str) else tuple(ax or ())
        if ax is None or size == 0 or shape[i] % size != 0 or any(a in used for a in flat):
            spec.append(None)
        else:
            spec.append(ax)
            used.update(flat)
    return P(*spec)


def shard(x: jax.Array, *names: str | None) -> jax.Array:
    """Annotate activation x with logical axes (no-op without a mesh)."""
    mesh = _STATE["mesh"]
    if mesh is None or _STATE["off"]:
        return x
    assert len(names) == x.ndim, f"shard(): {len(names)} names for rank-{x.ndim} array"
    spec = resolve(tuple(names), x.shape, mesh)
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


def named_sharding(*names: str, shape: tuple) -> NamedSharding | None:
    mesh = _STATE["mesh"]
    if mesh is None:
        return None
    return NamedSharding(mesh, resolve(tuple(names), shape, mesh))
