"""Logical-axis sharding context (MaxText-style, minimal).

Model code annotates activations with *logical* axis names via
``shard(x, "batch", "seq", None)``.  Outside any context this is the
identity, so the model runs on a single CPU device unchanged.  The
launch layer activates a mesh + rules mapping logical names to mesh
axes; ``shard`` then applies ``with_sharding_constraint`` so GSPMD
propagates the intended layout.
"""
from __future__ import annotations

import contextlib
import threading
from typing import Dict, Optional, Sequence, Tuple, Union

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

_state = threading.local()

AxisVal = Union[None, str, Tuple[str, ...]]


def current_mesh() -> Optional[Mesh]:
    return getattr(_state, "mesh", None)


def current_rules() -> Dict[str, AxisVal]:
    return getattr(_state, "rules", {})


@contextlib.contextmanager
def logical_axis_rules(mesh: Mesh, rules: Dict[str, AxisVal]):
    """Activate (mesh, logical->physical rules) for model tracing."""
    old_mesh = getattr(_state, "mesh", None)
    old_rules = getattr(_state, "rules", {})
    _state.mesh, _state.rules = mesh, dict(rules)
    try:
        yield
    finally:
        _state.mesh, _state.rules = old_mesh, old_rules


def logical_to_spec(axes: Sequence[Optional[str]]) -> P:
    rules = current_rules()
    return P(*[rules.get(a) if a is not None else None for a in axes])


def _manual_axes() -> frozenset:
    """Mesh axes that are Manual in the current trace context (inside a
    shard_map region) — constraints must not mention them."""
    am = jax.sharding.get_abstract_mesh()
    return frozenset(n for n, t in zip(am.axis_names, am.axis_types)
                     if t == jax.sharding.AxisType.Manual)


def shard_map(f, mesh, in_specs, out_specs, axis_names=None,
              check_vma: bool = False):
    """``jax.shard_map`` with the repo's defaults: ``axis_names`` lists
    the MANUAL mesh axes (the rest stay GSPMD-auto); None makes every
    axis manual.  The repro.dist runtime and tests/dist_checks.py go
    through this front-end."""
    kwargs = dict(mesh=mesh, in_specs=in_specs, out_specs=out_specs,
                  check_vma=check_vma)
    if axis_names is not None:
        kwargs["axis_names"] = set(axis_names)
    return jax.shard_map(f, **kwargs)


def shard(x: jax.Array, *axes: Optional[str]) -> jax.Array:
    """Constrain ``x`` to the sharding implied by logical ``axes``.
    Axes that are currently manual (we are inside a shard_map over
    them) are dropped from the constraint — the value is already
    device-local along those."""
    mesh = current_mesh()
    if mesh is None:
        return x
    if len(axes) != x.ndim:
        raise ValueError(f"shard: {len(axes)} axes for rank-{x.ndim} array")
    spec = logical_to_spec(axes)
    manual = _manual_axes()
    if manual:
        entries = []
        for e in spec:
            if e is None:
                entries.append(None)
            elif isinstance(e, str):
                entries.append(None if e in manual else e)
            else:
                kept = tuple(a for a in e if a not in manual)
                entries.append(kept if kept else None)
        spec = P(*entries)
    if all(e is None for e in spec):
        # nothing left to constrain (e.g. fully-manual shard_map body)
        return x
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))
