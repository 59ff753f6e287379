"""Logical-axis sharding: mesh registry + MaxText-style axis rules.

Model code names *logical* axes ("batch", "embed", "kv_seq", ...); this
module maps them onto whatever mesh is active.  Everything degrades to a
no-op when no mesh is set — ``constraint`` returns its input unchanged —
so single-host tests and the CPU container run the exact same model code
that the 256/512-chip dry-run compiles.

Key pieces:

* ``set_mesh``/``use_mesh``/``get_mesh`` — a process-global active mesh
  (``use_mesh`` is the scoped context-manager form).
* ``PARAM_RULES``/``ACT_RULES`` — mutable logical->mesh-axis dictionaries
  (parameter axes vs activation axes).  ``override_rules`` /
  ``override_param_rules`` scope an update and restore on exit.
* ``logical_spec(*names)`` — a ``PartitionSpec`` for the active mesh, with
  the "pod" data-parallel axis automatically prepended to the batch entry
  on multi-pod meshes.
* ``filter_spec(spec, shape, mesh)`` — divisibility filter: any entry whose
  mesh-axis product does not evenly divide the corresponding dim is dropped
  to ``None`` (GSPMD would otherwise reject the sharding); short specs are
  padded with ``None`` to the array rank.
* ``shardings_for`` / ``axes_to_shardings`` — pytree helpers producing
  ``NamedSharding`` trees for parameter specs and logical-axis-name trees.
"""
from __future__ import annotations

import contextlib
from typing import Any, Dict, Optional, Tuple

import jax
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

__all__ = [
    "PARAM_RULES",
    "ACT_RULES",
    "get_mesh",
    "set_mesh",
    "use_mesh",
    "logical_spec",
    "filter_spec",
    "constraint",
    "shardings_for",
    "axes_to_shardings",
    "override_rules",
    "override_param_rules",
]

#: logical parameter axis -> mesh axis (or tuple of axes, or None=replicated).
#: "embed" carries FSDP ("data"); the tensor-parallel dims ride "model".
PARAM_RULES: Dict[str, Any] = {
    "embed": "data",
    "embed_tp": None,
    "mlp": "model",
    "heads": "model",
    "kv_heads": "model",
    "head_dim": None,
    "vocab": "model",
    "experts": None,
    "state": None,
    "conv": None,
    "layers": None,
}

#: logical activation axis -> mesh axis.
ACT_RULES: Dict[str, Any] = {
    "batch": "data",
    "seq": None,
    "seq_res": None,
    "kv_seq": "model",
    "heads": "model",
    "kv_heads": "model",
    "mlp": "model",
    "vocab": "model",
}

_ACTIVE_MESH = None


def get_mesh():
    """The active mesh, or None (=> every helper becomes a passthrough)."""
    return _ACTIVE_MESH


def set_mesh(mesh) -> None:
    global _ACTIVE_MESH
    _ACTIVE_MESH = mesh


@contextlib.contextmanager
def use_mesh(mesh):
    """Scoped ``set_mesh``: restores the previous mesh on exit."""
    global _ACTIVE_MESH
    prev, _ACTIVE_MESH = _ACTIVE_MESH, mesh
    try:
        yield mesh
    finally:
        _ACTIVE_MESH = prev


def _mesh_sizes(mesh) -> Dict[str, int]:
    return dict(mesh.shape)


def _rule_entry(name: Optional[str], mesh, rules: Dict[str, Any]) -> Any:
    """Resolve one logical axis name to a spec entry under ``mesh``."""
    if name is None:
        return None
    rule = rules.get(name)
    if rule is None:
        return None
    axes: Tuple[str, ...] = rule if isinstance(rule, tuple) else (rule,)
    if (
        name == "batch"
        and mesh is not None
        and "pod" in getattr(mesh, "axis_names", ())
        and "pod" not in axes
    ):
        # multi-pod meshes carry pure data parallelism on the leading "pod"
        # axis; batch entries absorb it transparently.
        axes = ("pod",) + axes
    if mesh is not None:
        axes = tuple(a for a in axes if a in mesh.axis_names)
    if not axes:
        return None
    return axes if len(axes) > 1 else axes[0]


def logical_spec(*names: Optional[str]) -> P:
    """PartitionSpec for logical activation axes under the active mesh."""
    mesh = get_mesh()
    return P(*[_rule_entry(n, mesh, ACT_RULES) for n in names])


def filter_spec(spec: P, shape: Tuple[int, ...], mesh) -> P:
    """Drop spec entries that do not evenly divide the array shape."""
    sizes = _mesh_sizes(mesh)
    entries = list(spec) + [None] * (len(shape) - len(spec))
    out = []
    for dim, entry in zip(shape, entries):
        if entry is None:
            out.append(None)
            continue
        axes = entry if isinstance(entry, tuple) else (entry,)
        if all(a in sizes for a in axes):
            prod = 1
            for a in axes:
                prod *= sizes[a]
            if prod and dim % prod == 0:
                out.append(entry)
                continue
        out.append(None)
    return P(*out)


def constraint(x, *names: Optional[str]):
    """``with_sharding_constraint`` by logical axis names; identity when no
    mesh is active (the single-host / unit-test path)."""
    mesh = get_mesh()
    if mesh is None:
        return x
    spec = filter_spec(logical_spec(*names), x.shape, mesh)
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


def _is_spec(x) -> bool:
    return isinstance(x, P)


def shardings_for(specs: Any, abs_tree: Any, mesh) -> Any:
    """PartitionSpec tree (e.g. from ``spec_tree``) -> NamedSharding tree,
    divisibility-filtered against the matching abstract arrays."""
    return jax.tree.map(
        lambda s, a: NamedSharding(mesh, filter_spec(s, a.shape, mesh)),
        specs,
        abs_tree,
        is_leaf=_is_spec,
    )


def _is_axes_leaf(x) -> bool:
    # a leaf is a per-dim tuple of logical axis names, e.g. (None, "batch",
    # "kv_seq", None, None) — or () for scalar leaves.  Containers (dicts,
    # NamedTuples of such tuples) keep getting traversed.
    return x is None or (
        type(x) is tuple and all(e is None or isinstance(e, str) for e in x)
    )


def axes_to_shardings(axes: Any, abs_tree: Any, mesh) -> Any:
    """Tree of logical-axis-name tuples -> tree of NamedSharding."""
    abs_leaves, treedef = jax.tree.flatten(abs_tree)
    axes_leaves = jax.tree.flatten(axes, is_leaf=_is_axes_leaf)[0]
    assert len(axes_leaves) == len(abs_leaves), (len(axes_leaves), len(abs_leaves))
    out = []
    for ax, a in zip(axes_leaves, abs_leaves):
        names = () if ax is None else ax
        spec = P(*[_rule_entry(n, mesh, ACT_RULES) for n in names])
        out.append(NamedSharding(mesh, filter_spec(spec, a.shape, mesh)))
    return jax.tree.unflatten(treedef, out)


@contextlib.contextmanager
def override_rules(**updates):
    """Scoped ACT_RULES update (e.g. a shape-specific kv_seq placement)."""
    saved = dict(ACT_RULES)
    ACT_RULES.update(updates)
    try:
        yield
    finally:
        ACT_RULES.clear()
        ACT_RULES.update(saved)


@contextlib.contextmanager
def override_param_rules(**updates):
    """Scoped PARAM_RULES update (e.g. inference flips embed -> None)."""
    saved = dict(PARAM_RULES)
    PARAM_RULES.update(updates)
    try:
        yield
    finally:
        PARAM_RULES.clear()
        PARAM_RULES.update(saved)
