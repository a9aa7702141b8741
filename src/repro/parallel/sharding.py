"""Logical-axis sharding: t5x-style logical→mesh axis rules.

Model code annotates activations with *logical* axis names
(``constrain(x, ("batch", "seq", "embed"))``). The launcher installs a rule
set mapping logical names to mesh axes; outside a mesh context every
annotation is a no-op so the same model code runs in single-device tests.
"""
from __future__ import annotations

import contextlib
import threading
from typing import Dict, Optional, Sequence, Tuple, Union

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

MeshAxes = Union[None, str, Tuple[str, ...]]

# Default rules for the production mesh (data, model[, pod]).
# "batch" spans the pure-DP axes; "expert"/"heads"/"mlp"/"vocab" use TP axis.
DEFAULT_RULES: Dict[str, MeshAxes] = {
    "batch": ("pod", "data"),
    "seq": None,
    "seq_shard": "data",      # sequence parallelism for long-context decode
    "embed": None,
    "heads": "model",
    "kv_heads": "model",
    "head_dim": None,
    "mlp": "model",
    "vocab": "model",
    "expert": "model",
    "expert_cap": None,
    "ssm_inner": "model",
    "ssm_state": None,
    "conv_ch": "model",
    "stack": None,            # scan-over-layers leading axis
}

_local = threading.local()


def _state():
    if not hasattr(_local, "rules"):
        _local.rules = None
        _local.mesh = None
    return _local


@contextlib.contextmanager
def axis_rules(rules: Dict[str, MeshAxes], mesh: Optional[Mesh] = None):
    st = _state()
    prev = (st.rules, st.mesh)
    st.rules, st.mesh = rules, mesh
    try:
        yield
    finally:
        st.rules, st.mesh = prev


def current_mesh() -> Optional[Mesh]:
    """The mesh that the innermost ``axis_rules`` context names, if any."""
    return _state().mesh


def resolve_spec(logical: Sequence[Optional[str]],
                 rules: Optional[Dict[str, MeshAxes]] = None,
                 mesh: Optional[Mesh] = None) -> P:
    """Map logical axis names to a PartitionSpec valid for `mesh`."""
    st = _state()
    rules = rules if rules is not None else (st.rules or DEFAULT_RULES)
    mesh = mesh if mesh is not None else current_mesh()
    mesh_axes = set(mesh.axis_names) if mesh is not None else None
    out, used = [], set()
    for name in logical:
        axes = rules.get(name) if name else None
        if axes is None:
            out.append(None)
            continue
        if isinstance(axes, str):
            axes = (axes,)
        # drop axes missing from the mesh (e.g. "pod" on single-pod) or reused
        axes = tuple(a for a in axes
                     if (mesh_axes is None or a in mesh_axes) and a not in used)
        used.update(axes)
        if not axes:
            out.append(None)
        elif len(axes) == 1:
            out.append(axes[0])
        else:
            out.append(axes)
    # trim trailing Nones for cleanliness
    while out and out[-1] is None:
        out.pop()
    return P(*out)


def constrain(x, logical: Sequence[Optional[str]]):
    """Sharding-constrain activation `x`; no-op outside a mesh context.
    Axes that don't divide their dim evenly are dropped (uneven constraints
    are legal but confuse SPMD propagation into expensive reshards)."""
    mesh = current_mesh()
    if mesh is None or _state().rules is None:
        return x
    spec = resolve_spec(logical, mesh=mesh)
    dims = list(spec) + [None] * (x.ndim - len(spec))
    out = []
    for dim_size, axes in zip(x.shape, dims):
        if axes is None:
            out.append(None)
            continue
        tup = (axes,) if isinstance(axes, str) else tuple(axes)
        n = 1
        for a in tup:
            n *= mesh.shape[a]
        out.append(axes if dim_size % n == 0 else None)
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, P(*out)))


def named_sharding(mesh: Mesh, *logical: Optional[str]) -> NamedSharding:
    return NamedSharding(mesh, resolve_spec(logical, mesh=mesh))
