"""Logical-axis sharding rules (MaxText-style) -> PartitionSpec.

Physical mesh axes:
  "pod"   - inter-pod data parallelism (only on multi-pod meshes)
  "data"  - intra-pod data parallelism / FSDP
  "model" - tensor / expert / sequence parallelism

Logical axes used by the model code:
  batch       -> ("pod", "data")
  fsdp        -> ("pod", "data")   weight embed dims (ZeRO-3 style)
  model       -> "model"           heads / ff-hidden / experts / vocab
  seq_sp      -> "model"           residual-stream sequence dim when SP enabled
  None        -> replicated
"""
from __future__ import annotations

import threading
from contextlib import contextmanager
from typing import Optional, Sequence, Tuple

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

# logical name -> tuple of preferred physical axes (tried in order, all used)
DEFAULT_RULES = {
    "batch": ("pod", "data"),
    "fsdp": ("pod", "data"),
    "model": ("model",),
    "seq_sp": ("model",),
    None: (),
}

_state = threading.local()


def _ctx():
    if not hasattr(_state, "mesh"):
        _state.mesh = None
        _state.rules = DEFAULT_RULES
    return _state


@contextmanager
def use_mesh(mesh: Optional[Mesh], rules=None):
    st = _ctx()
    prev = (st.mesh, st.rules)
    st.mesh, st.rules = mesh, (rules or DEFAULT_RULES)
    try:
        yield
    finally:
        st.mesh, st.rules = prev


def current_mesh() -> Optional[Mesh]:
    return _ctx().mesh


def rules_for(cfg) -> dict:
    """Per-arch rule overrides: small models turn FSDP off (replicated
    weights kill the per-microbatch all-gathers, §Perf gemma3 hillclimb)."""
    rules = dict(DEFAULT_RULES)
    if getattr(cfg, "pure_dp", False):
        rules.update(batch=("pod", "data", "model"), fsdp=(), model=(),
                     seq_sp=())
        return rules
    if not getattr(cfg, "fsdp", True):
        rules["fsdp"] = ()
    return rules


def mesh_axis_size(mesh: Mesh, names: Sequence[str]) -> int:
    n = 1
    for a in names:
        if a in mesh.shape:
            n *= mesh.shape[a]
    return n


def resolve_spec(logical: Sequence[Optional[str]],
                 dims: Optional[Sequence[int]] = None,
                 mesh: Optional[Mesh] = None) -> P:
    """Build a PartitionSpec from logical axis names.

    Physical axes absent from the mesh are dropped; if ``dims`` is given, an
    axis is only used when the dimension is divisible by its total size
    (uneven GSPMD sharding avoided; e.g. kv_heads=4 on model=16 -> replicate).
    """
    st = _ctx()
    mesh = mesh or st.mesh
    rules = st.rules
    out = []
    for i, name in enumerate(logical):
        axes: Tuple[str, ...] = tuple(rules.get(name, ()) or ())
        if mesh is not None:
            axes = tuple(a for a in axes if a in mesh.shape)
            if dims is not None and axes:
                size = mesh_axis_size(mesh, axes)
                if size == 0 or dims[i] % size != 0:
                    # try progressively fewer axes (drop leading "pod" first)
                    while axes and (dims[i] % mesh_axis_size(mesh, axes) != 0):
                        axes = axes[1:]
        if not axes:
            out.append(None)
        elif len(axes) == 1:
            out.append(axes[0])
        else:
            out.append(tuple(axes))
    return P(*out)


def shard(x, *logical: Optional[str]):
    """with_sharding_constraint by logical axis names (no-op without a mesh)."""
    mesh = current_mesh()
    if mesh is None:
        return x
    spec = resolve_spec(logical, dims=x.shape, mesh=mesh)
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


def named_sharding(mesh: Mesh, *logical: Optional[str],
                   dims: Optional[Sequence[int]] = None) -> NamedSharding:
    return NamedSharding(mesh, resolve_spec(logical, dims=dims, mesh=mesh))
