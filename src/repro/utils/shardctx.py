"""Mesh context for intra-model sharding constraints.

Model code calls ``maybe_shard(x, spec_entries...)``; when a mesh has been
installed (launch/dryrun path) this becomes a ``with_sharding_constraint``
with divisibility-sanitized entries, otherwise it is a no-op (CPU smoke
tests run on 1 device with no mesh).
"""
from __future__ import annotations

import contextlib
import threading
from typing import Any, Optional

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

_state = threading.local()


def current_mesh():
    return getattr(_state, "mesh", None)


@contextlib.contextmanager
def use_mesh(mesh):
    prev = current_mesh()
    _state.mesh = mesh
    try:
        with mesh:
            yield mesh
    finally:
        _state.mesh = prev


def _sanitize(shape, entries, mesh):
    out = []
    for size, ax in zip(shape, entries):
        if ax is None:
            out.append(None)
            continue
        axes = ax if isinstance(ax, tuple) else (ax,)
        if not all(a in mesh.shape for a in axes):
            out.append(None)
            continue
        n = 1
        for a in axes:
            n *= mesh.shape[a]
        out.append(ax if size % n == 0 else None)
    return P(*out)


def maybe_shard(x, *entries):
    """Apply a sanitized sharding constraint if a mesh is installed."""
    mesh = current_mesh()
    if mesh is None:
        return x
    entries = entries + (None,) * (x.ndim - len(entries))
    spec = _sanitize(x.shape, entries, mesh)
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


def batch_axis():
    """Logical batch axes for the current mesh ('pod','data') or ('data',)."""
    mesh = current_mesh()
    if mesh is not None and "pod" in mesh.shape:
        return ("pod", "data")
    return ("data",)
