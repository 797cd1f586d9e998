"""Mesh-aware activation sharding hints.

``with_sharding_constraint`` pins where GSPMD would otherwise guess (and,
per the dry-run HLO analysis, guess badly: the 8-head gemma2 attention
reshape triggered thousands of collective-permutes / all-to-alls per step —
EXPERIMENTS.md §Perf). Hints are NO-OPS when no mesh is active (smoke tests,
single-device examples) or when a requested axis doesn't exist / doesn't
divide the dimension, so model code stays mesh-agnostic.
"""
from __future__ import annotations

from typing import Optional, Sequence, Union

import jax
from jax.sharding import PartitionSpec as P

AxisEntry = Union[None, str, Sequence[str]]


def _active_mesh():
    """The mesh set by ``jax.set_mesh``, or None (hints become no-ops)."""
    mesh = jax.sharding.get_abstract_mesh()
    return None if mesh.empty else mesh


def _axis_size(mesh, entry: AxisEntry) -> int:
    if entry is None:
        return 1
    names = (entry,) if isinstance(entry, str) else tuple(entry)
    size = 1
    for n in names:
        size *= mesh.shape[n]
    return size


def hint(x, *entries: AxisEntry):
    """Constrain ``x`` to P(*entries), dropping entries whose axes are absent
    or don't divide the corresponding dimension."""
    mesh = _active_mesh()
    if mesh is None or x.ndim != len(entries):
        return x
    cleaned = []
    for dim, e in zip(x.shape, entries):
        if e is None:
            cleaned.append(None)
            continue
        names = (e,) if isinstance(e, str) else tuple(e)
        if not all(n in mesh.axis_names for n in names):
            cleaned.append(None)
            continue
        if dim % _axis_size(mesh, e) != 0 or dim == 0:
            cleaned.append(None)
            continue
        cleaned.append(e if isinstance(e, str) else tuple(names))
    if all(c is None for c in cleaned):
        return x
    return jax.lax.with_sharding_constraint(x, P(*cleaned))


def hint_heads(x, *, batch_axes: AxisEntry = "data", model_axis: str = "model"):
    """Shard a [B, S, H, hd] tensor over heads when the head count divides the
    model axis. Do NOT fall back to sharding head_dim: hd is the contraction
    dim of the q·k einsum, and pinning it forces a partial-sum all-reduce per
    attention block — measured as a 16x collective regression on gemma2
    (8 heads) and arctic (56 heads); see EXPERIMENTS.md §Perf iteration 1."""
    mesh = _active_mesh()
    if mesh is None or x.ndim != 4:
        return x
    model_size = mesh.shape.get(model_axis, 1)
    H = x.shape[2]
    if H % model_size == 0:
        return hint(x, batch_axes, None, model_axis, None)
    return x
