"""Mesh context threaded through model code.

Model forward functions are mesh-agnostic except for explicitly
communication-aware blocks (MoE expert parallelism) and two choices made
from the mesh (a long prefill's tokens per pass, the dense decode
cache's layout pin).  Those consult the active mesh set by the step
builders / launchers via ``use_mesh``.
"""
from __future__ import annotations

import threading
from contextlib import contextmanager
from typing import Optional, Tuple

import jax

_STATE = threading.local()


def set_mesh(mesh) -> None:
    _STATE.mesh = mesh


def get_mesh():
    return getattr(_STATE, "mesh", None)


@contextmanager
def use_mesh(mesh):
    prev = get_mesh()
    set_mesh(mesh)
    try:
        yield mesh
    finally:
        set_mesh(prev)


def set_unroll(flag: bool) -> None:
    """Counting mode: unroll inner (chunk) loops so XLA cost_analysis sees
    every iteration (it counts a while loop's body once)."""
    _STATE.unroll = bool(flag)


def get_unroll() -> bool:
    return getattr(_STATE, "unroll", False)


@contextmanager
def use_unroll(flag: bool = True):
    prev = get_unroll()
    set_unroll(flag)
    try:
        yield
    finally:
        set_unroll(prev)


def batch_axes(mesh) -> Tuple[str, ...]:
    """Mesh axes over which the global batch is sharded."""
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


def dp_size(mesh) -> int:
    out = 1
    for a in batch_axes(mesh):
        out *= mesh.shape[a]
    return out
