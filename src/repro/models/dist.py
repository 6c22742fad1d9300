"""Distributed execution context for model code.

The launcher installs a mesh + axis-role mapping here; model code (the MoE
block) queries it to decide between the single-device path and the
expert-parallel ``shard_map`` path. When nothing is installed models run as
plain single-device JAX.
"""
from __future__ import annotations

import contextlib
import dataclasses
from typing import Optional, Tuple

import jax


@dataclasses.dataclass(frozen=True)
class MeshContext:
    mesh: jax.sharding.Mesh
    batch_axes: Tuple[str, ...]      # e.g. ('pod', 'data') or ('data',)
    model_axis: str                  # tensor/expert-parallel axis, e.g. 'model'

    @property
    def model_size(self) -> int:
        return self.mesh.shape[self.model_axis]

    @property
    def batch_size(self) -> int:
        n = 1
        for a in self.batch_axes:
            n *= self.mesh.shape[a]
        return n


_CTX: Optional[MeshContext] = None


def set_mesh_context(ctx: Optional[MeshContext]) -> None:
    global _CTX
    _CTX = ctx


def get_mesh_context() -> Optional[MeshContext]:
    return _CTX


@contextlib.contextmanager
def mesh_context(ctx: Optional[MeshContext]):
    prev = _CTX
    set_mesh_context(ctx)
    try:
        yield
    finally:
        set_mesh_context(prev)
