"""Ambient-mesh access for ops that want shard_map fast paths inside jit.

The mesh set with ``jax.set_mesh(mesh)`` is visible at trace time as an
``AbstractMesh``; ops consult it to decide whether a distributed
implementation (e.g. LSE-combined decode attention) is available.
``jax.shard_map`` and ``NamedSharding`` both accept it in place of the
concrete mesh.
"""
from __future__ import annotations

from typing import Optional

import jax
from jax.sharding import AbstractMesh


def ambient_mesh() -> Optional[AbstractMesh]:
    mesh = jax.sharding.get_abstract_mesh()
    return None if mesh.empty else mesh


def use_mesh(mesh):
    """Scope in which traced programs see ``mesh`` through
    :func:`ambient_mesh`.  Arrays created inside stay uncommitted, so each
    jitted program places its inputs by its own ``in_shardings`` (under
    ``jax.set_mesh`` they would be committed replicated and refused)."""
    return jax.sharding.use_abstract_mesh(mesh.abstract_mesh)
