"""Mesh definitions.

Every builder is a FUNCTION (never a module-level constant) so importing
this module does not touch jax device state — the dry-run sets XLA_FLAGS
for 512 host devices before any jax initialization, and smoke tests must
keep seeing 1 device.

Meshes carry ``Auto`` axes: the model code places arrays through logical
sharding constraints and ``shard_map`` regions, which is the GSPMD
(``Auto``) contract.  ``jax.make_mesh`` defaults to ``Explicit`` axes,
under which the embedding gather and friends refuse to trace.
"""
from __future__ import annotations

from typing import Optional, Sequence

import jax
from jax.sharding import AxisType


def make_mesh(shape: Sequence[int], axes: Sequence[str],
              devices: Optional[Sequence] = None) -> jax.sharding.Mesh:
    """``jax.make_mesh`` with ``Auto`` axes (optionally over ``devices``)."""
    kw = {} if devices is None else {"devices": devices}
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes), **kw)


def make_production_mesh(*, multi_pod: bool = False):
    """Single pod: 256 chips as (data=16, model=16).  Multi-pod: 2 pods x
    256 chips as (pod=2, data=16, model=16); the 'pod' axis carries pure DP
    plus the numaPTE block-table coherence domain."""
    if multi_pod:
        return make_mesh((2, 16, 16), ("pod", "data", "model"))
    return make_mesh((16, 16), ("data", "model"))


def make_debug_mesh(n_devices: int = 8, *, multi_pod: bool = False):
    """Small mesh for CI-scale distributed tests (8 host devices)."""
    if multi_pod:
        return make_mesh((2, 2, n_devices // 4), ("pod", "data", "model"))
    return make_mesh((2, n_devices // 2), ("data", "model"))
