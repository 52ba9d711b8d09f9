"""The single home of version-sensitive jax calls (lint rule REP001).

Every call site whose spelling jax has changed between releases goes
through this module, so an upgrade touches one file:

* ``AxisType``      — ``jax.sharding.AxisType``.
* ``make_mesh``     — ``jax.make_mesh`` with every axis ``AxisType.Auto``
  unless the caller passes ``axis_types``.  ``jax.make_mesh`` itself
  defaults to ``Explicit`` axes, under which a plan's
  ``shard_map``/``NamedSharding`` layouts stop composing with ordinary
  jnp arithmetic on its outputs.
* ``shard_map``     — ``jax.shard_map``.
* ``cost_analysis_dict`` — ``compiled.cost_analysis()`` as a dict.
"""
from __future__ import annotations

from typing import Any, Callable, Optional, Sequence

import jax
from jax.sharding import AxisType


def make_mesh(axis_shapes: Sequence[int], axis_names: Sequence[str], *,
              axis_types: Optional[Sequence[Any]] = None, **kw):
    """``jax.make_mesh`` with ``AxisType.Auto`` axes by default."""
    if axis_types is None:
        axis_types = (AxisType.Auto,) * len(axis_shapes)
    return jax.make_mesh(tuple(axis_shapes), tuple(axis_names),
                         axis_types=tuple(axis_types), **kw)


def shard_map(f: Callable, *, mesh, in_specs, out_specs,
              check_vma: bool = True) -> Callable:
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=check_vma)


def cost_analysis_dict(compiled) -> dict:
    """``compiled.cost_analysis()``; an absent analysis becomes ``{}``."""
    return compiled.cost_analysis() or {}
