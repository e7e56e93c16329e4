"""The single import point for version-sensitive JAX/Pallas API names.

The repo targets one installation: jax/jaxlib 0.9.0 with libtpu (the
version ``pyproject.toml`` and CI pin).  The names below are the ones that
moved or changed shape across recent releases (Pallas TPU memory spaces
and compiler params, ``dimension_semantics`` enums, ``make_mesh`` axis
types, ``shard_map`` and its replication check, profiler annotations).
Kernels and launchers import them from here, so the next rename is one
edit in this module instead of a sweep.

This table is also the single source of truth for the ``SL001`` lint
(``python -m repro.analysis``): every ````-quoted name or ``kwarg=`` token
between the table rules below is banned outside this module.  Adding a shim
here (with its table row) is how the banned list grows.

======================  ==============================  ========================
concept                 version-sensitive spelling      routed through
======================  ==============================  ========================
TPU memory spaces       ``pltpu.MemorySpace``           ``MemorySpace``
VMEM scratch shapes     ``pltpu.VMEM``                  ``VMEM``
compiler params         ``pltpu.CompilerParams``        ``CompilerParams``
dimension semantics     ``dimension_semantics=``        ``tpu_compiler_params``
                        ``GridDimensionSemantics``      ``dimension_semantics``
mesh construction       ``jax.make_mesh``               ``make_mesh``
mesh axis types         ``axis_types=``                 ``make_mesh``
shard_map               ``jax.experimental.shard_map``  ``shard_map``
                        ``jax.shard_map``               ``shard_map``
replication check       ``check_vma=``                  ``shard_map``
profiler annotations    ``jax.profiler.TraceAnnotation``  ``trace_annotation``
======================  ==============================  ========================
"""
from __future__ import annotations

from typing import Any, Optional, Sequence

import jax
from jax.experimental.pallas import tpu as pltpu

__all__ = [
    "MemorySpace",
    "VMEM",
    "CompilerParams",
    "dimension_semantics",
    "tpu_compiler_params",
    "make_mesh",
    "shard_map",
    "trace_annotation",
]

# --- Pallas TPU memory spaces and compiler params ---------------------------
MemorySpace = pltpu.MemorySpace
VMEM = pltpu.VMEM  # scratch-shape constructor (== MemorySpace.VMEM)
CompilerParams = pltpu.CompilerParams

_SEMANTICS = {
    "parallel": pltpu.GridDimensionSemantics.PARALLEL,
    "arbitrary": pltpu.GridDimensionSemantics.ARBITRARY,
}


def dimension_semantics(*kinds: str) -> tuple:
    """Map ``'parallel'``/``'arbitrary'`` strings onto the grid enum.

    Usage::

        compiler_params=tpu_compiler_params("parallel", "arbitrary")
    """
    for k in kinds:
        if k not in _SEMANTICS:
            raise ValueError(f"unknown dimension semantic {k!r}")
    return tuple(_SEMANTICS[k] for k in kinds)


def tpu_compiler_params(*kinds: str, **kwargs: Any):
    """``CompilerParams`` with the grid's ``dimension_semantics``."""
    return CompilerParams(dimension_semantics=dimension_semantics(*kinds), **kwargs)


# --- Mesh construction -------------------------------------------------------
def make_mesh(
    axis_shapes: Sequence[int],
    axis_names: Sequence[str],
    *,
    devices: Optional[Sequence[Any]] = None,
):
    """``jax.make_mesh`` with every axis ``AxisType.Auto``.

    The repo never uses Explicit axes; naming the type keeps sharding
    propagation the same whatever the installed default is.
    """
    return jax.make_mesh(
        tuple(axis_shapes), tuple(axis_names),
        axis_types=(jax.sharding.AxisType.Auto,) * len(axis_names),
        devices=devices)


# --- shard_map ---------------------------------------------------------------
def shard_map(
    f,
    mesh,
    in_specs,
    out_specs,
    *,
    check_replication: bool = False,
    axis_names: Optional[frozenset] = None,
):
    """``jax.shard_map`` with the replication check off by default.

    ``check_replication=False`` disables the out-spec replication check
    (``check_vma``) -- the fleet runtime emits psum-reduced telemetry whose
    replication the checker cannot always prove.  ``axis_names``: the
    subset of mesh axes the body is manual over (default: all of them).
    """
    kwargs: dict = {"mesh": mesh, "in_specs": in_specs,
                    "out_specs": out_specs, "check_vma": check_replication}
    if axis_names is not None:
        kwargs["axis_names"] = frozenset(axis_names)
    return jax.shard_map(f, **kwargs)


# --- profiler trace annotations ----------------------------------------------
# The observability layer (repro.obs) routes through this name so
# serving-loop spans can also land inside XLA device profiles.
trace_annotation = jax.profiler.TraceAnnotation
