"""Thin wrappers over the jax APIs this codebase leans on (jax 0.9).

Every call site in src/, tests/ and benchmarks/ goes through these wrappers,
so the defaults the repo relies on live in one place:

* ``shard_map``  — the manual collectives are not expressible under the VMA
  checker, so it is off by default;
* ``make_mesh``  — every axis is ``AxisType.Auto``;
* ``distributed_initialize`` — a bounded coordinator connect;
* ``cost_analysis_dict`` — an empty analysis normalizes to ``{}``.
"""

from __future__ import annotations

from typing import Any, Callable, Sequence

import jax
from jax import lax

__all__ = [
    "shard_map",
    "make_mesh",
    "set_mesh",
    "axis_size",
    "pallas_tpu_compiler_params",
    "distributed_initialize",
    "cost_analysis_dict",
]


def axis_size(axis_name: str) -> int:
    """Static size of a named mesh axis, inside ``shard_map``.

    A *python int* — the halo code unrolls loops and builds permutation
    tables from it at trace time.
    """
    return lax.axis_size(axis_name)


def shard_map(
    f: Callable,
    *,
    mesh: Any,
    in_specs: Any,
    out_specs: Any,
    check: bool = False,
) -> Callable:
    """``jax.shard_map`` with the VMA check disabled by default."""
    return jax.shard_map(
        f, mesh=mesh, in_specs=in_specs, out_specs=out_specs, check_vma=check,
    )


def make_mesh(
    axis_shapes: Sequence[int],
    axis_names: Sequence[str],
    *,
    devices: Sequence[Any] | None = None,
) -> jax.sharding.Mesh:
    """``jax.make_mesh`` with every axis auto-typed."""
    return jax.make_mesh(
        tuple(axis_shapes), tuple(axis_names),
        axis_types=(jax.sharding.AxisType.Auto,) * len(axis_names),
        devices=devices,
    )


def set_mesh(mesh: jax.sharding.Mesh) -> Any:
    """Context manager installing ``mesh`` as the ambient mesh for ``jit``."""
    return jax.set_mesh(mesh)


def pallas_tpu_compiler_params(**kwargs: Any) -> Any:
    """``pltpu.CompilerParams`` (``dimension_semantics`` etc.)."""
    from jax.experimental.pallas import tpu as pltpu

    return pltpu.CompilerParams(**kwargs)


def distributed_initialize(
    *,
    coordinator_address: str,
    num_processes: int,
    process_id: int,
    timeout: float | None = None,
) -> None:
    """``jax.distributed.initialize`` with a bounded coordinator connect.

    Without a bound, a worker whose coordinator died before binding blocks
    in the barrier forever (the zombie-grid failure mode
    :func:`repro.launch.stencil.launch_grid` must reap).
    """
    kwargs: dict[str, Any] = dict(
        coordinator_address=coordinator_address,
        num_processes=num_processes,
        process_id=process_id,
    )
    if timeout is not None:
        kwargs["initialization_timeout"] = max(1, int(timeout))
    jax.distributed.initialize(**kwargs)


def cost_analysis_dict(compiled: Any) -> dict:
    """``Compiled.cost_analysis()`` as a plain dict (``{}`` when empty)."""
    return dict(compiled.cost_analysis() or {})
