"""Halo pack/unpack Pallas TPU kernel.

The paper packs boundary slabs into contiguous buffers with OpenMP threads
before communication.  The TPU analogue is a VMEM-tiled strided-to-contiguous
copy, with two fusions the CPU version cannot do for free:

* dtype conversion on the fly (e.g. f32 mesh -> bf16 wire format, halving
  halo bytes on the wire — a gradient-compression-style optimization), and
* optional scaling (for compressed-wire formats).

The kernel operates on a 2-D view (lead, lane) of the slab; ``ops.py`` builds
that view, splits partitions, and re-inserts unpacked ghosts.  Grid tiles are
(block_lead, block_lane) VMEM blocks.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.compat import pallas_tpu_compiler_params


def _copy_convert_kernel(x_ref, o_ref, *, scale: float):
    x = x_ref[...]
    if scale != 1.0:
        x = x.astype(jnp.float32) * scale
    o_ref[...] = x.astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("out_dtype", "scale", "block_lead", "block_lane", "interpret"),
)
def pack_2d(
    slab: jax.Array,  # (lead, lane) view of a boundary slab
    *,
    out_dtype=None,
    scale: float = 1.0,
    block_lead: int = 256,
    block_lane: int = 256,
    interpret: bool = False,
) -> jax.Array:
    """Tiled contiguous copy (+convert/scale) of a 2-D slab view."""
    lead, lane = slab.shape
    out_dtype = out_dtype or slab.dtype
    bl = min(block_lead, lead)
    bn = min(block_lane, lane)
    # pad to tile multiples (the paper's equal-partition padding, §II-B)
    pl_lead = -lead % bl
    pl_lane = -lane % bn
    padded = slab
    if pl_lead or pl_lane:
        padded = jnp.pad(slab, ((0, pl_lead), (0, pl_lane)))
    grid = (padded.shape[0] // bl, padded.shape[1] // bn)
    out = pl.pallas_call(
        functools.partial(_copy_convert_kernel, scale=scale),
        grid=grid,
        in_specs=[pl.BlockSpec((bl, bn), lambda i, j: (i, j))],
        out_specs=pl.BlockSpec((bl, bn), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct(padded.shape, out_dtype),
        compiler_params=pallas_tpu_compiler_params(
            dimension_semantics=("parallel", "parallel"),
        ),
        interpret=interpret,
    )(padded)
    if pl_lead or pl_lane:
        out = out[:lead, :lane]
    return out


@functools.partial(
    jax.jit,
    static_argnames=("out_dtype", "scale", "block_lead", "block_lane", "interpret"),
)
def unpack_2d(
    buf: jax.Array,
    *,
    out_dtype=None,
    scale: float = 1.0,
    block_lead: int = 256,
    block_lane: int = 256,
    interpret: bool = False,
) -> jax.Array:
    """Inverse of :func:`pack_2d` (convert back, inverse scale)."""
    return pack_2d(
        buf,
        out_dtype=out_dtype,
        scale=1.0 / scale if scale != 1.0 else 1.0,
        block_lead=block_lead,
        block_lane=block_lane,
        interpret=interpret,
    )
