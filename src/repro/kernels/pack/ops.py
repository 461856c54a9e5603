"""Public jit'd wrappers composing slice -> pack kernel -> (exchange) -> unpack.

``pack_slab`` / ``unpack_slab`` are what the transport layer's kernel-backed
packers use (:class:`repro.core.transport.PallasPacker`): they carry any N-D
slab the halo schedules emit — full-extent sequential faces, the fused
schedule's ``3^D - 1`` face/edge/corner blocks, and clipped partitions —
through the lane-dense 2-D (lead, lane) kernel view (:func:`view_2d`).
``pack_face`` / ``unpack_face`` are the face-level forms (slice by axis/side
baked in).  Every wrapper runs the kernel; which backend gets the kernel and
which gets the jnp oracle (:mod:`repro.kernels.pack.ref`) is the packer's
choice, made once in :func:`repro.kernels.use_kernel`.
"""

from __future__ import annotations

import jax

from repro.kernels.pack.pack import pack_2d, unpack_2d
from repro.kernels.pack.ref import view_2d


def pack_slab(
    slab: jax.Array,
    *,
    out_dtype=None,
    scale: float = 1.0,
    interpret: bool = False,
) -> jax.Array:
    """Pack an N-D slab (face, edge, corner, or partition block) into a
    contiguous 2-D wire buffer via the tiled copy kernel."""
    return pack_2d(slab.reshape(view_2d(slab.shape)), out_dtype=out_dtype,
                   scale=scale, interpret=interpret)


def unpack_slab(
    buf: jax.Array,
    shape: tuple[int, ...],
    *,
    out_dtype=None,
    scale: float = 1.0,
    interpret: bool = False,
) -> jax.Array:
    """Inverse of :func:`pack_slab`: wire buffer back to the slab ``shape``."""
    vals = unpack_2d(buf, out_dtype=out_dtype, scale=scale,
                     interpret=interpret)
    return vals.reshape(shape)


def pack_face(
    x: jax.Array,
    array_axis: int,
    side: str,  # 'low' | 'high'
    halo: int,
    *,
    out_dtype=None,
    scale: float = 1.0,
    interpret: bool = False,
) -> jax.Array:
    """Pack one interior boundary face into a contiguous (possibly
    wire-compressed) 2-D buffer."""
    size = x.shape[array_axis]
    if side == "low":
        slab = jax.lax.slice_in_dim(x, halo, 2 * halo, axis=array_axis)
    elif side == "high":
        slab = jax.lax.slice_in_dim(x, size - 2 * halo, size - halo, axis=array_axis)
    else:
        raise ValueError(side)
    return pack_slab(slab, out_dtype=out_dtype, scale=scale,
                     interpret=interpret)


def unpack_face(
    x: jax.Array,
    buf: jax.Array,
    array_axis: int,
    side: str,  # ghost side to fill: 'low' | 'high'
    halo: int,
    *,
    scale: float = 1.0,
    interpret: bool = False,
) -> jax.Array:
    """Unpack a received contiguous buffer into the ghost rim of ``x``."""
    size = x.shape[array_axis]
    ghost_shape = list(x.shape)
    ghost_shape[array_axis] = halo
    ghost = unpack_slab(buf, tuple(ghost_shape), out_dtype=x.dtype,
                        scale=scale, interpret=interpret)
    starts = [0] * x.ndim
    starts[array_axis] = 0 if side == "low" else size - halo
    return jax.lax.dynamic_update_slice(x, ghost, tuple(starts))
