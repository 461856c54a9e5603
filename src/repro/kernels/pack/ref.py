"""Pure-jnp oracle for the pack/unpack kernel."""

from __future__ import annotations

import jax
import jax.numpy as jnp


def pack_2d_ref(slab: jax.Array, *, out_dtype=None, scale: float = 1.0) -> jax.Array:
    out_dtype = out_dtype or slab.dtype
    x = slab
    if scale != 1.0:
        x = x.astype(jnp.float32) * scale
    return x.astype(out_dtype)


def unpack_2d_ref(buf: jax.Array, *, out_dtype=None, scale: float = 1.0) -> jax.Array:
    return pack_2d_ref(buf, out_dtype=out_dtype, scale=(1.0 / scale if scale != 1.0 else 1.0))


#: lanes a 2-D slab view aims for: one TPU vector register row
_LANES = 128


def view_2d(shape: tuple[int, ...]) -> tuple[int, int]:
    """Lane-dense ``(lead, lane)`` view of a row-major slab.

    The lane axis takes the trailing dims, innermost first, until it holds
    at least 128 elements (or every dim), so a slab whose trailing dims are
    1 or halo-wide still gets wide rows instead of an ``(N, 1)`` column.
    """
    lane, k = 1, len(shape)
    while k > 0 and lane < _LANES:
        k -= 1
        lane *= shape[k]
    lead = 1
    for d in shape[:k]:
        lead *= d
    return lead, lane


def pack_slab_ref(
    slab: jax.Array, *, out_dtype=None, scale: float = 1.0
) -> jax.Array:
    """N-D slab -> contiguous 2-D wire buffer (jnp oracle of ``pack_slab``)."""
    return pack_2d_ref(slab.reshape(view_2d(slab.shape)), out_dtype=out_dtype,
                       scale=scale)


def unpack_slab_ref(
    buf: jax.Array, shape, *, out_dtype=None, scale: float = 1.0
) -> jax.Array:
    """Wire buffer -> slab of ``shape`` (jnp oracle of ``unpack_slab``)."""
    return unpack_2d_ref(buf, out_dtype=out_dtype, scale=scale).reshape(shape)


def pack_face_ref(
    x: jax.Array, array_axis: int, side: str, halo: int,
    *, out_dtype=None, scale: float = 1.0,
) -> jax.Array:
    """Slice the interior boundary slab and pack it contiguously (jnp)."""
    size = x.shape[array_axis]
    if side == "low":
        slab = jax.lax.slice_in_dim(x, halo, 2 * halo, axis=array_axis)
    elif side == "high":
        slab = jax.lax.slice_in_dim(x, size - 2 * halo, size - halo, axis=array_axis)
    else:
        raise ValueError(side)
    return pack_2d_ref(slab.reshape(view_2d(slab.shape)), out_dtype=out_dtype,
                       scale=scale)
