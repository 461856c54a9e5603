"""27-point stencil update Pallas TPU kernel.

The local compute phase of the paper's workload: every interior cell is
replaced by a weighted sum of its 3x3x3 neighborhood.  The input is a block
ghosted by one cell on every axis, ``(Z+2, Y+2, X+2)``; the output is the
``(Z, Y, X)`` interior.

The grid walks the output ``tz`` planes at a time.  Each step's input block
is the overlapping window of ``tz + 2`` whole planes (``pl.Element``
indexing); whole planes keep the block's last two dims at the array's full
extent, which Mosaic accepts at any size.  Inside a step each output plane
is computed ``rows`` rows at a time: one aligned load of ``rows + 8`` input
rows per ``(dz, dx)`` pair, shifted by ``dy`` with a sublane rotation, so
the row loop needs no unaligned dynamic index.  The last rows that an
aligned load cannot reach without leaving the block are computed once with
static-offset loads.  The 27 weights ride in SMEM.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.compat import pallas_tpu_compiler_params

#: sublanes of one f32 vector register: the row loop's alignment unit
_SUBLANES = 8


def _weighted_sum(w_ref, load) -> jax.Array:
    acc = None
    for dz in range(3):
        for dy in range(3):
            for dx in range(3):
                term = w_ref[dz * 9 + dy * 3 + dx] * load(dz, dy, dx)
                acc = term if acc is None else acc + term
    return acc


def _stencil_kernel(w_ref, x_ref, o_ref, *, tz: int, ny: int, nx: int,
                    rows: int, n_chunks: int):
    span = rows + _SUBLANES

    def plane(k, carry):
        def chunk(c, carry):
            r0 = pl.multiple_of(c * rows, _SUBLANES)
            windows = {}

            def load(dz, dy, dx):
                if (dz, dx) not in windows:
                    windows[dz, dx] = x_ref[
                        k + dz, pl.ds(r0, span), pl.ds(dx, nx)
                    ].astype(jnp.float32)
                v = windows[dz, dx]
                if dy:  # row r of the result holds input row r + dy
                    v = pltpu.roll(v, span - dy, 0)
                return v[:rows]

            o_ref[k, pl.ds(r0, rows), :] = _weighted_sum(w_ref, load).astype(
                o_ref.dtype
            )
            return carry

        if n_chunks:
            jax.lax.fori_loop(0, n_chunks, chunk, 0)
        t0 = n_chunks * rows
        if t0 < ny:  # the tail: static offsets, any alignment
            o_ref[k, pl.ds(t0, ny - t0), :] = _weighted_sum(
                w_ref,
                lambda dz, dy, dx: x_ref[
                    k + dz, pl.ds(t0 + dy, ny - t0), pl.ds(dx, nx)
                ].astype(jnp.float32),
            ).astype(o_ref.dtype)
        return carry

    jax.lax.fori_loop(0, tz, plane, 0)


def _largest_divisor(n: int, cap: int) -> int:
    return max(d for d in range(1, min(n, cap) + 1) if n % d == 0)


@functools.partial(jax.jit, static_argnames=("tile", "interpret"))
def stencil27(
    x: jax.Array,  # (Z+2, Y+2, X+2) ghosted block
    w: jax.Array,  # (3, 3, 3) weights
    *,
    tile: tuple[int, int] = (1, 16),
    interpret: bool = False,
) -> jax.Array:
    """Apply the 27-point stencil to the interior; returns (Z, Y, X).

    ``tile`` is ``(tz, rows)``: output planes per grid step (shrunk to the
    largest divisor of ``Z`` it bounds) and rows per step of the row loop
    (a multiple of 8 on TPU).
    """
    assert w.shape == (3, 3, 3), w.shape
    zg, yg, xg = x.shape
    nz, ny, nx = zg - 2, yg - 2, xg - 2
    tz = _largest_divisor(nz, tile[0])
    rows = tile[1]
    # chunks whose aligned (rows + 8)-row loads stay inside the block
    n_chunks = max(0, (yg - _SUBLANES) // rows)
    el = pl.Element
    return pl.pallas_call(
        functools.partial(_stencil_kernel, tz=tz, ny=ny, nx=nx, rows=rows,
                          n_chunks=n_chunks),
        grid=(nz // tz,),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((el(tz + 2), el(yg), el(xg)),
                         lambda i: (i * tz, 0, 0)),
        ],
        out_specs=pl.BlockSpec((el(tz), el(ny), el(nx)),
                               lambda i: (i * tz, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((nz, ny, nx), x.dtype),
        compiler_params=pallas_tpu_compiler_params(
            dimension_semantics=("parallel",),
            vmem_limit_bytes=64 * 1024 * 1024,
        ),
        interpret=interpret,
    )(w.reshape(27).astype(jnp.float32), x)
