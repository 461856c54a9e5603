"""27-point stencil update Pallas TPU kernel, in place.

The local compute phase of the paper's workload: every interior cell is
replaced by a weighted sum of its 3x3x3 neighborhood.  The kernel takes the
stored block ``(Z+2, Y+2, X)`` -- one-cell ghost rims on z and y, none on
x, which is periodic -- and returns the same buffer
(``input_output_aliases``) with cells ``1..Z`` x ``1..Y`` x all of ``X``
replaced by the 27-point sum, computed in float32; the z and y rims pass
through unchanged (the :func:`repro.stencil.domain.interior_halo_split`
contract).

The grid walks the planes in order, one per step, and reads each once.
Plane ``k`` of the result needs the old planes ``k-1`` and ``k+1``, and the
old ``k-1`` has been overwritten by then, so the kernel carries the last
two planes it read in VMEM: step ``i`` reads plane ``i``, computes plane
``i-1`` from the carried ``i-2``, ``i-1`` and the new ``i``, then carries
plane ``i``.  The output block of steps 0 to 2 is plane 1 and of step
``i`` plane ``i-1`` after, and Pallas writes a block back only when its
index changes, so a plane reaches HBM after the next has been read and
none is read after it is written.  The steps therefore run in order
(``"arbitrary"``).

Inside a step the plane is computed ``rows`` rows at a time: one aligned
load of ``rows + 16`` rows per z-neighbor, its y-neighbors taken by
sublane rotations.  The x-neighbors come last: the sums for each x offset
are rotated along the lane axis, which spans the whole periodic x axis, so
the rotation is the wrap.  The first and last rows, which an aligned load
cannot reach without leaving the plane, are computed once with
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
#: lanes of one vector register: the x axis is a whole number of them
_LANES = 128


def _combine(w_ref, rows, nx: int) -> jax.Array:
    """The 27-point sum of ``n`` output rows; ``rows[dz][dy]`` holds the
    ``n`` input rows at offset ``(dz - 1, dy - 1)`` in float32."""
    by_dx = []
    for dx in range(3):
        acc = None
        for dz in range(3):
            for dy in range(3):
                term = w_ref[dz * 9 + dy * 3 + dx] * rows[dz][dy]
                acc = term if acc is None else acc + term
        by_dx.append(acc)
    # column x of the result takes column x + dx - 1 of each sum
    return (pltpu.roll(by_dx[0], 1, 1) + by_dx[1]
            + pltpu.roll(by_dx[2], nx - 1, 1))


def _stencil_kernel(w_ref, x_ref, o_ref, carry_ref, *, rows: int,
                    n_chunks: int):
    i = pl.program_id(0)
    yg, nx = x_ref.shape
    # planes i-2, i-1 (carried) and i (this step's block)
    planes = (carry_ref.at[i % 2], carry_ref.at[(i + 1) % 2], x_ref)

    def span(a: int, n: int) -> None:
        """Rows ``a .. a+n-1`` of the result, from static-offset loads."""
        got = [[p[pl.ds(a - 1 + dy, n), :].astype(jnp.float32)
                for dy in range(3)] for p in planes]
        o_ref[pl.ds(a, n), :] = _combine(w_ref, got, nx).astype(o_ref.dtype)

    def chunk(c, _):
        r0 = pl.multiple_of(_SUBLANES + c * rows, _SUBLANES)
        got = []
        for p in planes:
            win = p[pl.ds(r0 - _SUBLANES, rows + 2 * _SUBLANES), :].astype(
                jnp.float32)
            # row m of each holds input row r0 + m + dy - 1
            got.append([
                pltpu.roll(win[:rows + _SUBLANES], 1, 0)[_SUBLANES:],
                win[_SUBLANES:rows + _SUBLANES],
                pltpu.roll(win[_SUBLANES:], rows + _SUBLANES - 1, 0)[:rows],
            ])
        o_ref[pl.ds(r0, rows), :] = _combine(w_ref, got, nx).astype(
            o_ref.dtype)
        return _

    @pl.when(i >= 2)
    def _():
        for r in (0, yg - 1):  # the y rims pass through
            o_ref[pl.ds(r, 1), :] = planes[1][pl.ds(r, 1), :]
        head = min(_SUBLANES, yg - 1)
        span(1, head - 1)
        if n_chunks:
            jax.lax.fori_loop(0, n_chunks, chunk, 0)
        tail = max(head, _SUBLANES + n_chunks * rows)
        if tail < yg - 1:
            span(tail, yg - 1 - tail)

    carry_ref[i % 2, pl.ds(0, yg), :] = x_ref[...]


@functools.partial(jax.jit, static_argnames=("rows", "interpret"),
                   donate_argnums=(0,))
def stencil27(
    x: jax.Array,  # (Z+2, Y+2, X) stored block
    w: jax.Array,  # (3, 3, 3) weights
    *,
    rows: int = 32,
    interpret: bool | pltpu.InterpretParams = False,
) -> jax.Array:
    """Apply the 27-point stencil to the interior of the stored block, in
    place; returns the block, its rims unchanged.

    ``rows`` is the row loop's step, a multiple of 8.  ``interpret`` is
    passed to ``pallas_call`` (``True`` or ``pltpu.InterpretParams()``).
    """
    assert w.shape == (3, 3, 3), w.shape
    zg, yg, nx = x.shape
    assert zg >= 3 and yg >= 3, f"a ghosted block needs Z, Y >= 1: {x.shape}"
    assert rows % _SUBLANES == 0, rows
    assert interpret or nx % _LANES == 0, (
        f"the lane axis (x = {nx}) must be a multiple of {_LANES} on the "
        "chip: the kernel wraps it by rotating whole rows of lanes")
    # chunks whose aligned loads of rows r0 - 8 .. r0 + rows + 8 stay inside
    n_chunks = max(0, (yg - 2 * _SUBLANES) // rows)
    plane = pl.BlockSpec((None, yg, nx), lambda i: (i, 0, 0))
    return pl.pallas_call(
        functools.partial(_stencil_kernel, rows=rows, n_chunks=n_chunks),
        grid=(zg,),
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM), plane],
        # plane 1 for steps 0..2, then plane i - 1
        out_specs=pl.BlockSpec((None, yg, nx),
                               lambda i: (jnp.maximum(i - 1, 1), 0, 0)),
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        # whole sublane tiles per carried plane, so either can be sliced out
        scratch_shapes=[pltpu.VMEM((2, pl.cdiv(yg, _SUBLANES) * _SUBLANES,
                                    nx), x.dtype)],
        input_output_aliases={1: 0},
        compiler_params=pallas_tpu_compiler_params(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=64 * 1024 * 1024,
        ),
        interpret=interpret,
        name="stencil27",
    )(w.reshape(27).astype(jnp.float32), x)
