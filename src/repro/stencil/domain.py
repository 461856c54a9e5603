"""Cartesian domain decomposition for stencil workloads (Comb's mesh layer).

A :class:`Domain` splits a global interior mesh across named mesh axes; every
shard carries ghost rims of width ``halo`` on each decomposed axis.  The
*stored* global array is therefore ``(interior/procs + 2*halo) * procs`` per
decomposed axis — the per-shard ghosted block layout that
``repro.core.halo.exchange`` operates on.
"""

from __future__ import annotations

import dataclasses
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core.halo import HaloSpec, ghost_pspec


@dataclasses.dataclass(frozen=True)
class Domain:
    """A periodic structured mesh decomposed over ``mesh_axes``.

    ``global_interior[i]`` cells along array axis ``i``; axis ``i`` is
    decomposed over mesh axis ``mesh_axes[i]`` (None = not decomposed).
    """

    mesh: Mesh
    global_interior: tuple[int, ...]
    mesh_axes: tuple[str | None, ...]
    halo: int = 1
    dtype: str = "float32"

    def __post_init__(self):
        assert len(self.global_interior) == len(self.mesh_axes)
        for size, name in zip(self.global_interior, self.mesh_axes):
            if name is not None:
                procs = self.mesh.shape[name]
                assert size % procs == 0, (size, name, procs)
                assert size // procs >= self.halo, "shard thinner than halo"

    # -- geometry -----------------------------------------------------------
    @property
    def decomposed(self) -> list[tuple[int, str]]:
        return [
            (i, name) for i, name in enumerate(self.mesh_axes) if name is not None
        ]

    @property
    def local_interior(self) -> tuple[int, ...]:
        out = []
        for size, name in zip(self.global_interior, self.mesh_axes):
            out.append(size // self.mesh.shape[name] if name else size)
        return tuple(out)

    @property
    def local_ghosted(self) -> tuple[int, ...]:
        return tuple(
            s + (2 * self.halo if name else 0)
            for s, name in zip(self.local_interior, self.mesh_axes)
        )

    @property
    def stored_global(self) -> tuple[int, ...]:
        """Shape of the stored (ghost-carrying) global array."""
        out = []
        for s, name in zip(self.local_ghosted, self.mesh_axes):
            out.append(s * self.mesh.shape[name] if name else s)
        return tuple(out)

    def face_bytes(self) -> dict[str, int]:
        """Per decomposed mesh axis: bytes of one face message (the paper's
        *message size* axis — a full-extent ghost slab of width ``halo``)."""
        itemsize = np.dtype(self.dtype).itemsize
        out = {}
        for axis, name in self.decomposed:
            slab = 1
            for a, s in enumerate(self.local_ghosted):
                slab *= self.halo if a == axis else s
            out[name] = slab * itemsize
        return out

    def max_face_bytes(self) -> int:
        """Largest single face message — the sweep's message-size coordinate."""
        return max(self.face_bytes().values(), default=0)

    def pspec(self) -> P:
        return P(*self.mesh_axes)

    def sharding(self) -> NamedSharding:
        return NamedSharding(self.mesh, self.pspec())

    def halo_spec(self, strategy: str = "standard", n_parts: int = 1) -> HaloSpec:
        idxs, names = [], []
        for i, name in self.decomposed:
            idxs.append(i)
            names.append(name)
        return HaloSpec(
            mesh_axes=tuple(names),
            array_axes=tuple(idxs),
            halo=self.halo,
            periodic=True,
            strategy=strategy,
            n_parts=n_parts,
        )

    # -- data ---------------------------------------------------------------
    def from_global_interior(self, interior: np.ndarray) -> jax.Array:
        """Scatter a dense global interior into the ghosted sharded layout
        (ghosts zeroed; call an exchange to fill them).

        Works on multi-process meshes too: when this process cannot address
        every shard (a ``jax.distributed`` grid), each process contributes
        its addressable blocks via ``make_array_from_callback`` — every rank
        holds the same dense ``interior``, so the assembled global array is
        consistent without any cross-process data movement.
        """
        sharding = self.sharding()
        stored = self.stored_from_interior(interior)
        if not sharding.is_fully_addressable:
            return jax.make_array_from_callback(
                stored.shape, sharding, lambda idx: stored[idx]
            )
        # straight from the host array: each device gets only its shard
        return jax.device_put(stored, sharding)

    def stored_from_interior(self, interior: np.ndarray) -> np.ndarray:
        """Host-side stored (ghost-carrying) layout of a dense interior.

        The carve-and-pad is a pure function of this domain's decomposition,
        exposed separately so elastic JOINs can re-shard *live* state onto a
        grown mesh through :func:`repro.train.fault_tolerance.reshard_state`
        (stored layout here, placement there) instead of restoring a
        checkpoint through :meth:`from_global_interior`.
        """
        assert interior.shape == self.global_interior, interior.shape
        h = self.halo
        blocks = interior
        # carve into per-shard blocks and pad each with ghost rims
        for axis, name in reversed(self.decomposed):
            procs = self.mesh.shape[name]
            pieces = np.split(blocks, procs, axis=axis)
            widths = [(0, 0)] * blocks.ndim
            widths[axis] = (h, h)
            pieces = [np.pad(p, widths) for p in pieces]
            blocks = np.concatenate(pieces, axis=axis)
        return np.asarray(blocks, dtype=self.dtype)

    def to_global_interior(self, x: jax.Array) -> np.ndarray:
        """Strip ghosts and reassemble the dense global interior."""
        h = self.halo
        arr = np.asarray(x)
        for axis, name in self.decomposed:
            procs = self.mesh.shape[name]
            pieces = np.split(arr, procs, axis=axis)
            pieces = [
                p[tuple(
                    slice(h, -h) if a == axis else slice(None)
                    for a in range(p.ndim)
                )]
                for p in pieces
            ]
            arr = np.concatenate(pieces, axis=axis)
        return arr

    def random(self, seed: int = 0) -> jax.Array:
        rng = np.random.default_rng(seed)
        return self.from_global_interior(
            rng.normal(size=self.global_interior).astype(self.dtype)
        )


def reference_exchange(domain: Domain, interior: np.ndarray) -> np.ndarray:
    """Single-device reference roll: the exchanged stored layout, by gather.

    Along each decomposed axis (chunk ``c``, halo ``h``) shard ``i`` stores
    ``[ghost_l | interior | ghost_r]`` = global indices
    ``(i*c - h) .. (i*c + c + h)`` wrapped periodically; the full stored
    array is the tensor product of those per-axis index maps.  This is the
    correctness oracle every exchange strategy is held to — in-process
    (``tests/stencil/test_equivalence.py``) and across real processes
    (``tests/distributed_progs/check_multihost.py``), where each rank
    compares just its addressable shards against this dense prediction.
    """
    out = np.asarray(interior, dtype=domain.dtype)
    h = domain.halo
    for axis, name in domain.decomposed:
        k = domain.mesh.shape[name]
        g = interior.shape[axis]
        c = g // k
        idx = [
            (i * c + off - h) % g for i in range(k) for off in range(c + 2 * h)
        ]
        out = np.take(out, idx, axis=axis)
    return out


# ---------------------------------------------------------------------------
# interior/halo region split (the communication/computation-overlap schedule)
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class UpdateRegion:
    """One piece of an interior/boundary-split stencil update.

    ``src`` is the (start, size) window of the local ghosted block fed to
    the update fn; ``out`` is the (start, size) window *within the piece's
    output* whose cells were validly updated; ``dst`` is where that window
    lands back in the block.  ``needs_fresh_ghosts`` says whether the piece
    must read the post-exchange buffer (boundary shell) or may read the
    pre-exchange one (deep interior — computable while messages fly).
    """

    src: tuple[tuple[int, int], ...]
    out: tuple[tuple[int, int], ...]
    dst: tuple[int, ...]
    needs_fresh_ghosts: bool

    @staticmethod
    def _window(x: jax.Array, win: tuple[tuple[int, int], ...]) -> jax.Array:
        return jax.lax.slice(
            x, [s for s, _ in win], [s + n for s, n in win]
        )

    def updated(self, block: jax.Array, update_fn) -> jax.Array:
        """Run ``update_fn`` on this piece's window; return the valid cells."""
        return self._window(update_fn(self._window(block, self.src)), self.out)


def interior_halo_split(
    shape: tuple[int, ...], array_axes: tuple[int, ...], halo: int
) -> tuple[UpdateRegion, ...]:
    """Split a local ghosted block into overlap-schedulable update pieces.

    The contract on the update fn is the stencil-workload one: a local,
    shift-invariant stencil of radius <= ``halo`` along each decomposed
    axis, writing positions at distance >= ``halo`` from the block edge on
    those axes and leaving the ``halo``-wide rim untouched (undecomposed
    axes are unconstrained — pieces always span their full extent).

    Under that contract, the *deep interior* piece (all decomposed-axis
    positions >= ``2*halo`` from the edge) reads only interior cells, so it
    is computable from the **pre-exchange** buffer concurrently with the
    boundary exchange; the two boundary-shell pieces per decomposed axis
    need the refreshed ghosts.  Piece outputs tile the full updatable
    region; where shells meet at edges/corners they recompute identical
    values, so unpack order is immaterial.
    """
    h = halo
    dec = set(array_axes)
    for a in dec:
        assert shape[a] >= 3 * h, (shape, a, h)
    regions: list[UpdateRegion] = []

    def full(a: int) -> tuple[int, int]:
        return (0, shape[a])

    # deep interior: feed the interior sub-block (all values locally valid)
    if all(shape[a] - 4 * h > 0 for a in dec):
        src = tuple(
            (h, shape[a] - 2 * h) if a in dec else full(a)
            for a in range(len(shape))
        )
        out = tuple(
            (h, shape[a] - 4 * h) if a in dec else full(a)
            for a in range(len(shape))
        )
        dst = tuple(2 * h if a in dec else 0 for a in range(len(shape)))
        regions.append(UpdateRegion(src, out, dst, needs_fresh_ghosts=False))

    # boundary shells: one 3h-thick slab per side of each decomposed axis
    for axis in array_axes:
        s = shape[axis]
        for lo in (True, False):
            src = tuple(
                ((0, 3 * h) if lo else (s - 3 * h, 3 * h)) if a == axis
                else full(a)
                for a in range(len(shape))
            )
            out = tuple(
                (h, h) if a == axis
                else ((h, shape[a] - 2 * h) if a in dec else full(a))
                for a in range(len(shape))
            )
            dst = tuple(
                ((h if lo else s - 2 * h) if a == axis
                 else (h if a in dec else 0))
                for a in range(len(shape))
            )
            regions.append(UpdateRegion(src, out, dst, needs_fresh_ghosts=True))
    return tuple(regions)


def overlapped_update(
    stale: jax.Array,
    fresh: jax.Array,
    update_fn: Callable[[jax.Array], jax.Array],
    *,
    array_axes: tuple[int, ...],
    halo: int,
) -> jax.Array:
    """Apply ``update_fn`` with the interior/boundary overlap schedule.

    ``stale`` is the pre-exchange buffer, ``fresh`` the post-exchange one
    (identical except for refreshed ghost rims).  The deep-interior piece
    reads ``stale`` — giving it no data dependency on the exchange's
    collectives, so XLA may compute it while messages are in flight — and
    the boundary shells read ``fresh``.  Equals ``update_fn(fresh)`` under
    the :func:`interior_halo_split` contract.
    """
    out = fresh
    for region in interior_halo_split(stale.shape, array_axes, halo):
        piece = region.updated(
            fresh if region.needs_fresh_ghosts else stale, update_fn
        )
        out = jax.lax.dynamic_update_slice(out, piece, region.dst)
    return out


#: the implementations :func:`stencil27_update` can run, by name
UPDATE_IMPLS = ("xla", "pallas")


def stencil27_update(
    weights, *, impl: str = "xla", interpret: bool = False
) -> Callable[[jax.Array], jax.Array]:
    """The 27-point update of one local ghosted 3-D block.

    The block carries ghost rims on its two leading (decomposed) axes and
    none on its last axis, which is undecomposed and periodic: the update
    wraps that axis locally, applies the stencil and writes the new
    interior back, leaving the ghost rims untouched (the
    :func:`interior_halo_split` contract, so ``overlap`` can split it).
    One exchange plus this update is one Comb cycle of
    :func:`periodic_oracle_step`.  ``impl`` names the stencil: ``"xla"``
    is :func:`repro.kernels.stencil27.stencil27_ref` under XLA,
    ``"pallas"`` the :func:`repro.kernels.stencil27.stencil27` kernel
    (``interpret`` runs it in the Pallas interpreter, off the chip).
    """
    from repro.kernels.stencil27 import stencil27, stencil27_ref

    if impl not in UPDATE_IMPLS:
        raise ValueError(f"impl must be one of {UPDATE_IMPLS}, got {impl!r}")
    w = jnp.asarray(weights)

    def update(xl: jax.Array) -> jax.Array:
        xp = jnp.concatenate([xl[..., -1:], xl, xl[..., :1]], axis=-1)
        if impl == "pallas":
            interior = stencil27(xp, w, interpret=interpret)
        else:
            interior = stencil27_ref(xp, w)
        return jax.lax.dynamic_update_slice(xl, interior, (1, 1, 0))

    return update


def periodic_oracle_step(interior: np.ndarray, weights: np.ndarray) -> np.ndarray:
    """NumPy oracle: one 27-point (or 9-point in 2-D) periodic stencil update."""
    pad = np.pad(interior, 1, mode="wrap")
    out = np.zeros_like(interior, dtype=np.float32)
    ranges = [range(3)] * interior.ndim
    import itertools

    for offs in itertools.product(*ranges):
        sl = tuple(slice(o, o + s) for o, s in zip(offs, interior.shape))
        out += weights[offs].astype(np.float32) * pad[sl].astype(np.float32)
    return out.astype(interior.dtype)
