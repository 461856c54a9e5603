"""Unified pack/transport layer beneath every exchange path.

The paper's measured wins come from how messages are *packed* (contiguous
staging buffers, one per neighbor or partition) and *moved* (persistent
channels, partitioned sends).  This module is the one seam where both
concerns live, pMR-style: every communication path in the repo — the
sequential, fused, and partitioned halo exchanges, the LM ring primitives,
the sequence-parallel ghost pulls — describes its data movement as
:class:`Message` values and delegates the pack -> send -> unpack pipeline to
a :class:`Packer` and a :class:`Transport` chosen by *name*:

* **Message** — one neighbor message: the source slab window in the local
  ghosted block, the destination ghost window, the peer permutation chain
  (one hop per mesh axis crossed), and the partition policy (``n_parts``
  partitions split along ``part_axis``, the paper's ``MPI_Psend_init``
  analogue).
* **Packer** — how a slab window becomes a contiguous wire buffer and back.
  ``"slice"`` is the inline ``lax.slice``/``dynamic_update_slice`` staging
  the halo code historically did; ``"pallas"`` routes through the
  :mod:`repro.kernels.pack` VMEM-tiled copy kernel (Comb's OpenMP pack
  kernels) on TPU, and through its jnp oracle on other backends so CPU CI
  exercises identical semantics.  ``"bf16"`` and ``"scaled-int8"`` are the
  wire-compressed packers: the slab is re-encoded for the wire (bf16 cast /
  fixed-scale int8 quantization) and the block dtype restored on unpack —
  lossy within :meth:`Packer.wire_tolerance`, shrinking
  :meth:`Packer.wire_itemsize` (the sweep's wire-bytes axis).
* **Transport** — how a packed buffer crosses the mesh.  ``"ppermute"`` is
  the in-process XLA backend (one ``lax.ppermute`` per hop — the native ICI
  neighbor transport on a TPU torus).  ``"multihost"`` is the registered
  seam for multi-process meshes: the same schedule lowers to DCN/ICI
  collectives when the mesh spans hosts, so a real multi-host sweep backend
  plugs in here without touching any caller.

Registering a new packer or transport::

    register_packer(MyPacker(name="zstd-wire"))
    register_transport(MyTransport(name="nccl"))

and every registered exchange strategy, ``comb_measure``, and the §VI sweep
can select it through ``StrategyConfig(packer=..., transport=...)``.

The partition policy (equal-size rule, paper §II-B) lives here as
:class:`Partitioner`; the transport layer sends each partition's *clipped*
window (offsets on the equal-size grid, the zero-padding never crosses the
wire) and unpacks it into the ghost region on arrival (``MPI_Parrived``).

**Coalescing** (the pMR / MPI-Advance message-aggregation optimization) is
the third knob: with ``coalesce=True`` a delivery group's messages are
grouped by hop chain, every slab bound for one neighbor is packed into ONE
contiguous wire buffer (a static :class:`WireLayout` offset table, computed
at trace time and recorded in the persistent plan — the ``MPI_Send_init``
buffer-amortization analogue), and the whole chain is routed with a SINGLE
collective (multi-hop corner chains compose into one joint multi-axis
permutation).  Partitioned messages stay pipelined: round *k+1* packs from
the original buffer while round *k*'s coalesced buffer is in flight, and
each round's buffers unpack on arrival (``MPI_Parrived``).

All delivery functions run **inside** ``jax.shard_map``; message tables are
built at trace time, so permutation tables and slab geometry are baked into
the compiled plan — the "tag matching at init" the paper's persistent mode
amortizes.
"""

from __future__ import annotations

import abc
import contextlib
import dataclasses
import itertools
import math
import os
import warnings
from typing import Any, Callable, ClassVar, Iterable, Mapping, Sequence

import jax
import jax.numpy as jnp
from jax import lax


# ---------------------------------------------------------------------------
# Partitioner: the equal-partition (+padding) rule from the paper
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class Partitioner:
    """Splits an array axis into ``n_parts`` equal partitions, zero-padding the
    tail when the size does not divide (the paper's equal-size constraint)."""

    n_parts: int
    axis: int = 0

    def pad_amount(self, size: int) -> int:
        return (-size) % self.n_parts

    def part_size(self, size: int) -> int:
        return (size + self.pad_amount(size)) // self.n_parts

    def split(self, x: jax.Array) -> list[jax.Array]:
        size = x.shape[self.axis]
        pad = self.pad_amount(size)
        if pad:
            widths = [(0, 0)] * x.ndim
            widths[self.axis] = (0, pad)
            x = jnp.pad(x, widths)
        return jnp.split(x, self.n_parts, axis=self.axis)

    def merge(self, parts: Sequence[jax.Array], orig_size: int) -> jax.Array:
        x = jnp.concatenate(list(parts), axis=self.axis)
        if x.shape[self.axis] != orig_size:
            x = lax.slice_in_dim(x, 0, orig_size, axis=self.axis)
        return x

    def slices(self, size: int) -> list[tuple[int, int]]:
        """(offset, valid width) of each partition within the *un-padded*
        axis; the tail partition's width is clipped (0 when fully padding)."""
        c = self.part_size(size)
        return [
            (i * c, max(0, min(c, size - i * c))) for i in range(self.n_parts)
        ]


def ring_perm(axis_name: str, shift: int = 1) -> list[tuple[int, int]]:
    """Ring source->target table over a named mesh axis."""
    from repro.core import compat

    k = compat.axis_size(axis_name)
    return [(i, (i + shift) % k) for i in range(k)]


# ---------------------------------------------------------------------------
# Message: one neighbor message of an exchange schedule
# ---------------------------------------------------------------------------

#: one transport hop: (mesh axis name, source->target permutation table)
Hop = tuple[str, tuple[tuple[int, int], ...]]


@dataclasses.dataclass(frozen=True)
class Message:
    """One message of an exchange: src slab -> (hops) -> dst ghost window.

    ``src_start``/``shape`` window the source slab in the local ghosted
    block; ``dst_start`` is where the (identically shaped) payload lands on
    the receiving shard.  ``hops`` is the peer permutation chain — one
    ``(axis_name, perm)`` per mesh axis the message crosses (a corner
    message hops once per involved axis; an empty chain is a local
    self-copy, the single-shard periodic wrap).  ``n_parts > 1`` splits the
    slab along ``part_axis`` into equal partitions (paper §II-B), each
    packed, sent, and unpacked independently.
    """

    src_start: tuple[int, ...]
    dst_start: tuple[int, ...]
    shape: tuple[int, ...]
    hops: tuple[Hop, ...] = ()
    n_parts: int = 1
    part_axis: int | None = None

    def __post_init__(self):
        assert len(self.src_start) == len(self.dst_start) == len(self.shape)
        assert self.n_parts >= 1, self.n_parts
        if self.n_parts > 1:
            assert self.part_axis is not None, "partitioned message needs axis"

    def partitions(self) -> tuple["Message", ...]:
        """Expand into per-partition single messages (equal-size grid).

        Offsets follow the paper's equal-partition rule; each partition's
        window is clipped to the slab, so the zero-padding of a
        non-dividing tail never crosses the wire and an all-padding tail
        partition (``n_parts`` beyond the axis extent) is elided entirely.
        MPI would still post the fixed partition count; under XLA an
        arrival nobody consumes is dead code (the historical inline path's
        padding sends were eliminated the same way), so the wire-level
        cost of surplus partitions is a :mod:`repro.core.model_comm`
        concern, not something this backend can measure.
        """
        if self.n_parts <= 1:
            return (self,)
        a = self.part_axis
        out = []
        for off, width in Partitioner(self.n_parts, a).slices(self.shape[a]):
            if width <= 0:
                continue
            src = list(self.src_start)
            dst = list(self.dst_start)
            shape = list(self.shape)
            src[a] += off
            dst[a] += off
            shape[a] = width
            out.append(
                Message(tuple(src), tuple(dst), tuple(shape), self.hops)
            )
        return tuple(out)


@dataclasses.dataclass(frozen=True)
class WireSegment:
    """One slab's place inside a coalesced wire buffer.

    ``offset`` is the segment's start in wire *elements* (the wire dtype is
    uniform across a buffer, so element offsets are itemsize-free);
    ``src_start``/``dst_start``/``shape`` are the slab windows exactly as on
    :class:`Message`.  All fields are trace-time python ints — the layout is
    a static table baked into the compiled plan.
    """

    offset: int
    src_start: tuple[int, ...]
    dst_start: tuple[int, ...]
    shape: tuple[int, ...]

    @property
    def numel(self) -> int:
        return math.prod(self.shape)


@dataclasses.dataclass(frozen=True)
class WireLayout:
    """Static offset table of ONE coalesced wire buffer (one hop chain).

    Every segment's slab is packed at ``segments[i].offset`` into a single
    contiguous buffer of ``total`` wire elements, routed with one composed
    collective along ``hops``, and scatter-unpacked on arrival.
    ``wire_itemsize`` records what one element costs on the wire under the
    packer the layout was built for (compressed packers shrink it), so
    ``wire_bytes`` is the buffer's true wire footprint.
    """

    hops: tuple[Hop, ...]
    segments: tuple[WireSegment, ...]
    total: int
    wire_itemsize: int

    @property
    def wire_bytes(self) -> int:
        return self.total * self.wire_itemsize


def coalesced_layout(
    parts: Sequence[Message], hops: tuple[Hop, ...], packer: "Packer",
    dtype: Any,
) -> WireLayout:
    """Lay single-partition messages sharing ``hops`` end-to-end in one wire
    buffer (segment order = message order, offsets in wire elements)."""
    segments, offset = [], 0
    for m in parts:
        assert m.hops == hops, (m.hops, hops)
        assert m.n_parts == 1, "layouts are built from expanded partitions"
        segments.append(
            WireSegment(offset, m.src_start, m.dst_start, m.shape)
        )
        offset += math.prod(m.shape)
    return WireLayout(
        hops=tuple(hops), segments=tuple(segments), total=offset,
        wire_itemsize=packer.wire_itemsize(dtype),
    )


def coalesced_rounds(
    messages: Iterable[Message],
) -> list[list[tuple[tuple[Hop, ...], list[Message]]]]:
    """The pipelined partition schedule of one delivery group.

    Round *r* holds every message's *r*-th (clipped) partition, grouped by
    hop chain in first-seen order: each ``(chain, parts)`` cell becomes one
    coalesced buffer and one composed collective, and successive rounds
    pack/fly/unpack independently (the threaded-partitioned-send analogue —
    round *k+1* may pack while round *k* is in flight)."""
    per_msg = [m.partitions() for m in messages]
    n_rounds = max((len(p) for p in per_msg), default=0)
    rounds = []
    for r in range(n_rounds):
        chains: dict[tuple[Hop, ...], list[Message]] = {}
        for parts in per_msg:
            if r < len(parts):
                chains.setdefault(parts[r].hops, []).append(parts[r])
        rounds.append(list(chains.items()))
    return rounds


def composed_hop(hops: Sequence[Hop]) -> Hop | None:
    """Compose a hop chain into ONE joint permutation (a single collective).

    Per-axis neighbor tables act independently, so the chain equals the
    product map over the tuple of axis names: source coords ``(i_1..i_d)``
    reach ``(p_1(i_1)..p_d(i_d))`` iff every per-axis table defines the hop
    (clipped non-periodic edges drop the whole path — identical to what
    chained per-hop permutes deliver, where a missing hop zeros the buffer).
    Indices linearize row-major over the axis tuple, ``lax.ppermute``'s rule
    for multi-axis collectives.  Must run at trace time inside ``shard_map``
    (axis sizes come from the mesh).  ``None`` means a hop-free self-copy.
    """
    hops = tuple(hops)
    if not hops:
        return None
    if len(hops) == 1:
        return hops[0]
    from repro.core import compat

    names = tuple(name for name, _ in hops)
    sizes = [compat.axis_size(name) for name in names]
    maps = [dict(perm) for _, perm in hops]

    def lin(coords: Sequence[int]) -> int:
        idx = 0
        for c, k in zip(coords, sizes):
            idx = idx * k + c
        return idx

    pairs = []
    for coords in itertools.product(*[range(k) for k in sizes]):
        if all(c in m for c, m in zip(coords, maps)):
            pairs.append(
                (lin(coords), lin([m[c] for c, m in zip(coords, maps)]))
            )
    return (names, tuple(pairs))


def scheduled_collective_count(
    groups: Sequence[Sequence[Message]], *, coalesce: bool
) -> int:
    """Collectives one schedule launches per step (hop-free self-copies are
    free).  Uncoalesced: one per hop of every partition of every message.
    Coalesced: one per non-empty (round, hop chain) cell — the composed
    joint permutation — exactly mirroring the delivery choreography."""
    total = 0
    for group in groups:
        if coalesce:
            for chains in coalesced_rounds(group):
                total += sum(1 for hops, _ in chains if hops)
        else:
            for msg in group:
                for part in msg.partitions():
                    total += len(part.hops)
    return total


def schedule_layouts(
    groups: Sequence[Sequence[Message]],
    packer: "str | Packer",
    dtype: Any,
) -> tuple[WireLayout, ...]:
    """All wire-buffer offset tables of a coalesced schedule, in delivery
    order (group, partition round, hop chain) — what a persistent plan
    records at init (:func:`repro.core.plan.transport_plan`)."""
    p = resolve_packer(packer)
    out = []
    for group in groups:
        for chains in coalesced_rounds(group):
            for hops, parts in chains:
                out.append(coalesced_layout(parts, hops, p, dtype))
    return tuple(out)


@dataclasses.dataclass(frozen=True)
class ScheduleInfo:
    """Identity of one compiled transport schedule (for plan names/keys).

    ``kind`` names the choreography (``"sequential"`` axis passes,
    ``"fused"`` single pass, ...); ``mesh_axes`` the axes it spans;
    ``packer``/``transport`` the registered backends it resolves;
    ``coalesce`` whether messages aggregate into per-neighbor wire buffers;
    and ``mapping`` the registered process-to-node placement the mesh was
    built under (:mod:`repro.launch.mapping`) — two meshes of identical
    shape but different rank placement are different plans, never a silent
    cache hit.
    """

    kind: str
    mesh_axes: tuple[str, ...]
    packer: str = "slice"
    transport: str = "ppermute"
    coalesce: bool = False
    mapping: str = "row-major"
    #: how this cell was chosen when the autotuner picked it
    #: (:mod:`repro.core.autotune`); ``None`` for hand-pinned cells
    selected_by: str | None = None
    #: membership epoch of the mesh this schedule was compiled against
    #: (:mod:`repro.launch.membership`).  Every JOIN or in-grid LOSS
    #: recovery bumps the grid's epoch, so a plan built before the
    #: re-formation can never alias one built after it — stale plans
    #: cannot deliver into a re-formed mesh.  ``None`` (the default) means
    #: the caller lives outside the membership domain entirely: such plans
    #: are never epoch-invalidated and their tags/keys are byte-identical
    #: to before epochs existed.  0 is a *stamped* formation epoch.
    epoch: int | None = None

    def tag(self) -> str:
        axes = "x".join(self.mesh_axes) or "-"
        base = f"{self.kind}[{axes}]@{self.packer}/{self.transport}"
        if self.mapping != "row-major":
            base += f"%{self.mapping}"
        if self.selected_by is not None:
            base += f"?{self.selected_by}"
        if self.epoch is not None:
            base += f"!e{self.epoch}"
        return base + ("+coalesced" if self.coalesce else "")


# ---------------------------------------------------------------------------
# hop locality: which scheduled sends cross a node boundary
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class HopLocality:
    """Inter- vs intra-node tally of one schedule's directed sends.

    Counted per *shard-level directed send*: every mesh coordinate sends
    each (expanded-partition) message once, so one message contributes one
    send per coordinate whose full hop chain is defined (clipped
    non-periodic edges drop the send, exactly as the transport drops the
    path).  Hop-free self-copies never touch a wire and are not counted.
    ``*_elems`` weight each send by its slab element count — the
    wire-volume view of the same classification.  Derived purely from the
    static :class:`Message` tables plus a node-id vector, no timing.
    """

    intra_sends: int = 0
    inter_sends: int = 0
    intra_elems: int = 0
    inter_elems: int = 0

    @property
    def total_sends(self) -> int:
        return self.intra_sends + self.inter_sends

    def __add__(self, other: "HopLocality") -> "HopLocality":
        return HopLocality(
            self.intra_sends + other.intra_sends,
            self.inter_sends + other.inter_sends,
            self.intra_elems + other.intra_elems,
            self.inter_elems + other.inter_elems,
        )


def message_locality(
    msg: Message,
    *,
    axis_order: Sequence[str],
    axis_sizes: Mapping[str, int],
    node_of: Sequence[int],
) -> HopLocality:
    """Classify one message's per-shard sends as intra- vs inter-node.

    ``axis_order`` is the mesh's axis-name tuple in mesh-shape order;
    ``node_of[flat_coord]`` is the node id at each row-major mesh
    coordinate (:meth:`repro.launch.mapping.Mapping.node_of`, or
    :func:`repro.launch.mapping.mesh_node_ids` for a live mesh).  Each
    partition of the message is walked over every source coordinate: the
    composed hop chain maps the coordinate to its destination, and the send
    is inter-node iff the two coordinates live on different nodes.
    """
    shape = tuple(axis_sizes[name] for name in axis_order)
    assert len(node_of) == math.prod(shape), (len(node_of), shape)
    index = {name: i for i, name in enumerate(axis_order)}

    def flat(coords: Sequence[int]) -> int:
        idx = 0
        for c, k in zip(coords, shape):
            idx = idx * k + c
        return idx

    out = HopLocality()
    for part in msg.partitions():
        if not part.hops:
            continue  # self-copy: nothing crosses any boundary
        maps = [(index[name], dict(perm)) for name, perm in part.hops]
        elems = math.prod(part.shape)
        intra = inter = 0
        for coords in itertools.product(*[range(k) for k in shape]):
            dst = list(coords)
            for a, m in maps:
                if coords[a] not in m:
                    dst = None  # clipped edge: this shard sends nothing
                    break
                dst[a] = m[coords[a]]
            if dst is None:
                continue
            if node_of[flat(coords)] == node_of[flat(dst)]:
                intra += 1
            else:
                inter += 1
        out = out + HopLocality(intra, inter, intra * elems, inter * elems)
    return out


def schedule_locality(
    groups: Sequence[Sequence[Message]],
    *,
    axis_order: Sequence[str],
    axis_sizes: Mapping[str, int],
    node_of: Sequence[int],
) -> HopLocality:
    """Whole-schedule hop-locality tally (sum over every group's messages).

    This is what the §VI sweep records per cell (``intra_node_sends`` /
    ``inter_node_sends``) and what the mapping acceptance test asserts on:
    a blocked placement must strictly reduce ``inter_sends`` vs row-major
    on a multi-node 2-D grid — from the static tables alone.
    """
    out = HopLocality()
    for group in groups:
        for msg in group:
            out = out + message_locality(
                msg, axis_order=axis_order, axis_sizes=axis_sizes,
                node_of=node_of,
            )
    return out


# ---------------------------------------------------------------------------
# Packer: slab window <-> contiguous wire buffer
# ---------------------------------------------------------------------------


class Packer(abc.ABC):
    """Packs a slab window into a contiguous wire buffer and back.

    ``pack`` reads the window ``[start, start+shape)`` of the local block;
    ``unpack`` writes the received buffer into the (same-shaped) ghost
    window at ``dst_start``.  A packer may re-layout or re-encode the wire
    buffer (dtype conversion, scaling, compression) as long as
    ``unpack(pack(...))`` restores the slab values.
    """

    #: registry key (instances may override per-instance)
    name: ClassVar[str] = ""

    @abc.abstractmethod
    def pack(
        self, x: jax.Array, start: Sequence[int], shape: Sequence[int]
    ) -> jax.Array:
        """Stage the slab window as one contiguous wire buffer."""

    @abc.abstractmethod
    def unpack(
        self,
        x: jax.Array,
        buf: jax.Array,
        dst_start: Sequence[int],
        shape: Sequence[int],
    ) -> jax.Array:
        """Write a received wire buffer into the ghost window of ``x``."""

    # -- coalesced wire buffers (one buffer per neighbor) -------------------
    def pack_coalesced(self, x: jax.Array, layout: WireLayout) -> jax.Array:
        """Fill one coalesced 1-D wire buffer: every segment's slab packed
        at its static offset.  The default stages each segment through
        :meth:`pack` and concatenates (offsets are consecutive by
        construction)."""
        bufs = [
            jnp.ravel(self.pack(x, s.src_start, s.shape))
            for s in layout.segments
        ]
        return bufs[0] if len(bufs) == 1 else jnp.concatenate(bufs)

    def unpack_coalesced(
        self, x: jax.Array, buf: jax.Array, layout: WireLayout
    ) -> jax.Array:
        """Scatter an arrived coalesced buffer into its ghost windows."""
        flat = jnp.ravel(buf)
        for s in layout.segments:
            seg = lax.slice(flat, (s.offset,), (s.offset + s.numel,))
            x = self._unpack_segment(x, seg, s)
        return x

    def _unpack_segment(
        self, x: jax.Array, seg: jax.Array, s: WireSegment
    ) -> jax.Array:
        """One segment of :meth:`unpack_coalesced`; ``seg`` is the 1-D wire
        slice.  Packers whose :meth:`unpack` expects a non-slab wire view
        (the 2-D kernel form) override this reshape."""
        return self.unpack(x, seg.reshape(s.shape), s.dst_start, s.shape)

    # -- wire-format introspection (the sweep's wire-bytes axis) ------------
    def wire_itemsize(self, dtype: Any) -> int:
        """Bytes one block element occupies on the wire (compressed packers
        override; exact packers ship the block dtype unchanged)."""
        return jnp.dtype(dtype).itemsize

    def wire_tolerance(self, dtype: Any) -> tuple[float, float]:
        """``(rtol, atol)`` bound on ``unpack(pack(window))`` vs the window
        for blocks of ``dtype``; ``(0.0, 0.0)`` means the wire is bit-exact
        (the equivalence harness then asserts full bitwise equality)."""
        return (0.0, 0.0)


@dataclasses.dataclass(frozen=True)
class SlicePacker(Packer):
    """The historical inline staging: ``lax.slice`` out, ``lax.
    dynamic_update_slice`` back.  The wire buffer *is* the slab."""

    name: str = "slice"

    def pack(self, x, start, shape):
        limits = [s + n for s, n in zip(start, shape)]
        return lax.slice(x, list(start), limits)

    def unpack(self, x, buf, dst_start, shape):
        assert tuple(buf.shape) == tuple(shape), (buf.shape, shape)
        return lax.dynamic_update_slice(x, buf, tuple(dst_start))


@dataclasses.dataclass(frozen=True)
class PallasPacker(Packer):
    """Comb-pack-kernel analogue: the VMEM-tiled contiguous copy of
    :mod:`repro.kernels.pack`, extended to the N-D slabs the halo schedules
    emit (faces, edges, corners, partitions) via a lane-dense 2-D
    (lead, lane) view.  A coalesced buffer is filled with one kernel copy
    per segment.

    The kernel runs on TPU; elsewhere the packer runs the kernel's jnp
    oracle (:func:`repro.kernels.use_kernel` decides, once per call), so
    it is CI-runnable on virtual CPU devices with bit-identical results.
    ``force_kernel``/``interpret`` pin the Pallas interpreter path for
    kernel-parity tests.  ``wire_dtype`` re-encodes the slab for the wire
    (``None`` ships the block dtype unchanged).
    """

    name: str = "pallas"
    force_kernel: bool = False
    interpret: bool = False
    wire_dtype: Any = None

    def pack(self, x, start, shape):
        from repro.kernels import use_kernel
        from repro.kernels.pack import pack_slab, pack_slab_ref

        limits = [s + n for s, n in zip(start, shape)]
        slab = lax.slice(x, list(start), limits)
        if use_kernel(self.force_kernel):
            return pack_slab(slab, out_dtype=self.wire_dtype,
                             interpret=self.interpret)
        return pack_slab_ref(slab, out_dtype=self.wire_dtype)

    def unpack(self, x, buf, dst_start, shape):
        from repro.kernels import use_kernel
        from repro.kernels.pack import unpack_slab, unpack_slab_ref

        if use_kernel(self.force_kernel):
            ghost = unpack_slab(buf, tuple(shape), out_dtype=x.dtype,
                                interpret=self.interpret)
        else:
            ghost = unpack_slab_ref(buf, tuple(shape), out_dtype=x.dtype)
        return lax.dynamic_update_slice(x, ghost, tuple(dst_start))

    def _unpack_segment(self, x, seg, s):
        # unpack_slab consumes the kernel's 2-D (lead, lane) wire view
        from repro.kernels.pack import view_2d

        return self.unpack(x, seg.reshape(view_2d(s.shape)), s.dst_start,
                           s.shape)


@dataclasses.dataclass(frozen=True)
class Bf16Packer(PallasPacker):
    """Wire-compressed packer: the slab crosses the wire as ``bfloat16``.

    ``pack`` stages the window through the :mod:`repro.kernels.pack` slab
    kernel with a bf16 wire dtype (halving wire bytes for f32 fields);
    ``unpack`` restores the block dtype exactly.  Lossy for dtypes wider
    than bf16: one round-trip keeps 8 bits of significand (round-to-nearest
    error <= 2^-8 relative — half an ulp), and :meth:`wire_tolerance`
    documents 2x that bound (2^-7).
    """

    name: str = "bf16"
    wire_dtype: Any = jnp.bfloat16

    def wire_itemsize(self, dtype):
        return 2  # the wire dtype is always bfloat16

    def wire_tolerance(self, dtype):
        if jnp.dtype(dtype) == jnp.dtype(jnp.bfloat16):
            return (0.0, 0.0)  # the cast is the identity
        return (1.0 / 128.0, 1e-6)  # 2x the bf16 half-ulp relative error


@dataclasses.dataclass(frozen=True)
class ScaledInt8Packer(Packer):
    """Wire-compressed packer: fixed-scale symmetric int8 quantization.

    ``pack`` maps the slab onto the int8 grid ``round(x * 127 / amax)``
    (clipped to ±127); ``unpack`` rescales and restores the block dtype.
    The wire carries one byte per element — a 4x reduction for f32 fields.
    Quantization error is <= ``amax/254`` per element for ``|x| <= amax``;
    values beyond ``±amax`` saturate, so ``amax`` must cover the field's
    dynamic range (the default spans the unit-normal test fields by 8
    standard deviations).
    """

    name: str = "scaled-int8"
    amax: float = 8.0

    def pack(self, x, start, shape):
        limits = [s + n for s, n in zip(start, shape)]
        slab = lax.slice(x, list(start), limits).astype(jnp.float32)
        q = jnp.clip(jnp.round(slab * (127.0 / self.amax)), -127.0, 127.0)
        return q.astype(jnp.int8)

    def unpack(self, x, buf, dst_start, shape):
        assert tuple(buf.shape) == tuple(shape), (buf.shape, shape)
        vals = (buf.astype(jnp.float32) * (self.amax / 127.0)).astype(x.dtype)
        return lax.dynamic_update_slice(x, vals, tuple(dst_start))

    def wire_itemsize(self, dtype):
        return 1

    def wire_tolerance(self, dtype):
        return (0.0, self.amax / 127.0)  # 2x the half-step rounding bound


# ---------------------------------------------------------------------------
# Transport: how packed buffers cross the mesh
# ---------------------------------------------------------------------------


class Transport(abc.ABC):
    """Moves packed buffers between shards along named mesh axes."""

    name: ClassVar[str] = ""

    @abc.abstractmethod
    def permute(
        self,
        buf: jax.Array,
        axis_name: str | tuple[str, ...],
        perm: Sequence[tuple[int, int]],
    ) -> jax.Array:
        """One collective: send ``buf`` along ``axis_name`` per the
        (src, dst) table; shards receiving nothing get zeros (XLA ppermute
        rule).  ``axis_name`` may be a tuple of mesh axes — a composed
        multi-hop chain as ONE joint permutation over the row-major
        linearization of those axes (the coalesced corner route)."""

    def validate(self) -> None:
        """Runtime sanity check, run when the backend is resolved for a
        schedule (cheap: called once per exchange trace, never per group
        or per message)."""

    def route(self, buf: jax.Array, hops: Iterable[Hop]) -> jax.Array:
        """Chain the hops of one message (edges/corners hop per axis)."""
        for axis_name, perm in hops:
            buf = self.permute(buf, axis_name, list(perm))
        return buf

    def route_composed(self, buf: jax.Array, hops: Sequence[Hop]) -> jax.Array:
        """Route a whole hop chain as a SINGLE collective (the coalesced
        path): multi-axis chains compose into one joint permutation via
        :func:`composed_hop`; an empty chain is the hop-free self-copy."""
        hop = composed_hop(hops)
        if hop is None:
            return buf
        axis_name, perm = hop
        return self.permute(buf, axis_name, list(perm))


@dataclasses.dataclass(frozen=True)
class PpermuteTransport(Transport):
    """In-process backend: one ``lax.ppermute`` per hop — XLA's native
    neighbor transport (ICI on a TPU torus, shared-memory copies on the
    virtual-device CPU meshes CI runs)."""

    name: str = "ppermute"

    def permute(self, buf, axis_name, perm):
        return lax.ppermute(buf, axis_name, list(perm))


@dataclasses.dataclass(frozen=True)
class MultiHostTransport(PpermuteTransport):
    """The multi-host backend: same schedule, mesh spanning processes.

    ``lax.ppermute`` inside a global ``shard_map`` lowers to cross-process
    collective-permutes (DCN/ICI on real clusters, gloo on the CPU grids
    ``repro.launch.stencil`` boots) when the mesh's devices belong to
    several processes, so this backend runs today's schedules unchanged
    under ``jax.distributed``; a dedicated backend (e.g. per-hop NCCL rings
    or MPI partitioned sends) overrides :meth:`permute` and registers under
    its own name.  :meth:`is_multihost` reports whether the current runtime
    actually spans processes; the sweep stamps it into the BENCH records
    and config block (``repro.stencil.sweep.config_block``).

    Selecting ``multihost`` in a single-process runtime outside tests warns
    once (:meth:`validate`): the schedule still runs — it degenerates to
    in-process ``ppermute`` — but nothing crosses a host boundary, which is
    almost never what a caller asking for this backend means.  Launch a
    real grid with ``repro.launch.stencil`` (or set
    ``REPRO_ALLOW_SINGLE_PROCESS_MULTIHOST=1`` to silence deliberately).
    """

    name: str = "multihost"

    #: one warning per process, not one per exchange trace
    _warned_single_process: ClassVar[bool] = False

    @staticmethod
    def is_multihost() -> bool:
        return jax.process_count() > 1

    def validate(self) -> None:
        if self.is_multihost() or MultiHostTransport._warned_single_process:
            return
        if (os.environ.get("PYTEST_CURRENT_TEST")
                or os.environ.get("REPRO_ALLOW_SINGLE_PROCESS_MULTIHOST")):
            return
        MultiHostTransport._warned_single_process = True
        warnings.warn(
            "transport='multihost' selected but jax.process_count() == 1: "
            "no message will cross a process boundary (the schedule runs "
            "as in-process ppermute).  Boot a real process grid with "
            "`python -m repro.launch.stencil --processes N ...` or the "
            "sweep's --processes flag; set "
            "REPRO_ALLOW_SINGLE_PROCESS_MULTIHOST=1 if this is deliberate.",
            RuntimeWarning,
            stacklevel=3,
        )


# ---------------------------------------------------------------------------
# registries
# ---------------------------------------------------------------------------

_PACKERS: dict[str, Packer] = {}
_TRANSPORTS: dict[str, Transport] = {}


def register_packer(packer: Packer) -> Packer:
    """Add a packer instance to the registry under ``packer.name``."""
    if not packer.name:
        raise ValueError(f"{type(packer).__name__} must carry a name")
    if packer.name in _PACKERS:
        raise ValueError(f"packer {packer.name!r} already registered")
    _PACKERS[packer.name] = packer
    return packer


def register_transport(transport: Transport) -> Transport:
    """Add a transport instance to the registry under ``transport.name``."""
    if not transport.name:
        raise ValueError(f"{type(transport).__name__} must carry a name")
    if transport.name in _TRANSPORTS:
        raise ValueError(f"transport {transport.name!r} already registered")
    _TRANSPORTS[transport.name] = transport
    return transport


def available_packers() -> tuple[str, ...]:
    return tuple(_PACKERS)


def available_transports() -> tuple[str, ...]:
    return tuple(_TRANSPORTS)


def get_packer(name: str) -> Packer:
    try:
        return _PACKERS[name]
    except KeyError:
        raise KeyError(
            f"unknown packer {name!r}; registered: "
            f"{', '.join(_PACKERS) or '(none)'}"
        ) from None


def get_transport(name: str) -> Transport:
    try:
        return _TRANSPORTS[name]
    except KeyError:
        raise KeyError(
            f"unknown transport {name!r}; registered: "
            f"{', '.join(_TRANSPORTS) or '(none)'}"
        ) from None


def resolve_packer(packer: str | Packer) -> Packer:
    return packer if isinstance(packer, Packer) else get_packer(packer)


def resolve_transport(transport: str | Transport) -> Transport:
    t = transport if isinstance(transport, Transport) else get_transport(transport)
    t.validate()
    return t


register_packer(SlicePacker())
register_packer(PallasPacker())
register_packer(Bf16Packer())
register_packer(ScaledInt8Packer())
register_transport(PpermuteTransport())
register_transport(MultiHostTransport())


# ---------------------------------------------------------------------------
# delivery choreography (runs inside shard_map)
# ---------------------------------------------------------------------------

#: trace-time chaos seam: when set (via :func:`chaos_scope`), the delivery
#: choreography calls it at labeled points — ``"group"`` on entering a
#: delivery group, ``"round"`` before each pipelined partition round.  The
#: points fire while the step is being *traced* (message tables are built at
#: trace time), so a probe raising ``SimulatedFailure`` aborts a plan build
#: mid-assembly — exactly the adversarial window the elastic chaos tests
#: inject into.  ``None`` (the default) is a zero-cost no-op.
_CHAOS_PROBE: Callable[[str], None] | None = None


@contextlib.contextmanager
def chaos_scope(probe: Callable[[str], None] | None):
    """Install ``probe`` as the delivery chaos hook for the dynamic extent
    of the block (``None`` leaves the seam disabled — callers can pass
    their maybe-configured injector through unconditionally)."""
    global _CHAOS_PROBE
    prev, _CHAOS_PROBE = _CHAOS_PROBE, probe
    try:
        yield
    finally:
        _CHAOS_PROBE = prev


def _chaos(point: str) -> None:
    if _CHAOS_PROBE is not None:
        _CHAOS_PROBE(point)


def _deliver_group(
    x: jax.Array,
    messages: Iterable[Message],
    p: Packer,
    t: Transport,
    coalesce: bool,
) -> jax.Array:
    """One delivery group with *resolved* backends (no registry lookups,
    no re-validation — :func:`exchange_messages` hoists those once per
    schedule)."""
    _chaos("group")
    if not coalesce:
        arrived: list[tuple[Message, jax.Array]] = []
        for msg in messages:
            for part in msg.partitions():
                buf = p.pack(x, part.src_start, part.shape)  # pack
                buf = t.route(buf, part.hops)  # start/send
                arrived.append((part, buf))
        for part, buf in arrived:  # unpack (disjoint ghost windows)
            x = p.unpack(x, buf, part.dst_start, part.shape)
        return x

    # Coalesced: one wire buffer and ONE composed collective per (partition
    # round, hop chain) cell.  Every round packs from the group's ORIGINAL
    # buffer — round k+1's pack has no data dependency on round k's route
    # or unpack, so XLA may pack the next partition while the previous
    # coalesced buffer is in flight (the threaded-partitioned-send
    # analogue), and each round's arrivals unpack immediately
    # (``MPI_Parrived``).  Src slabs and dst ghost windows are disjoint
    # within a group, so packing from ``x0`` equals the uncoalesced order.
    x0 = x
    for chains in coalesced_rounds(messages):
        _chaos("round")
        for hops, parts in chains:
            layout = coalesced_layout(parts, hops, p, x0.dtype)
            buf = p.pack_coalesced(x0, layout)
            buf = t.route_composed(buf, hops)
            x = p.unpack_coalesced(x, buf, layout)
    return x


def deliver(
    x: jax.Array,
    messages: Iterable[Message],
    *,
    packer: str | Packer = "slice",
    transport: str | Transport = "ppermute",
    coalesce: bool = False,
) -> jax.Array:
    """Deliver one *group* of independent messages: pack and route every
    message (and every partition, ``MPI_Pready``-style), then unpack all
    arrivals into their disjoint ghost windows (``MPI_Parrived``).

    Within a group no message depends on another, so XLA is free to overlap
    all packs, transfers, and unpacks; sequencing *between* groups (the
    sequential schedule's axis passes) is the caller's ``exchange_messages``.
    With ``coalesce=True`` messages aggregate into one wire buffer and one
    composed collective per hop chain (partitions stay pipelined rounds).
    """
    return _deliver_group(
        x, messages, resolve_packer(packer), resolve_transport(transport),
        coalesce,
    )


def exchange_messages(
    x: jax.Array,
    groups: Sequence[Sequence[Message]],
    *,
    packer: str | Packer = "slice",
    transport: str | Transport = "ppermute",
    coalesce: bool = False,
) -> jax.Array:
    """Deliver a full schedule: groups run in order (group *i+1* packs from
    the buffer group *i* unpacked into — the sequential corner trick),
    messages within a group are independent.  Backends resolve (and the
    transport validates) exactly ONCE per schedule, not per group."""
    p = resolve_packer(packer)
    t = resolve_transport(transport)
    for group in groups:
        x = _deliver_group(x, group, p, t, coalesce)
    return x
