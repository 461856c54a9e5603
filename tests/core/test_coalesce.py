"""Coalesced wire-buffer transport: layouts, composed routes, plan keys.

The coalescing layer's contracts: static :class:`WireLayout` offset tables
round-trip mixed slab shapes through one buffer, partitioned rounds stay
pipelined and clipped (non-dividing ``n_parts``), compressed packers lay the
buffer out at their ``wire_itemsize``, backends resolve exactly once per
schedule, coalesced vs. uncoalesced plans never share a cache entry, and —
the headline — a coalesced fused 3-D step compiles to exactly ONE
collective per distinct hop chain where the uncoalesced step launches one
per hop of every message.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import compat
from repro.core.transport import (
    Message,
    Packer,
    PallasPacker,
    PpermuteTransport,
    SlicePacker,
    WireLayout,
    WireSegment,
    coalesced_layout,
    coalesced_rounds,
    composed_hop,
    deliver,
    exchange_messages,
    get_packer,
    schedule_layouts,
    scheduled_collective_count,
)

pytestmark = pytest.mark.skipif(
    len(jax.devices()) < 8, reason="needs 8 virtual devices (conftest)"
)


# ---------------------------------------------------------------------------
# offset tables
# ---------------------------------------------------------------------------


def _chain(axis_name="px", k=4, shift=1):
    return ((axis_name, tuple((i, (i + shift) % k) for i in range(k))),)


def test_layout_offsets_tile_mixed_slab_shapes():
    """Mixed face/edge/corner-shaped slabs lay end-to-end: offsets are the
    running element sum, total covers the buffer exactly."""
    hops = _chain()
    msgs = [
        Message((1, 0, 0), (5, 0, 0), (1, 6, 4), hops),   # face: 24 elems
        Message((1, 1, 0), (5, 5, 0), (1, 1, 4), hops),   # edge: 4
        Message((1, 1, 1), (5, 5, 5), (1, 1, 1), hops),   # corner: 1
    ]
    layout = coalesced_layout(msgs, hops, get_packer("slice"), jnp.float32)
    assert [s.offset for s in layout.segments] == [0, 24, 28]
    assert [s.numel for s in layout.segments] == [24, 4, 1]
    assert layout.total == 29
    assert layout.wire_itemsize == 4 and layout.wire_bytes == 116


@pytest.mark.parametrize("packer,itemsize", [
    ("slice", 4), ("pallas", 4), ("bf16", 2), ("scaled-int8", 1),
])
def test_layout_wire_itemsize_tracks_packer(packer, itemsize):
    """The offset table is wire_itemsize-aware: element offsets are shared,
    byte footprints shrink under the compressed packers."""
    hops = _chain()
    msgs = [Message((0, 0), (0, 0), (2, 8), hops)]
    layout = coalesced_layout(msgs, hops, get_packer(packer), jnp.float32)
    assert layout.wire_itemsize == itemsize
    assert layout.wire_bytes == 16 * itemsize


def test_layout_rejects_foreign_chains_and_partitioned_messages():
    hops = _chain()
    with pytest.raises(AssertionError):
        coalesced_layout(
            [Message((0,), (0,), (4,), _chain(shift=-1))], hops,
            get_packer("slice"), jnp.float32,
        )
    with pytest.raises(AssertionError):
        coalesced_layout(
            [Message((0, 0), (0, 0), (2, 8), hops, n_parts=2, part_axis=1)],
            hops, get_packer("slice"), jnp.float32,
        )


def test_coalesced_rounds_pipeline_clipped_partitions():
    """Non-dividing n_parts: round r holds every message's r-th clipped
    partition; all-padding tails vanish, so late rounds thin out."""
    hops = _chain()
    msgs = [
        # extent 10 over 4 parts: widths 3,3,3,1
        Message((0, 0), (8, 0), (1, 10), hops, n_parts=4, part_axis=1),
        # extent 2 over 4 parts: widths 1,1 then all-padding tails
        Message((1, 0), (9, 0), (1, 2), hops, n_parts=4, part_axis=1),
    ]
    rounds = coalesced_rounds(msgs)
    assert len(rounds) == 4
    widths = [
        [p.shape[1] for _, parts in chains for p in parts]
        for chains in rounds
    ]
    assert widths == [[3, 1], [3, 1], [3], [1]]
    # each round is one chain here -> one collective per round
    assert scheduled_collective_count([msgs], coalesce=True) == 4
    assert scheduled_collective_count([msgs], coalesce=False) == 6


def test_scheduled_count_merges_shared_chains_and_skips_self_copies():
    to_peer = _chain()
    local = Message((0,), (4,), (2,))  # hop-free self-copy
    a = Message((0, 0), (6, 0), (1, 4), to_peer)
    b = Message((1, 0), (7, 0), (1, 4), to_peer)
    # coalesced: a+b share one chain (1 collective); the self-copy is free
    assert scheduled_collective_count([(local, a, b)], coalesce=True) == 1
    assert scheduled_collective_count([(local, a, b)], coalesce=False) == 2


def test_schedule_layouts_enumerate_delivery_order():
    hops = _chain()
    msgs = [
        Message((0, 0), (6, 0), (1, 6), hops, n_parts=2, part_axis=1),
        Message((1, 0), (7, 0), (1, 6), hops, n_parts=2, part_axis=1),
    ]
    layouts = schedule_layouts([msgs], "bf16", jnp.float32)
    assert len(layouts) == 2  # one buffer per partition round
    for layout in layouts:
        assert isinstance(layout, WireLayout)
        assert len(layout.segments) == 2  # both messages share the chain
        assert layout.total == 6 and layout.wire_itemsize == 2


# ---------------------------------------------------------------------------
# composed hops
# ---------------------------------------------------------------------------


def test_composed_hop_identities():
    assert composed_hop(()) is None
    single = _chain()[0]
    assert composed_hop((single,)) == single


def test_composed_hop_joint_permutation_on_mesh():
    """Inside shard_map a 2-hop chain composes to the row-major joint
    table, dropping sources either per-axis table clips away."""
    from jax.sharding import PartitionSpec as P

    mesh = compat.make_mesh((2, 2), ("px", "py"), devices=jax.devices()[:4])
    seen = {}

    def probe(xl):
        hop_x = ("px", ((0, 1), (1, 0)))
        hop_y = ("py", ((0, 1),))  # clipped: source 1 has no hop
        seen["hop"] = composed_hop((hop_x, hop_y))
        return xl

    compat.shard_map(
        probe, mesh=mesh, in_specs=P("px", "py"), out_specs=P("px", "py")
    )(jnp.zeros((2, 2)))
    names, pairs = seen["hop"]
    assert names == ("px", "py")
    # (i,j) -> (1-i, 1) for j == 0 only; linearized row-major over (2, 2)
    assert sorted(pairs) == [(0, 3), (2, 1)]


# ---------------------------------------------------------------------------
# coalesced delivery on a mesh
# ---------------------------------------------------------------------------


def _ring_messages(shape, axis_name, k, halo=1):
    size = shape[0]
    to_left = tuple((i, (i - 1) % k) for i in range(k))
    to_right = tuple((i, (i + 1) % k) for i in range(k))

    def w(src_edge, dst_edge):
        src, dst, sz = [0] * len(shape), [0] * len(shape), list(shape)
        src[0], dst[0], sz[0] = src_edge, dst_edge, halo
        return tuple(src), tuple(dst), tuple(sz)

    left = Message(*w(halo, size - halo), ((axis_name, to_left),))
    right = Message(*w(size - 2 * halo, 0), ((axis_name, to_right),))
    return (left, right)


@pytest.mark.parametrize("packer", ["slice", "pallas", "bf16", "scaled-int8"])
@pytest.mark.parametrize("n_parts", [1, 3, 7])
def test_coalesced_delivery_matches_uncoalesced(packer, n_parts):
    """The oracle across packers and non-dividing partition counts: the
    coalesced pipeline moves exactly the cells the per-message one moves
    (within the packer's wire tolerance; both paths quantize identically,
    so the comparison is bitwise even for lossy packers)."""
    from jax.sharding import PartitionSpec as P

    k = 4
    mesh = compat.make_mesh((k,), ("px",), devices=jax.devices()[:k])
    rng = np.random.default_rng(5)
    x = jnp.asarray(rng.normal(size=(k * 4, 5)), jnp.float32)
    msgs = tuple(
        dataclasses.replace(m, n_parts=n_parts,
                            part_axis=1 if n_parts > 1 else None)
        for m in _ring_messages((4, 5), "px", k)
    )

    def run(coalesce):
        def step(xl):
            return deliver(xl, msgs, packer=packer, coalesce=coalesce)

        return np.asarray(
            compat.shard_map(
                step, mesh=mesh, in_specs=P("px", None),
                out_specs=P("px", None),
            )(x)
        )

    np.testing.assert_array_equal(run(True), run(False))


def test_coalesced_multi_hop_route_reaches_diagonal_neighbor():
    """A 2-hop corner message coalesces into ONE joint-permutation
    collective and still lands on the diagonal peer."""
    from jax.sharding import PartitionSpec as P

    mesh = compat.make_mesh((2, 2), ("px", "py"), devices=jax.devices()[:4])
    x = jnp.arange(16.0).reshape(4, 4)
    hop = tuple((i, (i + 1) % 2) for i in range(2))
    msg = Message((0, 0), (1, 1), (1, 1), (("px", hop), ("py", hop)))

    def step(xl):
        return exchange_messages(xl, ((msg,),), coalesce=True)

    got = np.asarray(
        compat.shard_map(
            step, mesh=mesh, in_specs=P("px", "py"), out_specs=P("px", "py")
        )(x)
    )
    xg = np.asarray(x)
    for i in range(2):
        for j in range(2):
            want = xg[2 * ((i + 1) % 2), 2 * ((j + 1) % 2)]
            assert got[2 * i + 1, 2 * j + 1] == want, (i, j)


def test_coalesced_backends_observe_one_buffer_per_chain():
    """Counting backends: two messages sharing a chain cross the packer as
    ONE coalesced buffer and the transport as ONE collective; the pallas
    packer's gather-pack fuses the fill into one launch."""
    from jax.sharding import PartitionSpec as P

    calls = {"pack_coalesced": 0, "unpack": 0, "permute": 0}

    @dataclasses.dataclass(frozen=True)
    class CountingPacker(SlicePacker):
        name: str = "counting-coal-test"

        def pack_coalesced(self, x, layout):
            calls["pack_coalesced"] += 1
            return super().pack_coalesced(x, layout)

        def unpack(self, x, buf, dst_start, shape):
            calls["unpack"] += 1
            return super().unpack(x, buf, dst_start, shape)

    @dataclasses.dataclass(frozen=True)
    class CountingTransport(PpermuteTransport):
        name: str = "counting-coal-test"

        def permute(self, buf, axis_name, perm):
            calls["permute"] += 1
            return super().permute(buf, axis_name, perm)

    k = 4
    mesh = compat.make_mesh((k,), ("px",), devices=jax.devices()[:k])
    x = jnp.arange(k * 4 * 6, dtype=jnp.float32).reshape(k * 4, 6)
    chain = _chain(k=k)
    msgs = (
        Message((1, 0), (13, 0), (1, 6), chain),
        Message((2, 0), (14, 0), (1, 6), chain),
    )

    def step(xl):
        return deliver(xl, msgs, packer=CountingPacker(),
                       transport=CountingTransport(), coalesce=True)

    compat.shard_map(
        step, mesh=mesh, in_specs=P("px", None), out_specs=P("px", None)
    )(x)
    # 2 messages, ONE chain: one coalesced pack, one collective, two
    # scatter-unpacks into the disjoint ghost windows
    assert calls == {"pack_coalesced": 1, "permute": 1, "unpack": 2}


def test_pallas_gather_pack_fills_buffer_in_one_launch():
    """The pack kernel (interpreter-pinned), one copy per segment, produces
    the same coalesced buffer as the per-slab reference concatenation."""
    p = PallasPacker(name="pallas-gather-test", force_kernel=True,
                     interpret=True)
    rng = np.random.default_rng(9)
    x = jnp.asarray(rng.normal(size=(8, 6, 4)), jnp.float32)
    hops = _chain()
    msgs = [  # mixed slab shapes, disjoint dst ghost windows
        Message((1, 0, 0), (7, 0, 0), (1, 6, 4), hops),
        Message((1, 1, 1), (0, 4, 2), (1, 2, 2), hops),
        Message((2, 2, 0), (1, 2, 0), (3, 1, 4), hops),
    ]
    layout = coalesced_layout(msgs, hops, p, x.dtype)
    got = p.pack_coalesced(x, layout)
    want = SlicePacker().pack_coalesced(x, layout)
    assert got.shape == (layout.total,)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    # and the scatter-unpack inverse restores every window
    ghost = jnp.zeros_like(x)
    out = p.unpack_coalesced(ghost, got, layout)
    for s in layout.segments:
        window = tuple(slice(b, b + n) for b, n in zip(s.src_start, s.shape))
        dst = tuple(slice(b, b + n) for b, n in zip(s.dst_start, s.shape))
        np.testing.assert_array_equal(np.asarray(out[dst]),
                                      np.asarray(x[window]))


def test_bf16_coalesced_buffer_ships_compressed_wire():
    """The bf16 packer's coalesced buffer is bfloat16 end-to-end (half the
    wire bytes) and unpacks within the documented tolerance."""
    p = get_packer("bf16")
    rng = np.random.default_rng(13)
    x = jnp.asarray(rng.normal(size=(6, 8)), jnp.float32)
    hops = _chain()
    msgs = [Message((1, 0), (5, 0), (1, 8), hops),
            Message((0, 2), (0, 6), (4, 2), hops)]
    layout = coalesced_layout(msgs, hops, p, x.dtype)
    buf = p.pack_coalesced(x, layout)
    assert buf.dtype == jnp.bfloat16 and buf.shape == (layout.total,)
    assert layout.wire_bytes == layout.total * 2
    out = p.unpack_coalesced(jnp.zeros_like(x), buf, layout)
    assert out.dtype == x.dtype
    rtol, atol = p.wire_tolerance(x.dtype)
    np.testing.assert_allclose(np.asarray(out)[5, :8], np.asarray(x)[1, :8],
                               rtol=rtol, atol=atol)
    np.testing.assert_allclose(np.asarray(out)[:4, 6:8],
                               np.asarray(x)[:4, 2:4], rtol=rtol, atol=atol)


def test_scaled_int8_coalesced_buffer_is_one_byte_per_element():
    p = get_packer("scaled-int8")
    x = jnp.asarray([[0.5, -0.25, 1.0, 2.0]], jnp.float32)
    hops = _chain()
    msgs = [Message((0, 0), (0, 0), (1, 2), hops),
            Message((0, 2), (0, 2), (1, 2), hops)]
    layout = coalesced_layout(msgs, hops, p, x.dtype)
    buf = p.pack_coalesced(x, layout)
    assert buf.dtype == jnp.int8 and layout.wire_bytes == 4
    out = p.unpack_coalesced(jnp.zeros_like(x), buf, layout)
    rtol, atol = p.wire_tolerance(x.dtype)
    np.testing.assert_allclose(np.asarray(out), np.asarray(x),
                               rtol=rtol, atol=atol)


# ---------------------------------------------------------------------------
# backends resolve once per schedule (the hoisted resolve_* fix)
# ---------------------------------------------------------------------------


def test_exchange_messages_validates_transport_once_per_schedule():
    """A multi-group schedule must resolve/validate the transport exactly
    once — not once per group (the historical per-deliver re-validation)."""
    from jax.sharding import PartitionSpec as P
    from repro.core import transport as T

    validations = []

    @dataclasses.dataclass(frozen=True)
    class ValidatingTransport(PpermuteTransport):
        name: str = "validating-test"

        def validate(self):
            validations.append(1)

    T.register_transport(ValidatingTransport())
    try:
        k = 4
        mesh = compat.make_mesh((k,), ("px",), devices=jax.devices()[:k])
        x = jnp.arange(k * 4 * 3, dtype=jnp.float32).reshape(k * 4, 3)
        group = _ring_messages((4, 3), "px", k)

        def step(xl):
            return exchange_messages(
                xl, (group, group, group), transport="validating-test",
            )

        compat.shard_map(
            step, mesh=mesh, in_specs=P("px", None), out_specs=P("px", None)
        )(x)
        assert sum(validations) == 1, "validate must run once per schedule"
    finally:
        del T._TRANSPORTS["validating-test"]


# ---------------------------------------------------------------------------
# plan identity: coalesce mode is part of the compiled schedule's key
# ---------------------------------------------------------------------------


def test_coalesced_and_uncoalesced_plans_get_distinct_keys():
    """A shared PlanCache must MISS when only the coalesce mode differs
    (the wire choreography is baked into the executable) and HIT on a
    true repeat; the coalesced plan records its offset tables."""
    from repro.core.plan import PlanCache
    from repro.stencil.domain import Domain
    from repro.stencil.strategies import StrategyConfig, make_driver

    mesh = compat.make_mesh((4,), ("px",), devices=jax.devices()[:4])
    domain = Domain(mesh, global_interior=(16, 8), mesh_axes=("px", None))
    cache = PlanCache()

    def drive(coalesce):
        drv = make_driver(
            StrategyConfig(name="persistent", coalesce=coalesce,
                           plan_cache=cache),
            domain.mesh, domain.halo_spec, ndim=2,
        )
        drv.wait(drv.step(domain.random(0)))
        plan = drv._plan
        drv.free()
        return plan

    coalesced = drive(True)
    uncoalesced = drive(False)
    assert len(cache) == 2, "coalesce change must not hit the cached plan"
    assert cache.stats.inits == 2 and cache.stats.cache_hits == 0
    drive(True)  # identical geometry AND coalesce mode: amortized
    assert len(cache) == 2 and cache.stats.cache_hits == 1
    # the schedule identity and static offset tables ride on the plan
    assert coalesced.schedule.coalesce is True
    assert coalesced.name.endswith("@slice")  # plan name unchanged
    assert coalesced.wire_layouts and all(
        isinstance(l, WireLayout) for l in coalesced.wire_layouts
    )
    assert uncoalesced.schedule.coalesce is False
    assert uncoalesced.wire_layouts == ()
    cache.free_all()


# ---------------------------------------------------------------------------
# the headline: one collective per distinct hop chain in compiled HLO
# ---------------------------------------------------------------------------


def _fused_driver(domain, coalesce, n_parts=1, strategy="fused"):
    from repro.stencil.strategies import StrategyConfig, make_driver

    return make_driver(
        StrategyConfig(name=strategy, coalesce=coalesce, n_parts=n_parts),
        domain.mesh, domain.halo_spec,
        ndim=len(domain.global_interior),
    )


def test_fused_3d_coalesced_step_is_one_collective_per_hop_chain():
    """hlo_analysis acceptance: on a 2x2x2 torus a fused 3-D step has 26
    neighbor messages; coalesced they compile to exactly one
    collective-permute per DISTINCT hop chain (7 here — the +-1 hops of a
    2-wide periodic axis share one neighbor table, so chains merge), while
    the uncoalesced step launches one per hop of every message (54)."""
    from repro.core.halo import fused_message_group
    from repro.core.hlo_analysis import parse_collectives
    from repro.stencil.domain import Domain

    mesh = compat.make_mesh((2, 2, 2), ("px", "py", "pz"),
                            devices=jax.devices()[:8])
    domain = Domain(mesh, global_interior=(8, 6, 4),
                    mesh_axes=("px", "py", "pz"))
    x = domain.random(0)

    spec = domain.halo_spec()
    local_shape = tuple(
        g // mesh.shape[name] + 2 for g, name in
        zip(domain.global_interior, ("px", "py", "pz"))
    )
    group = fused_message_group(
        local_shape, spec, {n: 2 for n in ("px", "py", "pz")}
    )
    assert len(group) == 26  # 3^3 - 1 neighbor messages
    distinct_chains = {m.hops for m in group}

    counts = {}
    for coalesce in (True, False):
        drv = _fused_driver(domain, coalesce)
        stats = parse_collectives(drv.compiled_text(x))
        counts[coalesce] = stats.by_op_counts.get("collective-permute", 0)
        assert counts[coalesce] == drv.scheduled_collectives(x)
        drv.free()
    assert counts[True] == len(distinct_chains) == 7
    assert counts[False] == sum(len(m.hops) for m in group) == 54


def test_wide_mesh_fused_chains_compile_per_distinct_chain():
    """On a (4, 2) mesh the 4-wide axis keeps left/right chains distinct
    while the 2-wide axis merges its +-1 chains, leaving 5 distinct chains
    for the 8 fused 2-D messages: the coalesced step compiles to exactly
    those 5 collectives (vs 12 per-hop uncoalesced)."""
    from repro.core.halo import fused_message_group
    from repro.core.hlo_analysis import parse_collectives
    from repro.stencil.domain import Domain

    mesh = compat.make_mesh((4, 2), ("px", "py"), devices=jax.devices()[:8])
    domain = Domain(mesh, global_interior=(16, 8), mesh_axes=("px", "py"))
    x = domain.random(0)
    group = fused_message_group(
        (6, 6), domain.halo_spec(), {"px": 4, "py": 2}
    )
    assert len(group) == 8
    distinct_chains = {m.hops for m in group}
    assert len(distinct_chains) == 5
    for coalesce, want in ((True, 5), (False, 12)):
        drv = _fused_driver(domain, coalesce)
        stats = parse_collectives(drv.compiled_text(x))
        assert stats.by_op_counts.get("collective-permute", 0) == want
        assert drv.scheduled_collectives(x) == want
        drv.free()


def test_partitioned_coalesced_keeps_per_partition_collectives():
    """Partitions stay pipelined under coalescing: each partition round is
    its own collective (the early-arrival semantics), so a 2-part
    sequential exchange halves its collectives only through the shared
    2-wide-axis chains, never by merging rounds."""
    from repro.core.hlo_analysis import parse_collectives
    from repro.stencil.domain import Domain

    mesh = compat.make_mesh((2, 2), ("px", "py"), devices=jax.devices()[:4])
    domain = Domain(mesh, global_interior=(8, 8), mesh_axes=("px", "py"))
    x = domain.random(0)
    for coalesce, want in ((True, 4), (False, 8)):
        drv = _fused_driver(domain, coalesce, n_parts=2,
                            strategy="partitioned")
        stats = parse_collectives(drv.compiled_text(x))
        # 2 axes x 2 rounds x (1 merged chain if coalesced else 2 messages)
        assert stats.by_op_counts.get("collective-permute", 0) == want
        assert drv.scheduled_collectives(x) == want
        drv.free()
