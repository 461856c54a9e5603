"""Pack/unpack kernel vs pure-jnp oracle + roundtrip properties.

Beyond the historical 2-D face coverage, the slab-level wrappers
(``pack_slab``/``unpack_slab`` — what the transport layer's ``pallas``
packer stages every message through) are held to kernel-vs-oracle parity on
the exact N-D slab shapes the halo schedules emit: sequential full-extent
faces, the fused pass's ``3^D - 1`` face/edge/corner blocks, and clipped
partition windows.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from repro.testing import given, settings, st  # hypothesis or deterministic fallback

from repro.kernels.pack import (
    pack_2d, pack_2d_ref, pack_face, unpack_face,
    pack_slab, pack_slab_ref, unpack_slab, unpack_slab_ref, view_2d,
)


@pytest.mark.parametrize("dtype_in,dtype_out", [
    (jnp.float32, jnp.float32),
    (jnp.float32, jnp.bfloat16),
    (jnp.bfloat16, jnp.bfloat16),
])
@pytest.mark.parametrize("shape,blocks", [
    ((64, 128), (32, 64)),
    ((17, 130), (16, 64)),   # padding path
    ((1, 256), (8, 128)),
    ((300, 7), (64, 8)),
])
def test_pack_2d_matches_ref(dtype_in, dtype_out, shape, blocks):
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=shape), dtype_in)
    got = pack_2d(x, out_dtype=dtype_out, block_lead=blocks[0],
                  block_lane=blocks[1], interpret=True)
    want = pack_2d_ref(x, out_dtype=dtype_out)
    assert got.dtype == want.dtype and got.shape == want.shape
    np.testing.assert_array_equal(np.asarray(got, np.float32), np.asarray(want, np.float32))


def test_pack_2d_scale():
    x = jnp.asarray(np.random.default_rng(1).normal(size=(32, 64)), jnp.float32)
    got = pack_2d(x, out_dtype=jnp.bfloat16, scale=8.0, interpret=True)
    want = pack_2d_ref(x, out_dtype=jnp.bfloat16, scale=8.0)
    np.testing.assert_allclose(np.asarray(got, np.float32), np.asarray(want, np.float32))


@pytest.mark.parametrize("axis", [0, 1, 2])
@pytest.mark.parametrize("side", ["low", "high"])
def test_pack_unpack_face_roundtrip(axis, side):
    """pack one block's face, unpack into the neighbor's ghost: values match."""
    rng = np.random.default_rng(2)
    x = jnp.asarray(rng.normal(size=(10, 12, 14)), jnp.float32)
    halo = 1
    buf = pack_face(x, axis, side, halo, interpret=True)
    # unpack into the *opposite* ghost of a neighbor block
    other = jnp.zeros_like(x)
    ghost_side = "high" if side == "low" else "low"
    filled = unpack_face(other, buf, axis, ghost_side, halo,
                         interpret=True)
    size = x.shape[axis]
    if side == "low":
        want = jax.lax.slice_in_dim(x, halo, 2 * halo, axis=axis)
        got = jax.lax.slice_in_dim(filled, size - halo, size, axis=axis)
    else:
        want = jax.lax.slice_in_dim(x, size - 2 * halo, size - halo, axis=axis)
        got = jax.lax.slice_in_dim(filled, 0, halo, axis=axis)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@settings(max_examples=15, deadline=None)
@given(
    lead=st.integers(1, 80),
    lane=st.integers(1, 200),
    bl=st.sampled_from([8, 16, 32]),
    bn=st.sampled_from([8, 64, 128]),
)
def test_pack_property_arbitrary_shapes(lead, lane, bl, bn):
    """Property: tiled pack == straight copy for any slab shape (padding rule)."""
    rng = np.random.default_rng(lead * 1000 + lane)
    x = jnp.asarray(rng.normal(size=(lead, lane)), jnp.float32)
    got = pack_2d(x, block_lead=bl, block_lane=bn, interpret=True)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(x))


def test_wire_compression_halves_bytes():
    """bf16 wire format: pack halves bytes; unpack restores within bf16 eps."""
    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.normal(size=(64, 64)), jnp.float32)
    buf = pack_2d(x, out_dtype=jnp.bfloat16, interpret=True)
    assert buf.dtype == jnp.bfloat16 and buf.size == x.size
    back = np.asarray(buf, np.float32)
    np.testing.assert_allclose(back, np.asarray(x), rtol=1e-2, atol=1e-2)


# ---------------------------------------------------------------------------
# slab-level parity: the shapes the halo schedules actually emit
# ---------------------------------------------------------------------------

#: ghosted local blocks the tier-1 stencil lane runs (halo=1 unless noted)
HALO_BLOCKS = [
    ((6,), ("px",), 1),           # 1-D block
    ((6, 10), ("px",), 1),        # 2-D, one decomposed axis
    ((8, 6), ("px", "py"), 2),    # 2-D, both axes, halo 2
    ((6, 6, 5), ("px", "py"), 1),  # 3-D, two decomposed axes
]


def _halo_slab_shapes(shape, names, halo):
    """Every slab shape the sequential + fused schedules pack for a block."""
    from repro.core.halo import HaloSpec, fused_slab_table

    spec = HaloSpec(
        mesh_axes=tuple(names), array_axes=tuple(range(len(names))),
        halo=halo,
    )
    shapes = set()
    for a in spec.array_axes:  # sequential full-extent faces
        s = list(shape)
        s[a] = halo
        shapes.add(tuple(s))
    for slab in fused_slab_table(shape, spec):  # fused faces/edges/corners
        shapes.add(slab.shape)
    return sorted(shapes)


@pytest.mark.parametrize("shape,names,halo", HALO_BLOCKS)
def test_pack_slab_kernel_matches_ref_on_halo_shapes(shape, names, halo):
    """Kernel (interpreter) == jnp oracle on every emitted slab shape."""
    rng = np.random.default_rng(11)
    for slab_shape in _halo_slab_shapes(shape, names, halo):
        slab = jnp.asarray(rng.normal(size=slab_shape), jnp.float32)
        got = pack_slab(slab, interpret=True)
        want = pack_slab_ref(slab)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
        back = unpack_slab(got, slab_shape, interpret=True)
        np.testing.assert_array_equal(np.asarray(back), np.asarray(slab))
        np.testing.assert_array_equal(
            np.asarray(unpack_slab_ref(want, slab_shape)), np.asarray(slab)
        )


def test_pack_slab_partition_windows_roundtrip():
    """Clipped partition windows (equal-size grid tails) survive the
    kernel pack/unpack — incl. the width-1 tail a non-dividing split makes."""
    from repro.core.transport import Message

    msg = Message((1, 0, 0), (5, 0, 0), (1, 7, 5), n_parts=3, part_axis=1)
    rng = np.random.default_rng(12)
    for part in msg.partitions():
        slab = jnp.asarray(rng.normal(size=part.shape), jnp.float32)
        buf = pack_slab(slab, interpret=True)
        back = unpack_slab(buf, part.shape, interpret=True)
        np.testing.assert_array_equal(np.asarray(back), np.asarray(slab))


#: slabs whose trailing dims are 1 or halo-wide: the faces of a block whose
#: last (lane) axis is decomposed
THIN_TRAILING_SLABS = [
    ((6, 200, 1), (6, 200)),
    ((3, 100, 2), (3, 200)),
    ((4, 300, 1, 1), (4, 300)),
    ((2, 3, 50, 2), (2, 300)),
    ((5, 7), (1, 35)),
]


@pytest.mark.parametrize("shape,view", THIN_TRAILING_SLABS)
def test_pack_slab_lane_dense_view_roundtrip(shape, view):
    """A slab with thin trailing dims packs through a lane-dense 2-D view
    (never an (N, 1) column), and unpack restores it bit-exactly."""
    rng = np.random.default_rng(15)
    slab = jnp.asarray(rng.normal(size=shape), jnp.float32)
    assert view_2d(shape) == view
    buf = pack_slab(slab, interpret=True)
    assert buf.shape == view
    np.testing.assert_array_equal(np.asarray(buf),
                                  np.asarray(pack_slab_ref(slab)))
    back = unpack_slab(buf, shape, interpret=True)
    np.testing.assert_array_equal(np.asarray(back), np.asarray(slab))


def test_pack_slab_wire_compression_roundtrip():
    """bf16 wire format on an N-D slab: bytes halve, values within bf16 eps."""
    rng = np.random.default_rng(13)
    slab = jnp.asarray(rng.normal(size=(2, 12, 7)), jnp.float32)
    buf = pack_slab(slab, out_dtype=jnp.bfloat16, interpret=True)
    assert buf.dtype == jnp.bfloat16 and buf.size == slab.size
    back = unpack_slab(buf, slab.shape, out_dtype=jnp.float32,
                       interpret=True)
    np.testing.assert_allclose(np.asarray(back), np.asarray(slab),
                               rtol=1e-2, atol=1e-2)


def test_registered_compressed_packers_roundtrip_halo_slabs():
    """The registered wire-compressed packers (bf16 via the slab kernel
    wrappers, scaled-int8 quantization) round-trip every slab shape the
    halo schedules emit, within each packer's documented tolerance, and
    restore the block dtype exactly."""
    import jax.numpy as jnp

    from repro.core.transport import get_packer

    rng = np.random.default_rng(23)
    for packer_name in ("bf16", "scaled-int8"):
        p = get_packer(packer_name)
        rtol, atol = p.wire_tolerance(jnp.float32)
        for shape, names, halo in HALO_BLOCKS:
            block = jnp.asarray(rng.normal(size=shape), jnp.float32)
            for slab_shape in _halo_slab_shapes(shape, names, halo):
                start = (0,) * len(shape)
                buf = p.pack(block, start, slab_shape)
                out = p.unpack(jnp.zeros_like(block), buf, start, slab_shape)
                assert out.dtype == block.dtype, packer_name
                window = tuple(slice(0, n) for n in slab_shape)
                np.testing.assert_allclose(
                    np.asarray(out)[window], np.asarray(block)[window],
                    rtol=rtol, atol=atol,
                    err_msg=f"{packer_name} slab={slab_shape}",
                )


def test_bf16_packer_wire_matches_slab_kernel():
    """Bf16Packer's wire buffer IS pack_slab's bf16 wire format — the
    compressed packer rides the same kernel path as `pallas`."""
    from repro.core.transport import get_packer

    rng = np.random.default_rng(24)
    block = jnp.asarray(rng.normal(size=(6, 10)), jnp.float32)
    buf = get_packer("bf16").pack(block, (1, 2), (2, 7))
    want = pack_slab(
        jax.lax.slice(block, (1, 2), (3, 9)), out_dtype=jnp.bfloat16,
        interpret=True,
    )
    assert buf.dtype == jnp.bfloat16
    np.testing.assert_array_equal(
        np.asarray(buf, np.float32), np.asarray(want, np.float32)
    )


def test_pack_slab_cpu_fallback_is_oracle():
    """Off-TPU (no force_kernel) the pallas packer packs through the oracle
    — the CPU path the equivalence matrix relies on."""
    from repro.core.transport import get_packer

    assert jax.default_backend() != "tpu", "test assumes CPU/virtual devices"
    rng = np.random.default_rng(14)
    block = jnp.asarray(rng.normal(size=(3, 9, 4)), jnp.float32)
    np.testing.assert_array_equal(
        np.asarray(get_packer("pallas").pack(block, (0, 0, 0), (3, 9, 4))),
        np.asarray(pack_slab_ref(block)),
    )
