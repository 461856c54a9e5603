"""Ahead-of-time compiles of the stencil path's kernels for a v5e chip.

Nothing runs: each test lowers a kernel at the shapes ``chip_smoke.py`` uses
and compiles it for one chip of a described (not attached) ``v5e:2x2``
topology, which raises what the chip's compiler would raise — an unaligned
block, too much VMEM, an op Mosaic cannot lower.  This is the only file that
describes the topology, inside a module fixture: the TPU library is loaded
only by the worker that runs these tests, and where it cannot be described
every test here skips.
"""

import jax
import jax.numpy as jnp
import pytest

from repro.core.halo import (
    HaloSpec,
    fused_message_group,
    sequential_message_groups,
)
from repro.core.transport import PallasPacker, coalesced_rounds, coalesced_layout
from repro.kernels.pack import pack_2d, unpack_2d
from repro.kernels.stencil27 import stencil27

#: the one-chip smoke block: (512, 1024, 1024) interior, z and y ghosted
BLOCK = (514, 1026, 1024)
#: its z- and y-face slabs through the lane-dense 2-D view
FACE_VIEWS = [(1026, 1024), (514, 1024)]
#: what the 27-point update hands the stencil kernel (lane axis wrapped):
#: the whole block, and ``overlap``'s deep-interior and shell windows
STENCIL_INPUTS = {
    "block": (514, 1026, 1026),
    "overlap-interior": (512, 1024, 1026),
    "overlap-z-shell": (3, 1026, 1026),
    "overlap-y-shell": (514, 3, 1026),
}


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep these compiles out of it
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # noqa: BLE001 - any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    finally:
        jax.config.update("jax_enable_compilation_cache", enabled)


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


@pytest.mark.parametrize("wire", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "f32-bf16"])
@pytest.mark.parametrize("view", FACE_VIEWS, ids=["z-face", "y-face"])
def test_pack_unpack_compile_at_face_views(one_chip, view, wire):
    face = jax.ShapeDtypeStruct(view, jnp.float32, sharding=one_chip)
    _compile(lambda s: pack_2d(s, out_dtype=wire), face)
    buf = jax.ShapeDtypeStruct(view, wire, sharding=one_chip)
    _compile(lambda b: unpack_2d(b, out_dtype=jnp.float32), buf)


@pytest.mark.parametrize("schedule", ["partitioned", "fused"])
def test_coalesced_pack_compiles_at_smoke_layout(one_chip, schedule):
    """Every coalesced wire buffer of the smoke block's schedule, packed and
    unpacked through the kernel path in one program."""
    spec = HaloSpec(mesh_axes=("pz", "py"), array_axes=(0, 1),
                    n_parts=4 if schedule == "partitioned" else 1)
    sizes = {"pz": 1, "py": 1}
    groups = (
        sequential_message_groups(BLOCK, spec, sizes)
        if schedule == "partitioned"
        else (fused_message_group(BLOCK, spec, sizes),)
    )
    packer = PallasPacker(name="pallas-aot", force_kernel=True)
    layouts = [
        coalesced_layout(parts, hops, packer, jnp.float32)
        for group in groups
        for chains in coalesced_rounds(group)
        for hops, parts in chains
    ]
    assert layouts

    def step(x):
        for layout in layouts:
            x = packer.unpack_coalesced(x, packer.pack_coalesced(x, layout),
                                        layout)
        return x

    _compile(step, jax.ShapeDtypeStruct(BLOCK, jnp.float32,
                                        sharding=one_chip))


@pytest.mark.parametrize("shape", list(STENCIL_INPUTS.values()),
                         ids=list(STENCIL_INPUTS))
def test_stencil27_compiles_at_smoke_windows(one_chip, shape):
    x = jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)
    w = jax.ShapeDtypeStruct((3, 3, 3), jnp.float32, sharding=one_chip)
    _compile(stencil27, x, w)
