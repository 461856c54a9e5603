from repro.kernels.pack.pack import pack_2d, unpack_2d
from repro.kernels.pack.ops import (
    pack_face, unpack_face, pack_slab, unpack_slab,
)
from repro.kernels.pack.ref import (
    pack_2d_ref, unpack_2d_ref, pack_face_ref, pack_slab_ref, unpack_slab_ref,
    view_2d,
)

__all__ = [
    "pack_2d", "unpack_2d", "pack_face", "unpack_face",
    "pack_slab", "unpack_slab",
    "pack_2d_ref", "unpack_2d_ref", "pack_face_ref",
    "pack_slab_ref", "unpack_slab_ref", "view_2d",
]
