"""Public wrapper: model-layout WKV with Pallas fast path on TPU."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels import use_kernel
from repro.kernels.wkv.wkv import wkv_chunked
from repro.kernels.wkv.ref import wkv_chunked_ref


def wkv(
    r: jax.Array,  # (B, T, H, hd)
    k: jax.Array,
    v: jax.Array,
    lw: jax.Array,
    u: jax.Array,  # (H, hd)
    *,
    chunk: int = 16,
    force_kernel: bool = False,
    interpret: bool = False,
) -> jax.Array:
    """Model-layout WKV; (B, T, H, hd) -> (B, T, H, hd)."""
    B, T, H, hd = r.shape
    def flat(x):
        return x.transpose(0, 2, 1, 3).reshape(B * H, T, hd)

    uf = jnp.broadcast_to(u[None], (B, H, hd)).reshape(B * H, 1, hd)
    if use_kernel(force_kernel):
        y = wkv_chunked(flat(r), flat(k), flat(v), flat(lw), uf, chunk=chunk,
                        interpret=interpret)
    else:
        y = wkv_chunked_ref(flat(r), flat(k), flat(v), flat(lw), uf,
                            chunk=chunk)
    return y.reshape(B, H, T, hd).transpose(0, 2, 1, 3)
