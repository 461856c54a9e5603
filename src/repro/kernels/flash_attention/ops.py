"""Public jit'd wrappers for the flash attention kernel.

``attention(q, k, v)`` takes the model-layout tensors (B, S, H, D) and
dispatches to the Pallas kernel (TPU) or the jnp oracle (CPU and odd shapes).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels import use_kernel
from repro.kernels.flash_attention.flash import flash_attention
from repro.kernels.flash_attention.ref import attention_ref


def _use_kernel(sq: int, skv: int, d: int, block_q: int, block_kv: int) -> bool:
    if not use_kernel():
        return False
    bq, bkv = min(block_q, sq), min(block_kv, skv)
    return sq % bq == 0 and skv % bkv == 0 and d % 128 == 0


def attention(
    q: jax.Array,  # (B, Sq, Hq, D)
    k: jax.Array,  # (B, Skv, Hkv, D)
    v: jax.Array,  # (B, Skv, Hkv, D)
    *,
    causal: bool = True,
    scale: float | None = None,
    block_q: int = 128,
    block_kv: int = 128,
    force_kernel: bool = False,
    interpret: bool = False,
) -> jax.Array:
    """Multi-head attention with model-layout (B, S, H, D) tensors."""
    qt = jnp.swapaxes(q, 1, 2)
    kt = jnp.swapaxes(k, 1, 2)
    vt = jnp.swapaxes(v, 1, 2)
    if force_kernel or _use_kernel(q.shape[1], k.shape[1], q.shape[-1], block_q, block_kv):
        out = flash_attention(
            qt, kt, vt, causal=causal, scale=scale,
            block_q=block_q, block_kv=block_kv, interpret=interpret,
        )
    else:
        out = attention_ref(qt, kt, vt, causal=causal, scale=scale)
    return jnp.swapaxes(out, 1, 2)
