"""jit'd public wrapper for the flash-decode kernel.

Model code calls flash_decode(q, k, v, kv_valid=...) in the cache layout
([B, 1, Hq, D] query, [B, cap, Hkv, D] cache); this regroups query heads
under their kv head for the kernel's GQA blocking, transposes to
[B, Hkv, cap, D], and picks interpret mode on CPU (the container
validates kernels in interpret mode; TPU is the target). Decode is
inference-only, so unlike flash_attention there is no custom VJP.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.flash_decode.kernel import flash_decode_fwd
from repro.kernels.platform import interpret_default


def flash_decode(
    q: jax.Array,  # [B, 1, Hq, D]
    k: jax.Array,  # [B, cap, Hkv, D]
    v: jax.Array,
    *,
    kv_valid,  # [B] or scalar: live cache rows per batch row
    q_offset=None,  # [B] or scalar absolute position (default kv_valid - 1)
    window: int = 0,
    block_k: int = 128,
    interpret: bool | None = None,
) -> jax.Array:
    """Single-query attention over a padded cache. Row b attends cache
    slots j with j < kv_valid[b] (and j > q_offset[b] - window when
    windowed). Returns [B, 1, Hq, D]."""
    B, Sq, Hq, D = q.shape
    assert Sq == 1, q.shape
    _, cap, Hkv, _ = k.shape
    assert Hq % Hkv == 0, (Hq, Hkv)
    G = Hq // Hkv
    kv_valid = jnp.broadcast_to(jnp.asarray(kv_valid, jnp.int32), (B,))
    if q_offset is None:
        q_offset = kv_valid - 1
    q_offset = jnp.broadcast_to(jnp.asarray(q_offset, jnp.int32), (B,))
    qt = q[:, 0].reshape(B, Hkv, G, D)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)
    out = flash_decode_fwd(
        qt, kt, vt, kv_valid, q_offset, window=window, block_k=block_k,
        interpret=interpret_default() if interpret is None else interpret,
    )
    return out.reshape(B, 1, Hq, D)
