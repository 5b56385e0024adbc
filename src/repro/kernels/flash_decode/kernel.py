"""Pallas TPU flash-decode: single-query attention over a padded KV cache.

The serving hot path (continuous batching) decodes ONE token per slot
against a fixed-capacity `[cap, Hkv, D]` cache whose first `kv_valid[b]`
rows are live — every slot sits at its own depth, so the mask is per-row
data, not per-shape structure. The kernel is a split-KV online-softmax
reduction: the KV axis is the innermost *sequential* grid dimension, each
split carries (m, l, acc) partials in VMEM scratch, and splits entirely
past `kv_valid` (or entirely left of the sliding window) are skipped via
@pl.when on the per-row scalars, which arrive through scalar prefetch
(SMEM) rather than as (1, 1) VMEM blocks the TPU's tiling rule refuses.

One numerical trap specific to decode: a split can be FULLY masked (e.g.
the first split of a windowed row whose window starts in a later split).
There `m` stays NEG_INF and `s - m == NEG_INF - NEG_INF == 0`, so a bare
exp() would contribute 2**0 == 1 per masked entry — the probability mass
of garbage. The guard `p = where(mask, exp(s - m), 0)` keeps masked
entries at exactly zero.

Validated on CPU in interpret mode against ref.mha_reference(q_offset=,
kv_valid=); TPU v5e is the deployment target.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _decode_kernel(
    kv_valid_ref, q_off_ref,  # [B] int32 per-row scalars (SMEM)
    q_ref, k_ref, v_ref,  # [1,1,G,D], [1,1,Bk,D], [1,1,Bk,D]
    o_ref,  # [1,1,G,D]
    m_scr, l_scr, acc_scr,  # VMEM scratch: [G,1], [G,1], [G,D]
    *,
    scale: float,
    block_k: int,
    window: int,
):
    b = pl.program_id(0)
    ik = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ik == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    kv_valid = kv_valid_ref[b]
    q_off = q_off_ref[b]
    k_start = ik * block_k
    # split visibility: skip splits entirely past the live cache region or
    # entirely left of the sliding window
    visible = k_start < kv_valid
    if window:
        visible = jnp.logical_and(visible, k_start + block_k > q_off - window + 1)

    @pl.when(visible)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)  # [G, D] — all query heads of this kv head
        k = k_ref[0, 0].astype(jnp.float32)  # [Bk, D]
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale  # [G, Bk]

        kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        mask = kpos < kv_valid
        if window:
            mask = jnp.logical_and(mask, kpos > q_off - window)
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_scr[...]  # [G,1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        # fully-masked split: m_new stays NEG_INF and s - m_new == 0 for
        # masked entries — exp would give 1, so pin them to exactly 0
        p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
        alpha = jnp.exp(m_prev - m_new)
        l_scr[...] = l_scr[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        m_scr[...] = m_new

    @pl.when(ik == nk - 1)
    def _finalize():
        denom = l_scr[...]
        # every split masked (kv_valid == 0 row) -> zero output
        denom = jnp.where(denom == 0.0, 1.0, denom)
        o_ref[0, 0, :, :] = (acc_scr[...] / denom).astype(o_ref.dtype)


def flash_decode_fwd(
    q: jax.Array,  # [B, Hkv, G, D] — query heads grouped under their kv head
    k: jax.Array,  # [B, Hkv, cap, D]
    v: jax.Array,
    kv_valid: jax.Array,  # [B] int32 live cache rows per batch row
    q_offset: jax.Array,  # [B] int32 absolute query position per row
    *,
    window: int = 0,
    block_k: int = 128,
    interpret: bool = False,
) -> jax.Array:
    B, Hkv, G, D = q.shape
    cap = k.shape[2]
    scale = 1.0 / math.sqrt(D)

    block_k = min(block_k, cap)
    pad_k = (-cap) % block_k
    if pad_k:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pad_k), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pad_k), (0, 0)))
    nk = (cap + pad_k) // block_k

    kernel = functools.partial(
        _decode_kernel, scale=scale, block_k=block_k, window=window)
    # index maps take the two scalar-prefetch refs after the grid indices
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, Hkv, nk),
        in_specs=[
            pl.BlockSpec((1, 1, G, D), lambda b, h, ik, *_: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, block_k, D),
                         lambda b, h, ik, *_: (b, h, ik, 0)),
            pl.BlockSpec((1, 1, block_k, D),
                         lambda b, h, ik, *_: (b, h, ik, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, G, D),
                               lambda b, h, ik, *_: (b, h, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((G, 1), jnp.float32),
            pltpu.VMEM((G, 1), jnp.float32),
            pltpu.VMEM((G, D), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hkv, G, D), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(kv_valid, q_offset, q, k, v)
