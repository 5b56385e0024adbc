"""Pallas TPU flash attention (blockwise fused attention, online softmax).

TPU-native adaptation (DESIGN.md §4): q/k blocks are MXU-aligned (multiples
of 128 on the sequence dims, head_dim padded to 128), the k-loop is the
innermost *sequential* grid dimension carrying (m, l, acc) in VMEM scratch,
and fully-masked blocks are skipped via @pl.when on block coordinates.
Supports causal and sliding-window masks and GQA via the k/v index_map.

Validated on CPU in interpret mode against ref.mha_reference; TPU v5e is the
deployment target.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _flash_kernel(
    q_ref, k_ref, v_ref,  # [1,1,Bq,D], [1,1,Bk,D], [1,1,Bk,D]
    o_ref,  # [1,1,Bq,D]
    m_scr, l_scr, acc_scr,  # VMEM scratch: [Bq,1], [Bq,1], [Bq,D]
    *,
    scale: float,
    block_q: int,
    block_k: int,
    seq_q: int,
    seq_k: int,
    causal: bool,
    window: int,
):
    iq = pl.program_id(2)
    ik = pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(ik == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q_start = iq * block_q
    k_start = ik * block_k
    # block visibility: skip blocks fully above the causal diagonal or fully
    # left of the sliding window.
    visible = jnp.bool_(True)
    if causal:
        visible = jnp.logical_and(visible, k_start <= q_start + block_q - 1)
    if window:
        visible = jnp.logical_and(visible, k_start + block_k - 1 > q_start - window)

    @pl.when(visible)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)  # [Bq, D]
        k = k_ref[0, 0].astype(jnp.float32)  # [Bk, D]
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale  # [Bq, Bk]

        qpos = q_start + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
        kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
        mask = kpos < seq_k  # padding
        if causal:
            mask = jnp.logical_and(mask, kpos <= qpos)
        if window:
            mask = jnp.logical_and(mask, qpos - kpos < window)
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_scr[...]  # [Bq,1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)  # [Bq,1]
        l_scr[...] = l_scr[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        m_scr[...] = m_new

    @pl.when(ik == nk - 1)
    def _finalize():
        denom = l_scr[...]
        # fully-masked rows -> zero output
        denom = jnp.where(denom == 0.0, 1.0, denom)
        o_ref[0, 0, :, :] = (acc_scr[...] / denom).astype(o_ref.dtype)


def flash_attention_fwd(
    q: jax.Array,  # [B, Hq, Sq, D]
    k: jax.Array,  # [B, Hkv, Sk, D]
    v: jax.Array,
    *,
    causal: bool = True,
    window: int = 0,
    block_q: int = 128,
    block_k: int = 128,
    interpret: bool = False,
) -> jax.Array:
    B, Hq, Sq, D = q.shape
    _, Hkv, Sk, _ = k.shape
    assert Hq % Hkv == 0
    group = Hq // Hkv
    scale = 1.0 / math.sqrt(D)

    block_q = min(block_q, Sq)
    block_k = min(block_k, Sk)
    pad_q = (-Sq) % block_q
    pad_k = (-Sk) % block_k
    if pad_q:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, pad_q), (0, 0)))
    if pad_k:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pad_k), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pad_k), (0, 0)))
    Sq_p, Sk_p = Sq + pad_q, Sk + pad_k
    nq, nk = Sq_p // block_q, Sk_p // block_k

    kernel = functools.partial(
        _flash_kernel,
        scale=scale,
        block_q=block_q,
        block_k=block_k,
        seq_q=Sq,
        seq_k=Sk,
        causal=causal,
        window=window,
    )
    out = pl.pallas_call(
        kernel,
        grid=(B, Hq, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, D), lambda b, h, iq, ik: (b, h, iq, 0)),
            pl.BlockSpec((1, 1, block_k, D), lambda b, h, iq, ik: (b, h // group, ik, 0)),
            pl.BlockSpec((1, 1, block_k, D), lambda b, h, iq, ik: (b, h // group, ik, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, D), lambda b, h, iq, ik: (b, h, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((B, Hq, Sq_p, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, D), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(q, k, v)
    return out[:, :, :Sq, :]
