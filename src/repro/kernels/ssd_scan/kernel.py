"""Pallas TPU kernel for the Mamba2 SSD chunked scan.

TPU adaptation of the SSD algorithm: the sequence is chunked (chunk = 128,
MXU-aligned); the grid is (B, H, n_chunks) with the chunk axis *sequential*
("arbitrary"), carrying the [P, N] per-head state in VMEM scratch across
chunks. Each chunk does three small matmuls on the MXU (C·Bᵀ, W·x, state
in/out) — the inter-chunk recurrence is O(1) per chunk.

Layout: every operand is re-laid out in XLA so that the chunk is its own
axis and each block spans the full last two dimensions of its array (the
TPU's (8, 128) tiling rule then holds for any chunk and head size). The
per-head decay A is folded into the chunk-local cumulative sum of dt·A
outside the kernel, which receives it, and dt, both as a column [T, 2] and
as a row [2, T] — the kernel needs each orientation and Mosaic has no
cheap [T] transpose.

Validated in interpret mode against ref.ssd_reference.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _dot(a, b, contract):
    return jax.lax.dot_general(a, b, (contract, ((), ())),
                               preferred_element_type=jnp.float32)


def _ssd_kernel(
    x_ref,  # [1, 1, 1, T, P]
    col_ref,  # [1, 1, 1, T, 2]  (cumsum(dt*A), dt) as columns
    row_ref,  # [1, 1, 1, 2, T]  the same as rows
    b_ref,  # [1, 1, T, N]
    c_ref,  # [1, 1, T, N]
    y_ref,  # [1, 1, 1, T, P]
    st_ref,  # [1, 1, P, N]  final state (written at last chunk)
    state_scr,  # VMEM [P, N] f32
):
    ci = pl.program_id(2)
    nc = pl.num_programs(2)

    @pl.when(ci == 0)
    def _init():
        state_scr[...] = jnp.zeros_like(state_scr)

    x = x_ref[0, 0, 0].astype(jnp.float32)  # [T, P]
    col = col_ref[0, 0, 0]  # [T, 2]
    row = row_ref[0, 0, 0]  # [2, T]
    cs_c, dt_c = col[:, 0:1], col[:, 1:2]  # [T, 1]: cs[t] = sum_{k<=t} dA_k
    cs_r, dt_r = row[0:1, :], row[1:2, :]  # [1, T]
    Bm = b_ref[0, 0].astype(jnp.float32)  # [T, N]
    Cm = c_ref[0, 0].astype(jnp.float32)  # [T, N]
    T = x.shape[0]

    # intra-chunk: W[t,s] = exp(cs[t]-cs[s]) * (C_t·B_s) * dt_s, s<=t
    tri = (jax.lax.broadcasted_iota(jnp.int32, (T, T), 0)
           >= jax.lax.broadcasted_iota(jnp.int32, (T, T), 1))
    L = jnp.where(tri, jnp.exp(cs_c - cs_r), 0.0)
    CB = _dot(Cm, Bm, ((1,), (1,)))  # [T, T]
    W = CB * L * dt_r
    y_diag = _dot(W, x, ((1,), (0,)))  # [T, P]

    # inter-chunk input: y_off[t] = exp(cs[t]) * C_t · h_in
    h_in = state_scr[...]  # [P, N]
    Ch = _dot(Cm, h_in, ((1,), (1,)))  # [T, P]
    y_ref[0, 0, 0] = (y_diag + jnp.exp(cs_c) * Ch).astype(y_ref.dtype)

    # state update: h_out = exp(sum dA) * h_in + xᵀ · (B * decay_to_end * dt)
    cs_last = cs_r[0, T - 1]  # scalar: Mosaic cannot broadcast a [1, 1]
    w_state = jnp.exp(cs_last - cs_c) * dt_c  # [T, 1]
    upd = _dot(x, Bm * w_state, ((0,), (0,)))  # [P, N]
    state_scr[...] = h_in * jnp.exp(cs_last) + upd

    @pl.when(ci == nc - 1)
    def _emit():
        st_ref[0, 0] = state_scr[...]


def ssd_scan_fwd(
    x: jax.Array,  # [B, L, H, P]
    dt: jax.Array,  # [B, L, H]
    A: jax.Array,  # [H]
    Bm: jax.Array,  # [B, L, N]
    Cm: jax.Array,  # [B, L, N]
    *,
    chunk: int = 128,
    interpret: bool = False,
):
    B, L, H, P = x.shape
    N = Bm.shape[-1]
    assert L % chunk == 0, (L, chunk)
    nc = L // chunk

    xc = x.reshape(B, nc, chunk, H, P).transpose(0, 3, 1, 2, 4)
    dA = (dt.astype(jnp.float32) * A.astype(jnp.float32)).reshape(
        B, nc, chunk, H).transpose(0, 3, 1, 2)  # [B, H, nc, T]
    dtc = dt.astype(jnp.float32).reshape(B, nc, chunk, H).transpose(
        0, 3, 1, 2)
    cs = jnp.cumsum(dA, axis=-1)
    col = jnp.stack([cs, dtc], axis=-1)  # [B, H, nc, T, 2]
    row = jnp.stack([cs, dtc], axis=-2)  # [B, H, nc, 2, T]
    Bc = Bm.reshape(B, nc, chunk, N)
    Cc = Cm.reshape(B, nc, chunk, N)

    y, st = pl.pallas_call(
        _ssd_kernel,
        grid=(B, H, nc),
        in_specs=[
            pl.BlockSpec((1, 1, 1, chunk, P), lambda b, h, c: (b, h, c, 0, 0)),
            pl.BlockSpec((1, 1, 1, chunk, 2), lambda b, h, c: (b, h, c, 0, 0)),
            pl.BlockSpec((1, 1, 1, 2, chunk), lambda b, h, c: (b, h, c, 0, 0)),
            pl.BlockSpec((1, 1, chunk, N), lambda b, h, c: (b, c, 0, 0)),
            pl.BlockSpec((1, 1, chunk, N), lambda b, h, c: (b, c, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, 1, chunk, P), lambda b, h, c: (b, h, c, 0, 0)),
            pl.BlockSpec((1, 1, P, N), lambda b, h, c: (b, h, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, nc, chunk, P), x.dtype),
            jax.ShapeDtypeStruct((B, H, P, N), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((P, N), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(xc, col, row, Bc, Cc)
    return y.transpose(0, 2, 3, 1, 4).reshape(B, L, H, P), st
