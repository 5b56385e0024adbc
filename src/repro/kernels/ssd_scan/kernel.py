"""Pallas TPU kernels for the Mamba2 SSD chunked scan: one fused forward and
one fused backward.

Both run a grid of (sequence, chunk), the chunk axis sequential
("arbitrary"), and handle every head of a chunk in one step, so C·Bᵀ is
computed once per chunk and shared by the heads (n_groups = 1). The carried
state lives in VMEM scratch as [N, H·P] (state index by head and head
channel): per-head decays then run along lanes, and the state's two
matmuls (C·h into the output, Bᵀ·(x·w) into the state) take every head at
once. Per chunk the forward keeps the [T, T] decay, C·Bᵀ and the state in
VMEM; the backward sweeps the chunks in reverse carrying dh [N, H·P].

Operands enter in the program's layouts: x (and dy, dx) as [B, L, H·P], B
and C as [B, L, N], dt as [B, L, H] float32, A as [1, H] float32. The
within-chunk cumulative sum of dt·A is a matmul with a lower-triangular
ones matrix at float32 precision. The [T, H·P] operands are taken in lane
slabs of `slab_width` lanes (whole heads, 128 lanes where P divides 128),
so every slice is aligned to the (8, 128) tiling; a loop runs over the
slabs and, inside a slab, over its heads. A head's values reach its P
lanes by selects, and sums over its lanes are masked lane reductions.
Matmuls on activations take operands in the activations' dtype with
float32 accumulation; float32 operands multiply at float32 precision.

Math (per head, chunk-local t, s; cs = cumsum(dt·A) in the chunk, cT its
last entry, h the state entering the chunk):
    y[t]  = Σ_{s<=t} exp(cs[t]-cs[s]) (C_t·B_s) dt_s x_s + exp(cs[t]) h C_t
    h_out = exp(cT) h + Σ_s exp(cT-cs[s]) dt_s x_s B_sᵀ
Validated in interpret mode against ref.ssd_reference and jax.vjp of it.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_F32 = jnp.float32
_HIGHEST = jax.lax.Precision.HIGHEST
# scoped VMEM a kernel may ask for: above the compiler's 16 MiB default,
# well inside a v5e core's 128 MiB
_VMEM_CAP = 96 * 2**20
# lane slabs per iteration of the kernels' loop over slabs. Fully unrolled
# (mamba2's 12 slabs) the kernels took ~20 % less time on a TPU v5e, but
# Pallas traces a kernel's body on every call, and the unrolled bodies
# added ~4 s to each process's start-up; 3 an iteration trace a quarter
_UNROLL = 3


def slab_width(H: int, P: int):
    """Lanes of x taken at a time: whole heads, aligned to 128 lanes, or
    None where the heads cannot be cut so."""
    if H * P <= 128:
        return H * P
    if P % 128 == 0:
        return P
    if 128 % P == 0 and (H * P) % 128 == 0:
        return 128
    return None


def _vmem_bytes(T, H, P, N, itemsize):
    """Scoped VMEM of the backward (the larger kernel): double-buffered
    blocks, the carried state, and room for the in-step temporaries."""
    HP = H * P
    blocks = 3 * T * HP * itemsize + 2 * N * HP * 4 + 2 * T * N * 4 \
        + 4 * T * H * 4
    temps = 16 * T * max(T, 128) * 4 + 8 * T * 128 * 4
    return 2 * blocks + N * HP * 4 + 2 * H * T * 4 + temps


def fits(L: int, H: int, P: int, N: int, chunk: int, itemsize: int) -> bool:
    """Whether the kernels' blocks tile these shapes: whole chunks on the
    16-row tiling, heads cut into aligned lane slabs, and the backward's
    blocks inside the VMEM it may ask for."""
    return (chunk % 16 == 0 and L % chunk == 0
            and slab_width(H, P) is not None
            and _vmem_bytes(chunk, H, P, N, itemsize) <= _VMEM_CAP)


def _params(T, H, P, N, itemsize):
    need = _vmem_bytes(T, H, P, N, itemsize)
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "arbitrary"),
        vmem_limit_bytes=int(min(max(need, 32 * 2**20), _VMEM_CAP)))


def _dot(a, b, contract):
    """float32 accumulation; float32 operands at float32 precision (the
    cumulative sums and the head-to-lane moves must be exact)."""
    f32 = a.dtype == _F32 and b.dtype == _F32
    return jax.lax.dot_general(a, b, (contract, ((), ())),
                               precision=_HIGHEST if f32 else None,
                               preferred_element_type=_F32)


def _mm(a, b):
    return _dot(a, b, ((1,), (0,)))


def _mm_nt(a, b):  # a @ bᵀ
    return _dot(a, b, ((1,), (1,)))


def _mm_tn(a, b):  # aᵀ @ b
    return _dot(a, b, ((0,), (0,)))


def _mm_split(a, b, tn=False):
    """a @ b (aᵀ @ b with tn) where b is float32 and a is not: b goes in as
    two terms of a's dtype (16 mantissa bits), for products that feed a
    state carried across chunks, which bf16 rounding would make drift
    chunk after chunk. Two float32 operands multiply at float32
    precision."""
    mm = _mm_tn if tn else _mm
    if a.dtype == b.dtype:
        return mm(a, b)
    hi = b.astype(a.dtype)
    return mm(a, hi) + mm(a, (b - hi.astype(_F32)).astype(a.dtype))


def _iota(shape, dim):
    return jax.lax.broadcasted_iota(jnp.int32, shape, dim)


def _eye(n, dtype):
    return (_iota((n, n), 0) == _iota((n, n), 1)).astype(dtype)


def _slab_loop(n, body, carry):
    """carry = body(k, carry) for the n lane slabs k, up to _UNROLL slabs to
    an iteration (Mosaic unrolls a loop wholly or not at all)."""
    u = max(d for d in range(1, _UNROLL + 1) if n % d == 0)

    def group(i, carry):
        for r in range(u):
            carry = body(i * u + r, carry)
        return carry

    return jax.lax.fori_loop(0, n // u, group, carry)


class _Chunk:
    """What both kernels derive from one chunk's dt and A: the cumulative
    decays cs of every head as columns [T, H] and as rows [H, T] (kept in
    scratch), and the moves of per-head values to and from the lanes of a
    slab of S lanes (g heads of P lanes)."""

    def __init__(self, dt, a, csT_ref, dtT_ref, P, S):
        T, H = dt.shape
        self.T, self.H, self.P, self.S, self.g = T, H, P, S, S // P
        self.tri = _iota((T, T), 0) >= _iota((T, T), 1)  # s <= t
        cs = _mm(self.tri.astype(_F32), dt * a)  # [T, H]
        eye = _eye(H, _F32)
        csT_ref[...] = _mm_nt(eye, cs)  # [H, T], exact transposes
        dtT_ref[...] = _mm_nt(eye, dt)
        self.dt, self.cs, self.cT = dt, cs, cs[T - 1:T, :]
        self.csT_ref, self.dtT_ref = csT_ref, dtT_ref
        self.lane_j = _iota((T, S), 1) // P  # slab lane -> head in slab

    def col(self, v, h):
        """Column h of a per-head [R, H] value, as [R, 1]."""
        lane_h = _iota(v.shape, 1)
        return jnp.sum(jnp.where(lane_h == h, v, 0.0), axis=1, keepdims=True)

    def head(self, h):
        """Head h's cs and dt as columns [T, 1], and its decay matrix M
        [T, T] with M[t,s] = exp(cs[t]-cs[s]) for s <= t, else 0, and dt
        as a row [1, T]."""
        cs = self.col(self.cs, h)
        row = self.csT_ref[pl.ds(h, 1), :]
        M = jnp.exp(jnp.where(self.tri, cs - row, -jnp.inf))
        return cs, self.col(self.dt, h), M, self.dtT_ref[pl.ds(h, 1), :]

    def lanes(self, cols):
        """g columns [R, 1] (the slab's heads) -> [R, S] on their lanes."""
        out = jnp.broadcast_to(cols[0], (cols[0].shape[0], self.S))
        for j in range(1, self.g):
            out = jnp.where(self.lane_j[:out.shape[0]] == j, cols[j], out)
        return out

    def heads(self, v, k):
        """Per-lane [R, S] of slab k -> [R, H]: sums over each head's lanes
        (0 for the heads of other slabs)."""
        R = v.shape[0]
        lane_j = _iota((R, self.S), 1) // self.P
        lane_h = _iota((R, self.H), 1)
        out = jnp.zeros((R, self.H), _F32)
        for j in range(self.g):
            sj = jnp.sum(jnp.where(lane_j == j, v, 0.0), axis=1,
                         keepdims=True)
            out = jnp.where(lane_h == k * self.g + j, sj, out)
        return out


def _fwd_kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, *refs, P, S,
                emit_states):
    if emit_states:
        y_ref, hT_ref, hs_ref, st_ref, csT_ref, dtT_ref = refs
    else:
        y_ref, hT_ref, st_ref, csT_ref, dtT_ref = refs
    ci = pl.program_id(1)

    @pl.when(ci == 0)
    def _init():
        st_ref[...] = jnp.zeros_like(st_ref)

    if emit_states:
        hs_ref[0, 0] = st_ref[...]  # the state entering this chunk

    mdt = x_ref.dtype
    ch = _Chunk(dt_ref[0], a_ref[...], csT_ref, dtT_ref, P, S)
    Bm, Cm = b_ref[0], c_ref[0]  # [T, N]
    CB = _mm_nt(Cm, Bm)  # [T, T]
    g = S // P

    def slab(k, carry):
        lanes = pl.ds(pl.multiple_of(k * S, S), S)
        xs = x_ref[0, :, lanes]
        h_in = st_ref[:, lanes]  # [N, S]
        y = None
        e, w, d = [], [], []
        for j in range(g):
            h = k * g + j
            cs, dt, M, dt_row = ch.head(h)
            W = (M * CB * dt_row).astype(mdt)
            y_j = _mm(W, xs)
            y = y_j if y is None else jnp.where(ch.lane_j == j, y_j, y)
            cT = ch.col(ch.cT, h)  # [1, 1]
            e.append(jnp.exp(cs))  # decay from the chunk's start through t
            w.append(jnp.exp(cT - cs) * dt)  # weight of x_t in the new state
            d.append(jnp.exp(cT))
        y = y + _mm(Cm, h_in.astype(Cm.dtype)) * ch.lanes(e)
        y_ref[0, :, lanes] = y.astype(y_ref.dtype)
        st_ref[:, lanes] = h_in * ch.lanes(d) + _mm_split(
            Bm, xs * ch.lanes(w), tn=True)
        return carry

    _slab_loop(x_ref.shape[2] // S, slab, 0)

    @pl.when(ci == pl.num_programs(1) - 1)
    def _emit():
        hT_ref[0] = st_ref[...]


def _bwd_kernel(x_ref, dy_ref, dt_ref, a_ref, b_ref, c_ref, hs_ref, gT_ref,
                dx_ref, db_ref, dc_ref, ddt_ref, dda_ref,
                ds_ref, csT_ref, dtT_ref, xzT_ref, *, P, S):
    ci = pl.program_id(1)

    @pl.when(ci == 0)  # the last chunk: dh is the final state's cotangent
    def _init():
        ds_ref[...] = gT_ref[0]

    mdt = x_ref.dtype
    ch = _Chunk(dt_ref[0], a_ref[...], csT_ref, dtT_ref, P, S)
    T, H = ch.T, ch.H
    Bm, Cm = b_ref[0], c_ref[0]
    CB = _mm_nt(Cm, Bm)
    CT = _mm_nt(_eye(Cm.shape[1], Cm.dtype), Cm)  # [N, T]
    g = S // P
    lane_h = _iota((T, H), 1)
    dCB = jnp.zeros((T, T), _F32)  # Σ_h ∂/∂(C_t·B_s)
    dB = jnp.zeros(Bm.shape, _F32)
    dC = jnp.zeros(Cm.shape, _F32)
    # per head: dy_t · y_t; its intra-chunk part and x_s · z_s (xzT) are
    # sums over [T, T] in float32: the diagonal s = t enters both, and
    # cancels in the decays' cotangent only if both are summed alike
    dy_y = jnp.zeros((T, H), _F32)
    x_dx = jnp.zeros((T, H), _F32)  # x_s · (dh B_s)
    dh_h = jnp.zeros((1, H), _F32)  # <dh, h_in>

    def slab(k, carry):
        dCB, dB, dC, dy_y, x_dx, dh_h = carry
        lanes = pl.ds(pl.multiple_of(k * S, S), S)
        xs, dys = x_ref[0, :, lanes], dy_ref[0, :, lanes]
        h_in = hs_ref[0, 0, :, lanes]  # [N, S]
        dh = ds_ref[:, lanes]
        z = jnp.zeros((T, S), _F32)  # z_s = Σ_t M[t,s] (C_t·B_s) dy_t
        e, w, dts, d = [], [], [], []
        for j in range(g):
            h = k * g + j
            cs, dt, M, dt_row = ch.head(h)
            V = M * CB
            dy_j = jnp.where(ch.lane_j == j, dys, 0).astype(mdt)
            MG = M * _mm_nt(dy_j, xs)  # M[t,s] (dy_t · x_s)
            dCB = dCB + MG * dt_row
            VG = MG * CB
            xzT_ref[pl.ds(h, 1), :] = jnp.sum(VG, axis=0, keepdims=True)
            dy_y = dy_y + jnp.where(
                lane_h == h, jnp.sum(VG * dt_row, axis=1, keepdims=True), 0.0)
            z = z + _mm_tn(V.astype(mdt), dy_j)
            cT = ch.col(ch.cT, h)
            e.append(jnp.exp(cs))
            w.append(jnp.exp(cT - cs) * dt)
            dts.append(dt)
            d.append(jnp.exp(cT))
        E_cs, E_w = ch.lanes(e), ch.lanes(w)
        y_off = _mm(Cm, h_in.astype(Cm.dtype)) * E_cs
        dy_y = dy_y + ch.heads(dys * y_off, k)
        dxh = _mm(Bm, dh.astype(Bm.dtype))  # (dh B_s) on the lanes
        x_dx = x_dx + ch.heads(xs * dxh, k)
        dh_h = dh_h + ch.heads(jnp.sum(dh * h_in, axis=0, keepdims=True), k)
        dx_ref[0, :, lanes] = (z * ch.lanes(dts)
                               + dxh * E_w).astype(dx_ref.dtype)
        dB = dB + _mm_nt((xs * E_w).astype(Bm.dtype), dh.astype(Bm.dtype))
        dyE = dys * E_cs
        dC = dC + _mm_nt(dyE.astype(Cm.dtype), h_in.astype(Cm.dtype))
        ds_ref[:, lanes] = dh * ch.lanes(d) + _mm_split(CT, dyE)
        return dCB, dB, dC, dy_y, x_dx, dh_h

    dCB, dB, dC, dy_y, x_dx, dh_h = _slab_loop(
        x_ref.shape[2] // S, slab, (dCB, dB, dC, dy_y, x_dx, dh_h))
    dC = dC + _mm(dCB.astype(Bm.dtype), Bm)
    dB = dB + _mm_tn(dCB.astype(Cm.dtype), Cm)
    u = x_dx * jnp.exp(ch.cT - ch.cs)  # ∂/∂dt_s through the state
    ddt = _mm_nt(_eye(T, _F32), xzT_ref[...]) + u
    # ∂/∂cs[t]; the last row also carries cT's share: <dh, h_out>
    dcs = dy_y - ch.dt * ddt
    last = (_iota((T, H), 0) == T - 1).astype(_F32)
    dcs = dcs + last * (jnp.exp(ch.cT) * dh_h + jnp.sum(
        ch.dt * u, axis=0, keepdims=True))
    # cs = cumsum(dt·A): ∂/∂(dt·A)[k] = Σ_{t>=k} ∂/∂cs[t]
    dda_ref[0] = _mm((_iota((T, T), 0) <= _iota((T, T), 1)).astype(_F32), dcs)
    ddt_ref[0] = ddt
    db_ref[0] = dB.astype(db_ref.dtype)
    dc_ref[0] = dC.astype(dc_ref.dtype)


def ssd_fwd(x, dt, A, Bm, Cm, *, chunk, emit_states=False,
            interpret=False):
    """x [B, L, H·P]; dt [B, L, H] f32; A [H]; Bm, Cm [B, L, N].

    Returns y [B, L, H·P] (x's dtype), the final state [B, N, H·P] f32 and,
    with emit_states, the state entering each chunk [B, L/chunk, N, H·P]
    f32 (the backward's residual)."""
    Bsz, L, HP = x.shape
    H = dt.shape[-1]
    P, N, T = HP // H, Bm.shape[-1], chunk
    nc = L // T
    out_specs = [pl.BlockSpec((1, T, HP), lambda b, c: (b, c, 0)),
                 pl.BlockSpec((1, N, HP), lambda b, c: (b, 0, 0))]
    out_shape = [jax.ShapeDtypeStruct((Bsz, L, HP), x.dtype),
                 jax.ShapeDtypeStruct((Bsz, N, HP), _F32)]
    if emit_states:
        out_specs.append(pl.BlockSpec((1, 1, N, HP),
                                      lambda b, c: (b, c, 0, 0)))
        out_shape.append(jax.ShapeDtypeStruct((Bsz, nc, N, HP), _F32))
    return pl.pallas_call(
        functools.partial(_fwd_kernel, P=P, S=slab_width(H, P),
                          emit_states=emit_states),
        grid=(Bsz, nc),
        in_specs=[
            pl.BlockSpec((1, T, HP), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, T, H), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, H), lambda b, c: (0, 0)),
            pl.BlockSpec((1, T, N), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, T, N), lambda b, c: (b, c, 0)),
        ],
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((N, HP), _F32),
                        pltpu.VMEM((H, T), _F32),
                        pltpu.VMEM((H, T), _F32)],
        compiler_params=_params(T, H, P, N, x.dtype.itemsize),
        interpret=interpret,
        name="ssd_fwd",
    )(x, dt, A.reshape(1, H).astype(_F32), Bm, Cm)


def ssd_bwd(x, dt, A, Bm, Cm, states, dy, d_final, *, chunk,
            interpret=False):
    """The reverse sweep. x, dy [B, L, H·P]; dt [B, L, H] f32; Bm, Cm
    [B, L, N]; states [B, L/chunk, N, H·P] (ssd_fwd's); d_final [B, N, H·P]
    f32, the final state's cotangent.

    Returns dx [B, L, H·P], dB, dC [B, L, N] (in their primals' dtypes),
    and, [B, L, H] f32, the cotangent of dt at fixed dt·A and that of
    dt·A: the caller folds the second into dt's and A's."""
    Bsz, L, HP = x.shape
    H = dt.shape[-1]
    P, N, T = HP // H, Bm.shape[-1], chunk
    nc = L // T

    def rev(b, c):  # block of the chunk nc-1-c: the sweep runs backward
        return (b, nc - 1 - c, 0)

    return pl.pallas_call(
        functools.partial(_bwd_kernel, P=P, S=slab_width(H, P)),
        grid=(Bsz, nc),
        in_specs=[
            pl.BlockSpec((1, T, HP), rev),
            pl.BlockSpec((1, T, HP), rev),
            pl.BlockSpec((1, T, H), rev),
            pl.BlockSpec((1, H), lambda b, c: (0, 0)),
            pl.BlockSpec((1, T, N), rev),
            pl.BlockSpec((1, T, N), rev),
            pl.BlockSpec((1, 1, N, HP), lambda b, c: (b, nc - 1 - c, 0, 0)),
            pl.BlockSpec((1, N, HP), lambda b, c: (b, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, T, HP), rev),
            pl.BlockSpec((1, T, N), rev),
            pl.BlockSpec((1, T, N), rev),
            pl.BlockSpec((1, T, H), rev),
            pl.BlockSpec((1, T, H), rev),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((Bsz, L, HP), x.dtype),
            jax.ShapeDtypeStruct((Bsz, L, N), Bm.dtype),
            jax.ShapeDtypeStruct((Bsz, L, N), Cm.dtype),
            jax.ShapeDtypeStruct((Bsz, L, H), _F32),
            jax.ShapeDtypeStruct((Bsz, L, H), _F32),
        ],
        scratch_shapes=[pltpu.VMEM((N, HP), _F32),
                        pltpu.VMEM((H, T), _F32),
                        pltpu.VMEM((H, T), _F32),
                        pltpu.VMEM((H, T), _F32)],
        compiler_params=_params(T, H, P, N, x.dtype.itemsize),
        interpret=interpret,
        name="ssd_bwd",
    )(x, dy, dt, A.reshape(1, H).astype(_F32), Bm, Cm, states, d_final)
