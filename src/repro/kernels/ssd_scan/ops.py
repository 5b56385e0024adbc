"""Public entry of the fused SSD kernels: `ssd_scan` (a custom VJP whose
forward and backward are kernel.ssd_fwd / kernel.ssd_bwd) and `use_kernel`,
the rule by which models/ssm.py picks it over ref.ssd_reference."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.platform import interpret_default
from repro.kernels.ssd_scan.kernel import fits, ssd_bwd, ssd_fwd


def use_kernel(backend: str, x_shape, n_state: int, chunk: int, itemsize: int,
               *, has_state: bool, sharded: bool) -> bool:
    """The fused kernels run on the TPU, from a zero state, where their
    blocks tile the shapes (x_shape [B, L, H, P]), and where no client-axis
    sharding is in effect (GSPMD does not partition a Pallas call).
    Everything else takes ssd_reference."""
    _, L, H, P = x_shape
    return (backend == "tpu" and not has_state and not sharded
            and fits(L, H, P, n_state, chunk, itemsize))


def _split(x):
    Bsz, L, H, P = x.shape
    return x.reshape(Bsz, L, H * P)


def _state(st, H):  # [B, N, H·P] -> [B, H, P, N]
    Bsz, N, HP = st.shape
    return st.reshape(Bsz, N, H, HP // H).transpose(0, 2, 3, 1)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def ssd_scan(x, dt, A, Bm, Cm, chunk=128):
    """x [B, L, H, P]; dt [B, L, H]; A [H]; Bm, Cm [B, L, N]; from a zero
    state. Returns (y [B, L, H, P], final state [B, H, P, N] f32), as
    ref.ssd_reference does."""
    y, st = ssd_fwd(_split(x), dt.astype(jnp.float32), A, Bm, Cm,
                    chunk=chunk, interpret=interpret_default())
    return y.reshape(x.shape), _state(st, x.shape[2])


def _fwd(x, dt, A, Bm, Cm, chunk):
    y, st, states = ssd_fwd(_split(x), dt.astype(jnp.float32), A, Bm, Cm,
                            chunk=chunk, emit_states=True,
                            interpret=interpret_default())
    out = (y.reshape(x.shape), _state(st, x.shape[2]))
    return out, (x, dt, A, Bm, Cm, states)


def _bwd(chunk, res, g):
    x, dt, A, Bm, Cm, states = res
    gy, gst = g
    Bsz, L, H, P = x.shape
    d_final = gst.astype(jnp.float32).transpose(0, 3, 1, 2).reshape(
        Bsz, -1, H * P)
    dt32 = dt.astype(jnp.float32)
    dx, dB, dC, ddt, dda = ssd_bwd(
        _split(x), dt32, A, Bm, Cm, states, _split(gy.astype(x.dtype)),
        d_final, chunk=chunk, interpret=interpret_default())
    # fold the cotangent of dt·A into dt's and A's
    ddt = ddt + dda * A.astype(jnp.float32)
    dA = jnp.sum(dda * dt32, axis=(0, 1))
    return (dx.reshape(x.shape), ddt.astype(dt.dtype), dA.astype(A.dtype),
            dB, dC)


ssd_scan.defvjp(_fwd, _bwd)
