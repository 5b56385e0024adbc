"""jit'd public wrapper for the SSD scan kernel (custom VJP recomputes the
backward through the reference — forward is the decode/prefill hot path)."""
from __future__ import annotations

import functools

import jax

from repro.kernels.ssd_scan.kernel import ssd_scan_fwd
from repro.kernels.ssd_scan.ref import ssd_reference
from repro.kernels.platform import interpret_default


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def ssd_scan(x, dt, A, Bm, Cm, chunk=128, initial_state=None):
    if initial_state is not None:
        # kernel assumes zero initial state; fold a nonzero one via the ref
        return ssd_reference(x, dt, A, Bm, Cm, chunk=chunk, initial_state=initial_state)
    return ssd_scan_fwd(x, dt, A, Bm, Cm, chunk=chunk, interpret=interpret_default())


def _fwd(x, dt, A, Bm, Cm, chunk, initial_state):
    out = ssd_scan(x, dt, A, Bm, Cm, chunk, initial_state)
    return out, (x, dt, A, Bm, Cm)


def _bwd(chunk, initial_state, res, g):
    x, dt, A, Bm, Cm = res
    _, vjp = jax.vjp(
        lambda x, dt, A, Bm, Cm: ssd_reference(
            x, dt, A, Bm, Cm, chunk=chunk, initial_state=initial_state
        ),
        x, dt, A, Bm, Cm,
    )
    return vjp(g)


ssd_scan.defvjp(_fwd, _bwd)
