"""jit'd public wrapper for the flash-attention kernel.

Model code calls flash_attention(q, k, v) with [B, S, H, D] layout; this
transposes to the kernel's [B, H, S, D], picks interpret mode on CPU
(the container validates kernels in interpret mode; TPU is the target),
and defines a custom VJP that recomputes attention with the reference
(flash backward on TPU is a follow-up; the forward is the serving hot path).
"""
from __future__ import annotations

import functools

import jax

from repro.kernels.flash_attention.kernel import flash_attention_fwd
from repro.kernels.flash_attention.ref import mha_reference
from repro.kernels.platform import interpret_default


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def flash_attention(q, k, v, causal=True, window=0, block_q=128, block_k=128):
    """q: [B, Sq, Hq, D]; k/v: [B, Sk, Hkv, D] -> [B, Sq, Hq, D]."""
    qt = q.transpose(0, 2, 1, 3)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)
    out = flash_attention_fwd(
        qt, kt, vt, causal=causal, window=window, block_q=block_q,
        block_k=block_k, interpret=interpret_default(),
    )
    return out.transpose(0, 2, 1, 3)


def _fwd(q, k, v, causal, window, block_q, block_k):
    out = flash_attention(q, k, v, causal, window, block_q, block_k)
    return out, (q, k, v)


def _bwd(causal, window, block_q, block_k, res, g):
    q, k, v = res
    # recompute-based backward through the reference (exact same math)
    _, vjp = jax.vjp(lambda q, k, v: mha_reference(q, k, v, causal=causal, window=window), q, k, v)
    return vjp(g)


flash_attention.defvjp(_fwd, _bwd)
