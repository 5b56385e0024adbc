"""The main-path Pallas kernels compile for a TPU v5e chip at real widths.

Interpret mode (tests/test_kernels.py) checks the math but not what the
TPU's compiler accepts: block shapes off the (8, 128) tiling, scalars in
VMEM, too much fast memory. These tests compile each kernel for a
described (not attached) v5e chip, which needs no accelerator. The
topology is described inside a module fixture, never at import time: only
one process at a time may load the TPU library, and every test worker
imports this file.
"""
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.flash_attention.kernel import flash_attention_fwd
from repro.kernels.flash_decode.kernel import flash_decode_fwd


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache off meanwhile
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compile(fn, sharding, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


def test_ssd_scan_compiles_at_mamba2_130m_widths(one_chip, monkeypatch):
    """mamba2-130m: 24 SSD heads of width 64, state 128, chunk 128. The
    gradient of a loss through ops.ssd_scan compiles to the fused forward
    and the fused backward kernel, each named under the caller's
    `mamba.ssd` scope (which the benchmark's SSD metrics read)."""
    from repro.kernels.ssd_scan import ops

    # the described chip is not the backend JAX sees: compile, not interpret
    monkeypatch.setattr(ops, "interpret_default", lambda: False)
    B, L, H, P, N = 4, 512, 24, 64, 128
    bf16, f32 = jnp.bfloat16, jnp.float32

    def loss(x, dt, a, b, c):
        with jax.named_scope("mamba.ssd"):
            y, st = ops.ssd_scan(x, dt, a, b, c, 128)
        return jnp.sum(y.astype(f32)) + jnp.sum(st)

    compiled = _compile(jax.grad(loss, argnums=(0, 1, 2, 3, 4)), one_chip,
                        ((B, L, H, P), bf16), ((B, L, H), f32), ((H,), f32),
                        ((B, L, N), bf16), ((B, L, N), bf16))
    calls = {}
    for line in compiled.as_text().splitlines():
        if 'custom_call_target="tpu_custom_call"' in line:
            name = re.match(r"\s*(?:ROOT )?%([\w-]+)", line).group(1)
            calls[name] = re.search(r'op_name="([^"]*)"', line).group(1)
    assert sorted(calls) == ["ssd_bwd", "ssd_fwd"], calls
    assert all("mamba.ssd" in op_name for op_name in calls.values()), calls


def test_ssd_scan_compiles_at_zamba2_widths_chunk_256(one_chip, monkeypatch):
    """zamba2-7b's Mamba2 blocks (112 heads of 64, state 64) in 256-token
    chunks, Mamba2's default chunk: the fused kernels' blocks still fit."""
    from repro.kernels.ssd_scan import ops

    monkeypatch.setattr(ops, "interpret_default", lambda: False)
    B, L, H, P, N = 2, 1024, 112, 64, 64
    bf16, f32 = jnp.bfloat16, jnp.float32

    def loss(x, dt, a, b, c):
        y, st = ops.ssd_scan(x, dt, a, b, c, 256)
        return jnp.sum(y.astype(f32)) + jnp.sum(st)

    _compile(jax.grad(loss, argnums=(0, 1, 2, 3, 4)), one_chip,
             ((B, L, H, P), bf16), ((B, L, H), f32), ((H,), f32),
             ((B, L, N), bf16), ((B, L, N), bf16))


def test_flash_decode_compiles_gqa_4k_cache(one_chip):
    """GQA decode: 8 kv heads of 4 query heads each, D=128, cap 4096."""
    B, Hkv, G, D, cap = 8, 8, 4, 128, 4096
    bf16, i32 = jnp.bfloat16, jnp.int32
    _compile(lambda q, k, v, n, o: flash_decode_fwd(q, k, v, n, o),
             one_chip, ((B, Hkv, G, D), bf16), ((B, Hkv, cap, D), bf16),
             ((B, Hkv, cap, D), bf16), ((B,), i32), ((B,), i32))


def test_flash_attention_compiles_4k_gqa(one_chip):
    """Causal prefill at 4k tokens, D=128, GQA 32 query / 8 kv heads."""
    S, D = 4096, 128
    bf16 = jnp.bfloat16
    _compile(lambda q, k, v: flash_attention_fwd(q, k, v, causal=True),
             one_chip, ((1, 32, S, D), bf16), ((1, 8, S, D), bf16),
             ((1, 8, S, D), bf16))
