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

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.flash_attention.kernel import flash_attention_fwd
from repro.kernels.flash_decode.kernel import flash_decode_fwd
from repro.kernels.ssd_scan.kernel import ssd_scan_fwd


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


def test_ssd_scan_compiles_at_mamba2_130m_widths(one_chip):
    """mamba2-130m: 24 SSD heads of width 64, state 128, chunk 128."""
    B, L, H, P, N = 4, 512, 24, 64, 128
    bf16, f32 = jnp.bfloat16, jnp.float32
    _compile(lambda x, dt, a, b, c: ssd_scan_fwd(x, dt, a, b, c, chunk=128),
             one_chip, ((B, L, H, P), bf16), ((B, L, H), f32), ((H,), f32),
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
