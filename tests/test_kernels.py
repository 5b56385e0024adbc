"""Per-kernel correctness: sweep shapes/dtypes, assert_allclose vs the
pure-jnp oracle (interpret mode on CPU; TPU is the deployment target)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:  # optional test dep: pip install -e .[test]; only gates the
    # hypothesis sweep below — the shape-parametrized pins always run
    from hypothesis import given, settings, strategies as st
except ImportError:  # pragma: no cover
    given = None

from repro.kernels.flash_attention.ops import flash_attention
from repro.kernels.flash_attention.ref import mha_chunked, mha_reference
from repro.kernels.flash_decode.ops import flash_decode
from repro.kernels.mtsl_update.ops import mtsl_update
from repro.kernels.mtsl_update.ref import mtsl_update_reference
from repro.kernels.ssd_scan.ops import ssd_scan
from repro.kernels.ssd_scan.ref import ssd_reference, ssd_decode_step


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------

FLASH_CASES = [
    # (B, Sq, Sk, Hq, Hkv, D, causal, window, dtype)
    (2, 64, 64, 4, 2, 32, True, 0, jnp.float32),
    (1, 128, 128, 2, 2, 64, True, 16, jnp.float32),
    (1, 96, 96, 4, 1, 16, True, 0, jnp.float32),  # non-pow2 seq
    (2, 32, 32, 8, 4, 32, False, 0, jnp.float32),
    (1, 64, 64, 4, 4, 128, True, 0, jnp.bfloat16),
    (1, 80, 80, 2, 1, 64, True, 24, jnp.float32),  # window > block residue
]


@pytest.mark.parametrize("case", FLASH_CASES)
def test_flash_attention_matches_reference(case):
    B, Sq, Sk, Hq, Hkv, D, causal, window, dtype = case
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.normal(size=(B, Sq, Hq, D)), dtype)
    k = jnp.asarray(rng.normal(size=(B, Sk, Hkv, D)), dtype)
    v = jnp.asarray(rng.normal(size=(B, Sk, Hkv, D)), dtype)
    out = flash_attention(q, k, v, causal, window, 32, 32)
    ref = mha_reference(q, k, v, causal=causal, window=window)
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32), atol=tol, rtol=tol
    )


def test_flash_attention_grad_matches_reference():
    rng = np.random.default_rng(1)
    q = jnp.asarray(rng.normal(size=(1, 32, 2, 16)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(1, 32, 1, 16)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(1, 32, 1, 16)), jnp.float32)

    def f_kernel(q, k, v):
        return flash_attention(q, k, v, True, 0, 16, 16).sum()

    def f_ref(q, k, v):
        return mha_reference(q, k, v, causal=True).sum()

    gk = jax.grad(f_kernel, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(f_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gk, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4)


@pytest.mark.parametrize("case", [
    (2, 64, 64, 4, 2, 32, True, 0, 16),
    (1, 96, 96, 4, 1, 16, True, 24, 32),
    (2, 32, 32, 8, 4, 32, False, 0, 8),
])
def test_chunked_attention_matches_reference(case):
    """The beyond-paper pure-JAX online-softmax path (cfg.attn_impl=chunked)
    is numerically identical to the reference, forward and backward."""
    B, Sq, Sk, Hq, Hkv, D, causal, window, chunk = case
    rng = np.random.default_rng(5)
    q = jnp.asarray(rng.normal(size=(B, Sq, Hq, D)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(B, Sk, Hkv, D)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(B, Sk, Hkv, D)), jnp.float32)
    out = mha_chunked(q, k, v, causal=causal, window=window, chunk=chunk)
    ref = mha_reference(q, k, v, causal=causal, window=window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)
    g1 = jax.grad(lambda a, b, c: mha_chunked(
        a, b, c, causal=causal, window=window, chunk=chunk).sum(),
        argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(lambda a, b, c: mha_reference(
        a, b, c, causal=causal, window=window).sum(), argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4)


def test_moe_grouped_dispatch_matches_global():
    """cfg.moe_groups splits dispatch into shard-local groups; with ample
    capacity the result is bit-identical to global dispatch."""
    from repro.configs.base import ModelConfig
    from repro.models.moe import moe_forward, moe_params
    from repro.utils.sharding import strip

    cfg = ModelConfig(name="t", family="moe", d_model=32, num_experts=4,
                      experts_per_token=2, num_shared_experts=1, moe_d_ff=16,
                      capacity_factor=8.0, dtype="float32")
    p = strip(moe_params(jax.random.PRNGKey(0), cfg))
    x = jax.random.normal(jax.random.PRNGKey(1), (4, 8, 32))
    y1, _ = moe_forward(p, x, cfg)
    y2, _ = moe_forward(p, x, cfg.with_updates(moe_groups=4))
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2), atol=1e-6)


# ---------------------------------------------------------------------------
# flash decode (single-query attention over a padded slot cache)
# ---------------------------------------------------------------------------

DECODE_CASES = [
    # (B, cap, Hq, Hkv, D, window, block_k, dtype)
    (4, 64, 4, 2, 32, 0, 16, jnp.float32),       # GQA, multi-split KV
    (3, 96, 8, 1, 16, 0, 32, jnp.float32),       # MQA, non-pow2 cap
    (2, 128, 4, 4, 64, 0, 128, jnp.float32),     # MHA, single split
    (4, 64, 6, 3, 32, 16, 16, jnp.float32),      # sliding window
    (2, 64, 4, 2, 64, 0, 32, jnp.bfloat16),
]


def _decode_inputs(case, seed=11):
    B, cap, Hq, Hkv, D, window, block_k, dtype = case
    rng = np.random.default_rng(seed)
    q = jnp.asarray(rng.normal(size=(B, 1, Hq, D)), dtype)
    k = jnp.asarray(rng.normal(size=(B, cap, Hkv, D)), dtype)
    v = jnp.asarray(rng.normal(size=(B, cap, Hkv, D)), dtype)
    # ragged per-row fill: includes 1 (just admitted) and cap (full)
    kv_valid = jnp.asarray(
        rng.integers(1, cap + 1, size=(B,)).tolist()[:-1] + [cap], jnp.int32)
    return q, k, v, kv_valid


@pytest.mark.parametrize("case", DECODE_CASES)
def test_flash_decode_matches_reference(case):
    """The continuous-batching decode path: each slot attends its own
    partially filled cache prefix (ragged kv_valid), GQA head grouping."""
    B, cap, Hq, Hkv, D, window, block_k, dtype = case
    q, k, v, kv_valid = _decode_inputs(case)
    out = flash_decode(q, k, v, kv_valid=kv_valid, window=window,
                       block_k=block_k, interpret=True)
    ref = mha_reference(q, k, v, causal=True, window=window,
                        q_offset=kv_valid - 1, kv_valid=kv_valid)
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               atol=tol, rtol=tol)


def test_flash_decode_ring_cache_full():
    """Ring layout (cap == window): kv_valid saturates at cap, the default
    q_offset = kv_valid - 1 keeps every live slot inside the window."""
    case = (3, 32, 4, 2, 32, 0, 16, jnp.float32)
    q, k, v, _ = _decode_inputs(case)
    kv_valid = jnp.asarray([32, 32, 7], jnp.int32)
    out = flash_decode(q, k, v, kv_valid=kv_valid, interpret=True)
    ref = mha_reference(q, k, v, causal=True, q_offset=kv_valid - 1,
                        kv_valid=kv_valid)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


def test_flash_decode_q_offset_window():
    """Non-ring sliding window: absolute q_offset decouples from kv_valid,
    so the window [pos-w, pos] slides over the padded cache."""
    B, cap, w = 4, 64, 12
    rng = np.random.default_rng(3)
    q = jnp.asarray(rng.normal(size=(B, 1, 4, 32)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(B, cap, 2, 32)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(B, cap, 2, 32)), jnp.float32)
    pos = jnp.asarray([0, 5, 30, 63], jnp.int32)
    out = flash_decode(q, k, v, kv_valid=pos + 1, q_offset=pos, window=w,
                       block_k=16, interpret=True)
    ref = mha_reference(q, k, v, causal=True, window=w, q_offset=pos,
                        kv_valid=pos + 1)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


def test_mha_reference_partial_cache_matches_dense_prefix():
    """Oracle self-consistency for the chunked-extend path: attention over
    a zero-padded cache with (q_offset, kv_valid) row masks must equal
    dense causal attention on each row's real prefix. This is the exact-FP
    argument for continuous-vs-sequential greedy parity."""
    rng = np.random.default_rng(9)
    cap, C, Hq, Hkv, D = 32, 8, 4, 2, 16
    starts = [0, 5, 24]  # chunk start offsets, incl. extend-from-empty
    B = len(starts)
    q = jnp.asarray(rng.normal(size=(B, C, Hq, D)), jnp.float32)
    kv_dense = rng.normal(size=(B, cap, Hkv, D))
    k_pad = np.zeros((B, cap, Hkv, D), np.float32)
    v_pad = np.zeros((B, cap, Hkv, D), np.float32)
    for b, s in enumerate(starts):
        k_pad[b, : s + C] = kv_dense[b, : s + C]
        v_pad[b, : s + C] = kv_dense[b, : s + C] * 0.5
    start = jnp.asarray(starts, jnp.int32)
    out = mha_reference(q, jnp.asarray(k_pad), jnp.asarray(v_pad),
                        causal=True, q_offset=start, kv_valid=start + C)
    for b, s in enumerate(starts):
        ref_b = mha_reference(
            jnp.asarray(np.concatenate(
                [np.zeros((1, s, Hq, D), np.float32),
                 np.asarray(q[b][None])], axis=1)),
            jnp.asarray(kv_dense[None, b, : s + C], jnp.float32),
            jnp.asarray(kv_dense[None, b, : s + C] * 0.5, jnp.float32),
            causal=True)[0, s:]
        np.testing.assert_allclose(np.asarray(out[b]), np.asarray(ref_b),
                                   atol=2e-5)


# ---------------------------------------------------------------------------
# SSD scan
# ---------------------------------------------------------------------------

SSD_CASES = [
    # (B, L, H, P, N, chunk, dtype)
    (2, 64, 3, 8, 16, 16, jnp.float32),
    (1, 128, 2, 16, 8, 32, jnp.float32),
    (2, 32, 1, 4, 4, 32, jnp.float32),
    (1, 64, 4, 32, 64, 16, jnp.float32),
    (1, 64, 2, 8, 8, 16, jnp.bfloat16),
]


@pytest.mark.parametrize("case", SSD_CASES)
def test_ssd_scan_matches_reference(case):
    B, L, H, P, N, chunk, dtype = case
    rng = np.random.default_rng(2)
    x = jnp.asarray(rng.normal(size=(B, L, H, P)), dtype)
    dt = jnp.asarray(rng.uniform(0.01, 0.2, size=(B, L, H)), jnp.float32)
    A = -jnp.asarray(rng.uniform(0.5, 2.0, size=(H,)), jnp.float32)
    Bm = jnp.asarray(rng.normal(size=(B, L, N)), dtype)
    Cm = jnp.asarray(rng.normal(size=(B, L, N)), dtype)
    y, st = ssd_scan(x, dt, A, Bm, Cm, chunk)
    yr, sr = ssd_reference(x, dt, A, Bm, Cm, chunk=chunk)
    tol = 2e-5 if dtype == jnp.float32 else 5e-2
    np.testing.assert_allclose(np.asarray(y, np.float32), np.asarray(yr, np.float32),
                               atol=tol, rtol=tol)
    np.testing.assert_allclose(np.asarray(st), np.asarray(sr), atol=1e-4)


SSD_VJP_CASES = [
    # (B, L, H, P, N, chunk, dtype, vmapped): one chunk and several, one
    # lane slab and three (H·P = 384, one iteration of three slabs), N != P
    (2, 64, 3, 8, 16, 16, jnp.float32, False),
    (1, 32, 2, 16, 8, 32, jnp.float32, False),
    (1, 256, 2, 64, 32, 128, jnp.float32, False),
    (1, 64, 6, 64, 32, 32, jnp.float32, False),
    (1, 128, 4, 32, 16, 128, jnp.bfloat16, False),
    (2, 64, 2, 8, 8, 16, jnp.bfloat16, True),
]


@pytest.mark.parametrize("case", SSD_VJP_CASES)
def test_ssd_scan_vjp_matches_reference(case):
    """The fused forward's outputs and the fused backward's cotangents of
    x, dt, A, B and C against jax.vjp of the chunked reference, with a
    nonzero cotangent on the final state too; `vmapped` maps a client axis
    over the call, as the round's towers do."""
    B, L, H, P, N, chunk, dtype, vmapped = case
    rng = np.random.default_rng(4)
    lead = (2, B) if vmapped else (B,)
    x = jnp.asarray(rng.normal(size=lead + (L, H, P)), dtype)
    dt = jnp.asarray(rng.uniform(0.01, 0.2, size=lead + (L, H)), jnp.float32)
    A = -jnp.asarray(rng.uniform(0.5, 2.0, size=(H,)), jnp.float32)
    Bm = jnp.asarray(rng.normal(size=lead + (L, N)), dtype)
    Cm = jnp.asarray(rng.normal(size=lead + (L, N)), dtype)
    gy = jnp.asarray(rng.normal(size=lead + (L, H, P)), dtype)
    gs = jnp.asarray(rng.normal(size=lead + (H, P, N)), jnp.float32)

    def run(fn):
        if vmapped:
            fn = jax.vmap(fn, in_axes=(0, 0, None, 0, 0))
        out, vjp = jax.vjp(fn, x, dt, A, Bm, Cm)
        return out, vjp((gy, gs))

    (y, st), grads = run(lambda *a: ssd_scan(*a, chunk))
    (yr, sr), grads_r = run(lambda *a: ssd_reference(*a, chunk=chunk))
    # max abs error over the reference's largest entry: bf16 rounds the
    # activations and the kernels' matmul operands (2**-8 relative)
    tol = 1e-5 if dtype == jnp.float32 else 3e-2
    for name, a, b in zip(["y", "state", "x", "dt", "A", "B", "C"],
                          (y, st) + tuple(grads), (yr, sr) + tuple(grads_r)):
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        assert a.shape == b.shape, name
        err = np.max(np.abs(a - b)) / np.max(np.abs(b))
        assert err < tol, (name, err)


@pytest.mark.parametrize("backend, shape, chunk, has_state, sharded, want", [
    ("tpu", (4, 512, 24, 64), 128, False, False, True),   # mamba2-130m
    ("tpu", (2, 1024, 112, 64), 256, False, False, True),  # zamba2 chunk
    ("cpu", (4, 512, 24, 64), 128, False, False, False),
    ("gpu", (4, 512, 24, 64), 128, False, False, False),
    ("tpu", (4, 512, 24, 64), 128, True, False, False),    # carried state
    ("tpu", (4, 512, 24, 64), 128, False, True, False),    # client sharding
    ("tpu", (4, 520, 24, 64), 128, False, False, False),   # partial chunk
    ("tpu", (4, 512, 24, 64), 8, False, False, False),     # 8-row chunk
    ("tpu", (4, 512, 6, 48), 128, False, False, False),    # heads off slabs
])
def test_ssd_kernel_dispatch_rule(backend, shape, chunk, has_state, sharded,
                                  want):
    """models/ssm.py takes the fused kernels only on the TPU, from a zero
    state, without client sharding, where the blocks tile the shapes."""
    from repro.kernels.ssd_scan.ops import use_kernel

    assert use_kernel(backend, shape, 128, chunk, 2, has_state=has_state,
                      sharded=sharded) is want


def test_ssd_decode_chain_matches_scan():
    rng = np.random.default_rng(3)
    B, L, H, P, N = 2, 16, 2, 4, 8
    x = jnp.asarray(rng.normal(size=(B, L, H, P)), jnp.float32)
    dt = jnp.asarray(rng.uniform(0.01, 0.2, size=(B, L, H)), jnp.float32)
    A = -jnp.asarray(rng.uniform(0.5, 2.0, size=(H,)), jnp.float32)
    Bm = jnp.asarray(rng.normal(size=(B, L, N)), jnp.float32)
    Cm = jnp.asarray(rng.normal(size=(B, L, N)), jnp.float32)
    yr, sr = ssd_reference(x, dt, A, Bm, Cm, chunk=16)
    h = jnp.zeros((B, H, P, N))
    ys = []
    for t in range(L):
        y_t, h = ssd_decode_step(h, x[:, t], dt[:, t], A, Bm[:, t], Cm[:, t])
        ys.append(np.asarray(y_t))
    np.testing.assert_allclose(np.stack(ys, 1), np.asarray(yr), atol=1e-5)
    np.testing.assert_allclose(np.asarray(h), np.asarray(sr), atol=1e-5)


# ---------------------------------------------------------------------------
# fused MTSL update (hypothesis sweep)
# ---------------------------------------------------------------------------


if given is not None:

    @settings(max_examples=25, deadline=None)
    @given(
        n=st.integers(1, 2000),
        eta=st.floats(0.0, 10.0, allow_nan=False),
        seed=st.integers(0, 2**31 - 1),
    )
    def test_mtsl_update_matches_reference(n, eta, seed):
        rng = np.random.default_rng(seed)
        p = jnp.asarray(rng.normal(size=(n,)), jnp.float32)
        g = jnp.asarray(rng.normal(size=(n,)), jnp.float32)
        out = mtsl_update(p, g, eta)
        ref = mtsl_update_reference(p, g, eta)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=1e-6)

else:  # pragma: no cover

    @pytest.mark.skip(reason="hypothesis not installed")
    def test_mtsl_update_matches_reference():
        pass


@pytest.mark.parametrize("shape", [(3, 5), (128,), (7, 129), (2, 3, 4)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_mtsl_update_shapes_dtypes(shape, dtype):
    rng = np.random.default_rng(7)
    p = jnp.asarray(rng.normal(size=shape), dtype)
    g = jnp.asarray(rng.normal(size=shape), dtype)
    out = mtsl_update(p, g, 0.1)
    ref = mtsl_update_reference(p, g, 0.1)
    assert out.shape == shape and out.dtype == dtype
    np.testing.assert_allclose(np.asarray(out, np.float32), np.asarray(ref, np.float32),
                               atol=1e-2 if dtype == jnp.bfloat16 else 1e-6)
