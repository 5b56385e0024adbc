"""Mamba2 (SSD) block: projections + causal depthwise conv + chunked SSD +
gated RMSNorm + output projection. Decode keeps (conv_state, ssm_state) and
is O(1) per token — this is what makes the ssm/hybrid archs long_500k-able.

Train/prefill math goes through kernels/ssd_scan: the fused Pallas
kernels (forward and backward) on the TPU where ops.use_kernel allows,
else the chunked reference, which is also the CPU path.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.kernels.ssd_scan.ops import ssd_scan, use_kernel
from repro.kernels.ssd_scan.ref import ssd_reference, ssd_decode_step
from repro.models.layers import rmsnorm_params, rmsnorm
from repro.nn import param


def _dims(cfg: ModelConfig):
    d_in = cfg.ssm_expand * cfg.d_model
    nheads = d_in // cfg.ssm_headdim
    return d_in, nheads, cfg.ssm_state, cfg.ssm_conv_width


def mamba_params(rng, cfg: ModelConfig):
    d = cfg.d_model
    d_in, H, N, W = _dims(cfg)
    ks = jax.random.split(rng, 12)
    dt = jnp.dtype(cfg.param_dtype)
    return {
        "norm": rmsnorm_params(ks[0], d),
        "wz": param(ks[1], (d, d_in), ("embed", "ssm_inner"), dtype=dt),
        "wx": param(ks[2], (d, d_in), ("embed", "ssm_inner"), dtype=dt),
        "wB": param(ks[3], (d, N), ("embed", "state"), dtype=dt),
        "wC": param(ks[4], (d, N), ("embed", "state"), dtype=dt),
        "wdt": param(ks[5], (d, H), ("embed", "ssm_heads"), dtype=dt),
        "conv_x": param(ks[6], (W, d_in), (None, "ssm_inner"), init="fan_in", dtype=dt, fan_in=W),
        "conv_B": param(ks[7], (W, N), (None, "state"), init="fan_in", dtype=dt, fan_in=W),
        "conv_C": param(ks[8], (W, N), (None, "state"), init="fan_in", dtype=dt, fan_in=W),
        "A_log": param(ks[9], (H,), ("ssm_heads",), init="zeros", dtype=jnp.float32),
        "D": param(ks[10], (H,), ("ssm_heads",), init="ones", dtype=jnp.float32),
        "dt_bias": param(ks[11], (H,), ("ssm_heads",), init="zeros", dtype=jnp.float32),
        "gate_norm": {"scale": param(rng, (d_in,), ("ssm_inner",), init="ones", dtype=dt)},
        "wo": param(jax.random.fold_in(rng, 7), (d_in, d), ("ssm_inner", "embed"), dtype=dt),
    }


def _causal_conv(x, w):
    """Depthwise causal conv. x: [B,L,D]; w: [W,D]."""
    W = w.shape[0]
    xp = jnp.pad(x, ((0, 0), (W - 1, 0), (0, 0)))
    y = sum(xp[:, i : i + x.shape[1], :] * w[i][None, None, :] for i in range(W))
    return y


def _gated_norm(p, y, z, eps):
    """RMSNorm(y * silu(z)) — Mamba2's gated output norm."""
    g = y * jax.nn.silu(z)
    gf = g.astype(jnp.float32)
    var = jnp.mean(jnp.square(gf), axis=-1, keepdims=True)
    return (gf * jax.lax.rsqrt(var + eps) * p["scale"].astype(jnp.float32)).astype(y.dtype)


def mamba_forward(p, x, cfg: ModelConfig, *, return_state: bool = False,
                  initial_state=None):
    """x: [B,L,d] -> y [B,L,d] (+ final ssm state if return_state)."""
    cdt = jnp.dtype(cfg.dtype)
    d_in, H, N, W = _dims(cfg)
    P = cfg.ssm_headdim
    B_, L, _ = x.shape
    h = rmsnorm(p["norm"], x, cfg.norm_eps)
    z = jnp.einsum("bld,de->ble", h, p["wz"].astype(cdt))
    xin = jnp.einsum("bld,de->ble", h, p["wx"].astype(cdt))
    Bm = jnp.einsum("bld,dn->bln", h, p["wB"].astype(cdt))
    Cm = jnp.einsum("bld,dn->bln", h, p["wC"].astype(cdt))
    dt_ = jnp.einsum("bld,dh->blh", h, p["wdt"].astype(cdt))

    xin = jax.nn.silu(_causal_conv(xin, p["conv_x"].astype(cdt)))
    Bm = jax.nn.silu(_causal_conv(Bm, p["conv_B"].astype(cdt)))
    Cm = jax.nn.silu(_causal_conv(Cm, p["conv_C"].astype(cdt)))
    dt_ = jax.nn.softplus(dt_.astype(jnp.float32) + p["dt_bias"][None, None, :])
    A = -jnp.exp(p["A_log"])  # negative decays

    xh = xin.reshape(B_, L, H, P)
    # pad L to a chunk multiple
    chunk = cfg.ssm_chunk
    Lp = -(-L // chunk) * chunk
    if Lp != L:
        padl = Lp - L
        xh = jnp.pad(xh, ((0, 0), (0, padl), (0, 0), (0, 0)))
        dt_ = jnp.pad(dt_, ((0, 0), (0, padl), (0, 0)))
        Bm = jnp.pad(Bm, ((0, 0), (0, padl), (0, 0)))
        Cm = jnp.pad(Cm, ((0, 0), (0, padl), (0, 0)))
    # imported here: repro.core imports the models
    from repro.core.client_axis import current_sharding

    fused = use_kernel(jax.default_backend(), xh.shape, N, chunk,
                       xh.dtype.itemsize, has_state=initial_state is not None,
                       sharded=current_sharding() is not None)
    # one scope whatever implements the scan, so its device time reads alike
    with jax.named_scope("mamba.ssd"):
        if fused:
            y, state = ssd_scan(xh, dt_, A, Bm, Cm, chunk)
        else:
            y, state = ssd_reference(xh, dt_, A, Bm, Cm, chunk=chunk, initial_state=initial_state)
    y = y[:, :L]
    y = y + xin.reshape(B_, L, H, P) * p["D"][None, None, :, None].astype(cdt)
    y = y.reshape(B_, L, d_in)
    y = _gated_norm(p["gate_norm"], y, z, cfg.norm_eps)
    out = jnp.einsum("ble,ed->bld", y, p["wo"].astype(cdt))
    if return_state:
        return out, state
    return out


def init_mamba_cache(cfg: ModelConfig, batch: int):
    d_in, H, N, W = _dims(cfg)
    P = cfg.ssm_headdim
    cdt = jnp.dtype(cfg.dtype)
    return {
        "conv_x": jnp.zeros((batch, W - 1, d_in), cdt),
        "conv_B": jnp.zeros((batch, W - 1, N), cdt),
        "conv_C": jnp.zeros((batch, W - 1, N), cdt),
        "state": jnp.zeros((batch, H, P, N), jnp.float32),
    }


def mamba_prefill(p, x, cfg: ModelConfig):
    """Forward + build decode cache from the tail of the sequence."""
    cdt = jnp.dtype(cfg.dtype)
    d_in, H, N, W = _dims(cfg)
    out, state = mamba_forward(p, x, cfg, return_state=True)
    h = rmsnorm(p["norm"], x, cfg.norm_eps)
    xin = jnp.einsum("bld,de->ble", h, p["wx"].astype(cdt))
    Bm = jnp.einsum("bld,dn->bln", h, p["wB"].astype(cdt))
    Cm = jnp.einsum("bld,dn->bln", h, p["wC"].astype(cdt))
    cache = {
        "conv_x": xin[:, -(W - 1):, :],
        "conv_B": Bm[:, -(W - 1):, :],
        "conv_C": Cm[:, -(W - 1):, :],
        "state": state,
    }
    return out, cache


def mamba_extend(p, x_c, cache, n_valid, cfg: ModelConfig):
    """Chunked-prefill continuation: run a fixed-size chunk of C tokens
    through the block, resuming from a decode cache. x_c: [B,C,d];
    n_valid: [B] real (non-padding) tokens per row, 1 <= n_valid <= C.

    Unlike attention (where padded K/V sit above every real query's causal
    horizon), the SSD state update is a running reduction — a padded step
    with garbage dt would decay and pollute the state. Padded steps are
    therefore neutralised *after* softplus (dt = 0 -> exp(dt*A) = 1 and a
    zero B-injection: an exact identity update), so the final state equals
    a real-row-only scan. Conv history is carried as raw pre-silu tails,
    matching mamba_prefill/mamba_decode, and the new tail is sliced at each
    row's n_valid offset. Returns (y [B,C,d], new_cache); outputs at padded
    positions are garbage and must be ignored by the caller."""
    cdt = jnp.dtype(cfg.dtype)
    d_in, H, N, W = _dims(cfg)
    P = cfg.ssm_headdim
    B_, C, _ = x_c.shape
    n_valid = jnp.broadcast_to(jnp.asarray(n_valid, jnp.int32), (B_,))
    h = rmsnorm(p["norm"], x_c, cfg.norm_eps)
    z = jnp.einsum("bld,de->ble", h, p["wz"].astype(cdt))
    xin = jnp.einsum("bld,de->ble", h, p["wx"].astype(cdt))
    Bm = jnp.einsum("bld,dn->bln", h, p["wB"].astype(cdt))
    Cm = jnp.einsum("bld,dn->bln", h, p["wC"].astype(cdt))
    dt_ = jnp.einsum("bld,dh->blh", h, p["wdt"].astype(cdt))

    def conv_extend(hist, new, w):
        # hist: [B,W-1,D] raw tail; new: [B,C,D]. Valid (no left pad) conv
        # over the concatenation — position t sees [t, t+W) of the full
        # array, i.e. the W-1 cached steps plus the chunk, causally.
        full = jnp.concatenate([hist.astype(new.dtype), new], axis=1)
        y = sum(full[:, i : i + C, :] * w[i][None, None, :] for i in range(W))
        tail = jax.vmap(
            lambda f, n: jax.lax.dynamic_slice_in_dim(f, n, W - 1, axis=0)
        )(full, n_valid)
        return y, tail

    xin_c, conv_x = conv_extend(cache["conv_x"], xin, p["conv_x"].astype(cdt))
    Bm_c, conv_B = conv_extend(cache["conv_B"], Bm, p["conv_B"].astype(cdt))
    Cm_c, conv_C = conv_extend(cache["conv_C"], Cm, p["conv_C"].astype(cdt))
    xin_c = jax.nn.silu(xin_c)
    Bm_c = jax.nn.silu(Bm_c)
    Cm_c = jax.nn.silu(Cm_c)
    dt_c = jax.nn.softplus(dt_.astype(jnp.float32) + p["dt_bias"][None, None, :])
    valid = (jnp.arange(C)[None, :] < n_valid[:, None])[:, :, None]
    dt_c = jnp.where(valid, dt_c, 0.0)
    A = -jnp.exp(p["A_log"])

    xh = xin_c.reshape(B_, C, H, P)
    chunk = cfg.ssm_chunk
    Lp = -(-C // chunk) * chunk
    if Lp != C:
        padl = Lp - C
        xh = jnp.pad(xh, ((0, 0), (0, padl), (0, 0), (0, 0)))
        dt_c = jnp.pad(dt_c, ((0, 0), (0, padl), (0, 0)))
        Bm_c = jnp.pad(Bm_c, ((0, 0), (0, padl), (0, 0)))
        Cm_c = jnp.pad(Cm_c, ((0, 0), (0, padl), (0, 0)))
    y, state = ssd_reference(xh, dt_c, A, Bm_c, Cm_c, chunk=chunk,
                             initial_state=cache["state"])
    y = y[:, :C]
    y = y + xin_c.reshape(B_, C, H, P) * p["D"][None, None, :, None].astype(cdt)
    y = y.reshape(B_, C, d_in)
    y = _gated_norm(p["gate_norm"], y, z, cfg.norm_eps)
    out = jnp.einsum("ble,ed->bld", y, p["wo"].astype(cdt))
    new_cache = {"conv_x": conv_x, "conv_B": conv_B, "conv_C": conv_C, "state": state}
    return out, new_cache


def mamba_decode(p, x_t, cache, cfg: ModelConfig):
    """One-token decode. x_t: [B,1,d]. Returns (y_t [B,1,d], new_cache)."""
    cdt = jnp.dtype(cfg.dtype)
    d_in, H, N, W = _dims(cfg)
    P = cfg.ssm_headdim
    h = rmsnorm(p["norm"], x_t, cfg.norm_eps)[:, 0]  # [B,d]
    z = h @ p["wz"].astype(cdt)
    xin = h @ p["wx"].astype(cdt)
    Bm = h @ p["wB"].astype(cdt)
    Cm = h @ p["wC"].astype(cdt)
    dt_ = h @ p["wdt"].astype(cdt)

    def conv_step(state, new, w):
        # state: [B, W-1, D]; new: [B, D]
        full = jnp.concatenate([state, new[:, None, :]], axis=1)  # [B,W,D]
        y = jnp.einsum("bwd,wd->bd", full, w)
        return y, full[:, 1:, :]

    xin_c, conv_x = conv_step(cache["conv_x"], xin, p["conv_x"].astype(cdt))
    Bm_c, conv_B = conv_step(cache["conv_B"], Bm, p["conv_B"].astype(cdt))
    Cm_c, conv_C = conv_step(cache["conv_C"], Cm, p["conv_C"].astype(cdt))
    xin_c = jax.nn.silu(xin_c)
    Bm_c = jax.nn.silu(Bm_c)
    Cm_c = jax.nn.silu(Cm_c)
    dt_c = jax.nn.softplus(dt_.astype(jnp.float32) + p["dt_bias"][None, :])
    A = -jnp.exp(p["A_log"])

    xh = xin_c.reshape(-1, H, P)
    y, state = ssd_decode_step(cache["state"], xh, dt_c, A, Bm_c, Cm_c)
    y = y + xh * p["D"][None, :, None].astype(cdt)
    y = y.reshape(-1, d_in)
    y = _gated_norm(p["gate_norm"], y, z, cfg.norm_eps)
    out = (y @ p["wo"].astype(cdt))[:, None, :]
    new_cache = {"conv_x": conv_x, "conv_B": conv_B, "conv_C": conv_C, "state": state}
    return out, new_cache
