"""Plain float32 reference of a Mamba-2 language model split for MTSL.

Written from the Mamba-2 paper (arXiv:2405.21060, section 7 and listing 1)
and the MTSL paper's Alg. 1, with nothing imported from the program. It reads
parameters in the program's tree layout (the benchmark makes them, see
`bench/weights.py`) and computes:

  forward    embedding x sqrt(d_model) -> blocks -> RMSNorm -> head logits
  block      x + out_proj(RMSNorm(SSD(conv(x W_x)) + D x) * silu(x W_z)))
             with B, C, dt from their own projections, a width-W causal
             depthwise convolution on x, B and C, softplus(dt + dt_bias), and
             A = -exp(A_log)
  SSD        y_t = sum_{s<=t} (C_t . B_s) exp(sum_{k=s+1..t} dt_k A) dt_s x_s,
             the whole-sequence quadratic ("dual") form: no chunks and no
             carried state, so it shares no algorithm with the program's
             chunked scan. `ssd_sequential` is the recurrence itself; the
             tests check that the two agree.
  MTSL round the loss is the sum over clients of each client's mean
             next-token cross-entropy; one AdamW step on towers and server,
             the server's learning rate scaled by `server_scale`.

Every matrix product runs at "highest" precision and every activation is
float32. `cdt` names a lower precision to round matmul operands and block
outputs to in the forward pass: the control that the benchmark's
comparison has to reject.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

F32 = jnp.float32
HIGHEST = jax.lax.Precision.HIGHEST


def _q(x, cdt):
    """Round to the control precision (identity for float32). The backward
    pass lets the cotangent through unrounded, as a lower-precision forward
    with higher-precision gradients does: rounded, the small cotangents of
    a deep model would flush to zero in float8."""
    if cdt is None or jnp.dtype(cdt) == F32:
        return x
    return _round(x, str(cdt))


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def _round(x, cdt):
    return x.astype(cdt).astype(F32)


_round.defvjp(lambda x, cdt: (_round(x, cdt), None),
              lambda cdt, _, g: (g,))


def _mm(spec, a, b, cdt=None):
    return jnp.einsum(spec, _q(a, cdt), _q(b, cdt), precision=HIGHEST,
                      preferred_element_type=F32)


def rmsnorm(x, scale, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale


def causal_conv(x, w):
    """Depthwise causal convolution: y_t = sum_i w[i] x_{t-(W-1)+i}."""
    W, L = w.shape[0], x.shape[1]
    xp = jnp.pad(x, ((0, 0), (W - 1, 0), (0, 0)))
    return sum(xp[:, i:i + L] * w[i] for i in range(W))


def ssd_quadratic(x, dt, A, B, C, cdt=None):
    """x [b,L,H,P], dt [b,L,H], A [H], B/C [b,L,N] -> y [b,L,H,P]."""
    L = x.shape[1]
    cs = jnp.cumsum(dt * A, axis=1)  # [b,L,H]
    seg = cs[:, :, None, :] - cs[:, None, :, :]  # [b,t,s,H]
    causal = jnp.tril(jnp.ones((L, L), bool))[None, :, :, None]
    decay = jnp.exp(jnp.where(causal, seg, -jnp.inf))
    cb = _mm("btn,bsn->bts", C, B, cdt)
    w = cb[..., None] * decay * dt[:, None, :, :]
    return _mm("btsh,bshp->bthp", w, x, cdt)


def ssd_sequential(x, dt, A, B, C):
    """The recurrence h_t = exp(dt_t A) h_{t-1} + dt_t x_t B_t^T,
    y_t = h_t C_t, one step at a time (for the tests)."""
    b, L, H, P = x.shape
    N = B.shape[-1]

    def step(h, inp):
        xt, dtt, bt, ct = inp
        h = (h * jnp.exp(dtt * A)[:, :, None, None]
             + jnp.einsum("bhp,bn->bhpn", xt * dtt[..., None], bt,
                          precision=HIGHEST))
        return h, jnp.einsum("bhpn,bn->bhp", h, ct, precision=HIGHEST)

    xs = tuple(jnp.moveaxis(t, 1, 0) for t in (x, dt, B, C))
    _, y = jax.lax.scan(step, jnp.zeros((b, H, P, N), F32), xs)
    return jnp.moveaxis(y, 0, 1)


def mamba_block(p, x, cfg, cdt=None, ssd=ssd_quadratic):
    """One residual Mamba-2 block. p: one layer's leaves; x [b,L,d]."""
    d_in = cfg["ssm_expand"] * cfg["d_model"]
    P = cfg["ssm_headdim"]
    H = d_in // P
    eps = cfg["norm_eps"]
    b, L, _ = x.shape
    h = rmsnorm(x, p["norm"]["scale"], eps)
    z = _mm("bld,de->ble", h, p["wz"], cdt)
    xin = _mm("bld,de->ble", h, p["wx"], cdt)
    Bm = _mm("bld,dn->bln", h, p["wB"], cdt)
    Cm = _mm("bld,dn->bln", h, p["wC"], cdt)
    dt = _mm("bld,dh->blh", h, p["wdt"], cdt)
    xin = jax.nn.silu(causal_conv(xin, p["conv_x"]))
    Bm = jax.nn.silu(causal_conv(Bm, p["conv_B"]))
    Cm = jax.nn.silu(causal_conv(Cm, p["conv_C"]))
    dt = jax.nn.softplus(dt + p["dt_bias"])
    A = -jnp.exp(p["A_log"])
    xh = xin.reshape(b, L, H, P)
    if ssd is ssd_quadratic:
        y = ssd(xh, dt, A, Bm, Cm, cdt)
    else:
        y = ssd(xh, dt, A, Bm, Cm)
    y = (y + xh * p["D"][:, None]).reshape(b, L, d_in)
    y = rmsnorm(y * jax.nn.silu(z), p["gate_norm"]["scale"], eps)
    return _q(x + _mm("ble,ed->bld", y, p["wo"], cdt), cdt)


def _layers(blocks):
    """The stacked [layers, ...] leaves of one stack."""
    return blocks["seg0"]["0"]["mamba"]


def run_blocks(stacked, x, cfg, cdt=None, ssd=ssd_quadratic):
    """Apply every layer of a stacked block tree in order, each layer
    recomputed in the backward pass so that one layer's SSD is live."""
    body = jax.checkpoint(lambda h, p: (mamba_block(p, h, cfg, cdt, ssd),
                                        None))
    x, _ = jax.lax.scan(body, x, _layers(stacked))
    return x


def tower(tp, tokens, cfg, cdt=None, ssd=ssd_quadratic):
    x = tp["embed"]["table"][tokens] * math.sqrt(cfg["d_model"])
    return run_blocks(tp["blocks"], _q(x, cdt), cfg, cdt, ssd)


def server_logits(sp, h, cfg, cdt=None, ssd=ssd_quadratic):
    x = run_blocks(sp["blocks"], h, cfg, cdt, ssd)
    x = rmsnorm(x, sp["norm"]["scale"], cfg["norm_eps"])
    return _mm("bld,dv->blv", x, sp["head"]["w"], cdt)


def logits(tp, sp, tokens, cfg, cdt=None, ssd=ssd_quadratic):
    """Full forward of one client's model: tokens [b,L] -> [b,L,V]."""
    return server_logits(sp, tower(tp, tokens, cfg, cdt, ssd), cfg, cdt, ssd)


def client_loss(tp, sp, tokens, cfg, cdt=None, ssd=ssd_quadratic):
    """Mean next-token cross-entropy of one client's batch [b,L]."""
    lg = logits(tp, sp, tokens, cfg, cdt, ssd)[:, :-1]
    gold = jnp.take_along_axis(lg, tokens[:, 1:, None], axis=-1)[..., 0]
    return jnp.mean(jax.nn.logsumexp(lg, axis=-1) - gold)


def loss_and_grads(params, tokens, cfg, cdt=None, ssd=ssd_quadratic):
    """Sum over clients of their mean losses, and its gradient. The clients
    run one at a time: the server's gradient is the sum of theirs."""
    towers, server = params["towers"], params["server"]
    grad_fn = jax.value_and_grad(
        lambda tp, sp, tk: client_loss(tp, sp, tk, cfg, cdt, ssd),
        argnums=(0, 1))

    def body(carry, xs):
        loss, gs = carry
        tp, tk = xs
        l, (gt, g) = grad_fn(tp, server, tk)
        return (loss + l, jax.tree.map(jnp.add, gs, g)), gt

    zero = jax.tree.map(jnp.zeros_like, server)
    (loss, gs), gt = jax.lax.scan(body, (jnp.zeros((), F32), zero),
                                  (towers, tokens))
    return loss, {"towers": gt, "server": gs}
