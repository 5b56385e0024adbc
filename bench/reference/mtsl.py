"""The MTSL round of the paper's Alg. 1 around a reference model's loss:
one gradient of the summed per-client losses, then one optimizer step in
which the server's learning rate is `server_scale` times the clients'.

AdamW follows Loshchilov & Hutter with bias correction (weight decay 0);
SGD is the plain step. Written without the program's optimizer library.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def _scaled(updates, server_scale):
    return {"towers": updates["towers"],
            "server": jax.tree.map(lambda u: u * server_scale,
                                   updates["server"])}


def init_opt(opt, params):
    if opt["name"] == "adamw":
        z = jax.tree.map(jnp.zeros_like, params)
        return {"mu": z, "nu": jax.tree.map(jnp.zeros_like, params)}
    return {}


def apply_opt(opt, params, grads, state, step):
    """One step; `step` counts from 1. Returns (params, state)."""
    lr = opt["lr"]
    if opt["name"] == "sgd":
        upd = jax.tree.map(lambda g: -lr * g, grads)
        new = {}
    else:
        b1, b2, eps = opt["b1"], opt["b2"], opt["eps"]
        mu = jax.tree.map(lambda m, g: b1 * m + (1 - b1) * g, state["mu"], grads)
        nu = jax.tree.map(lambda v, g: b2 * v + (1 - b2) * g * g, state["nu"],
                          grads)
        c1, c2 = 1 - b1 ** step, 1 - b2 ** step
        upd = jax.tree.map(
            lambda m, v: -lr * (m / c1) / (jnp.sqrt(v / c2) + eps), mu, nu)
        new = {"mu": mu, "nu": nu}
    upd = _scaled(upd, opt["server_scale"])
    return jax.tree.map(jnp.add, params, upd), new
