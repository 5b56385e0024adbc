"""Family `mamba2`: Mamba-2 language models (arXiv:2405.21060) split for
MTSL, tower = embedding + the first `split_layers` blocks.

Weights: projections N(0, 1/fan_in); conv taps N(0, 1/width); embedding
N(0, 0.02^2); A = -U[1, 16] (A_log = log of it); dt_bias the inverse
softplus of dt ~ logU[1e-3, 1e-1]; D and norm scales 1 (mamba_ssm's Mamba2
defaults).

Model FLOPs per token: every matrix product (2 per multiply-add), the
causal convolution, and the SSD as its linear recurrence per token and head
(decay and injection of the P x N state, 3PN, and its read-out, 2PN).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from reference import mamba2 as ref
from weights import F32, normal


def ref_cfg(config):
    """The reference's view of the configuration file."""
    return {"d_model": config["d_model"], "num_layers": config["n_layer"],
            "vocab_size": config["vocab_size"],
            "ssm_state": config["d_state"],
            "ssm_conv_width": config["d_conv"],
            "ssm_expand": config["expand"],
            "ssm_headdim": config["headdim"],
            "norm_eps": config["norm_epsilon"],
            "split_layers": config["split_layers"]}


def program_want(config):
    """The program config's fields that must equal the file's."""
    return {"d_model": config["d_model"], "num_layers": config["n_layer"],
            "vocab_size": config["vocab_size"],
            "ssm_state": config["d_state"],
            "ssm_conv_width": config["d_conv"],
            "ssm_expand": config["expand"],
            "ssm_headdim": config["headdim"],
            "ssm_chunk": config["chunk_size"],
            "norm_eps": config["norm_epsilon"]}


def _layers(key, lead, cfg):
    d, N, W = cfg["d_model"], cfg["ssm_state"], cfg["ssm_conv_width"]
    d_in = cfg["ssm_expand"] * d
    H = d_in // cfg["ssm_headdim"]
    ks = iter(jax.random.split(key, 16))

    def mat(shape, fan_in):
        return normal(next(ks), lead + shape, 1.0 / math.sqrt(fan_in))

    dt = jnp.exp(jax.random.uniform(next(ks), lead + (H,), F32,
                                    math.log(1e-3), math.log(1e-1)))
    return {"mamba": {
        "norm": {"scale": jnp.ones(lead + (d,), F32)},
        "wz": mat((d, d_in), d), "wx": mat((d, d_in), d),
        "wB": mat((d, N), d), "wC": mat((d, N), d), "wdt": mat((d, H), d),
        "conv_x": mat((W, d_in), W), "conv_B": mat((W, N), W),
        "conv_C": mat((W, N), W),
        "A_log": jnp.log(jax.random.uniform(next(ks), lead + (H,), F32,
                                            1.0, 16.0)),
        "D": jnp.ones(lead + (H,), F32),
        "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),  # softplus^-1(dt)
        "gate_norm": {"scale": jnp.ones(lead + (d_in,), F32)},
        "wo": mat((d_in, d), d_in),
    }}


def make_params(key, cfg, M):
    d, V = cfg["d_model"], cfg["vocab_size"]
    tower_layers = cfg["split_layers"]
    server_layers = cfg["num_layers"] - tower_layers
    k = jax.random.split(key, 4)
    return {
        "towers": {
            "embed": {"table": normal(k[0], (M, V, d), 0.02)},
            "blocks": {"seg0": {"0": _layers(k[1], (M, tower_layers), cfg)}},
        },
        "server": {
            "blocks": {"seg0": {"0": _layers(k[2], (server_layers,), cfg)}},
            "norm": {"scale": jnp.ones((d,), F32)},
            "head": {"w": normal(k[3], (d, V), 1.0 / math.sqrt(d))},
        },
    }


def forward_per_token(config):
    d, V, N, W = (config["d_model"], config["vocab_size"], config["d_state"],
                  config["d_conv"])
    d_in = config["expand"] * d
    H = d_in // config["headdim"]
    P = config["headdim"]
    proj = 2 * d * (2 * d_in + 2 * N + H) + 2 * d_in * d
    conv = 2 * W * (d_in + 2 * N)
    ssd = 5 * H * P * N
    return config["n_layer"] * (proj + conv + ssd) + 2 * d * V


def train_round_flops(config, traffic):
    tokens = (traffic["clients"] * traffic["batch_per_client"]
              * traffic["seq_len"])
    return 3 * tokens * forward_per_token(config)


def loss_and_grads(params, batch, cfg, cdt=None):
    return ref.loss_and_grads(params, batch["tokens"], cfg, cdt)
