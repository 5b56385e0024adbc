"""Weights made by the benchmark from the seed, in the program's tree layout.

The program runs and the reference checks the same weights, and neither
makes them. Each family's maker is one jitted call that builds every leaf
on the device in float32 from `jax.random` keys folded from the seed, with
the published initialisation of its architecture:

  mamba2   projections N(0, 1/fan_in); conv taps N(0, 1/width); embedding
           N(0, 0.02^2); A = -U[1, 16] (A_log = log of it); dt_bias the
           inverse softplus of dt ~ logU[1e-3, 1e-1]; D and norm scales 1
           (arXiv:2405.21060, mamba_ssm's Mamba2 defaults)
  resnet   convs N(0, 2/fan_in) (He); head N(0, 1/fan_in), bias 0

`unit_axes` says how many leading axes of each leaf index separate units
(client, then layer) for the per-unit norms that `check.py` compares.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

F32 = jnp.float32


def seed_key(seed: int):
    """A key for any whole-number seed (PRNGKey alone keeps 32 bits)."""
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                              (seed >> 32) & 0xFFFFFFFF)


def _normal(key, shape, std):
    return jax.random.normal(key, shape, F32) * std


def _mamba_layers(key, lead, cfg):
    d, N, W = cfg["d_model"], cfg["ssm_state"], cfg["ssm_conv_width"]
    d_in = cfg["ssm_expand"] * d
    H = d_in // cfg["ssm_headdim"]
    ks = iter(jax.random.split(key, 16))

    def mat(shape, fan_in):
        return _normal(next(ks), lead + shape, 1.0 / math.sqrt(fan_in))

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


def mamba2_params(key, cfg, M):
    d, V = cfg["d_model"], cfg["vocab_size"]
    tower_layers = cfg["split_layers"]
    server_layers = cfg["num_layers"] - tower_layers
    k = jax.random.split(key, 4)
    return {
        "towers": {
            "embed": {"table": _normal(k[0], (M, V, d), 0.02)},
            "blocks": {"seg0": {"0": _mamba_layers(k[1], (M, tower_layers),
                                                   cfg)}},
        },
        "server": {
            "blocks": {"seg0": {"0": _mamba_layers(k[2], (server_layers,),
                                                   cfg)}},
            "norm": {"scale": jnp.ones((d,), F32)},
            "head": {"w": _normal(k[3], (d, V), 1.0 / math.sqrt(d))},
        },
    }


def _conv(key, lead, k, cin, cout):
    return {"w": _normal(key, lead + (k, k, cin, cout),
                         math.sqrt(2.0 / (k * k * cin)))}


def _stage(key, lead, cin, cout, nblocks):
    out = {}
    for i, kb in enumerate(jax.random.split(key, nblocks)):
        k1, k2, k3 = jax.random.split(kb, 3)
        c = cin if i == 0 else cout
        b = {"conv1": _conv(k1, lead, 3, c, cout),
             "conv2": _conv(k2, lead, 3, cout, cout)}
        if c != cout:
            b["proj"] = _conv(k3, lead, 1, c, cout)
        out[f"b{i}"] = b
    return out


def resnet_params(key, cfg, M):
    stages, split = cfg["resnet_stages"], cfg["split_layers"]
    ks = jax.random.split(key, len(stages) + 2)
    towers = {"stem": _conv(ks[0], (M,), 3, cfg["image_channels"],
                            stages[0][0])}
    server = {}
    cin = stages[0][0]
    for s, (cout, nb) in enumerate(stages):
        lead, side = ((M,), towers) if s < split else ((), server)
        side[f"stage{s}"] = _stage(ks[s + 1], lead, cin, cout, nb)
        cin = cout
    server["head"] = {"w": _normal(ks[-1], (cin, cfg["num_classes"]),
                                   1.0 / math.sqrt(cin)),
                      "b": jnp.zeros((cfg["num_classes"],), F32)}
    return {"towers": towers, "server": server}


MAKERS = {"mamba2": mamba2_params, "resnet": resnet_params}


def make_params(family, seed, cfg, M):
    """Every weight of the cell, on the default device, from the seed."""
    fn = jax.jit(lambda key: MAKERS[family](key, cfg, M))
    return fn(seed_key(seed))


def unit_axes(params):
    """Leading axes per leaf that index units: the client axis of a tower
    leaf, and the layer axis of a stacked-block leaf."""
    def axes(path, _):
        names = [getattr(p, "key", None) for p in path]
        return int(names[0] == "towers") + int("seg0" in names)

    return jax.tree_util.tree_map_with_path(axes, params)
