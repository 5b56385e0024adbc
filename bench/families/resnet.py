"""Family `resnet`: CIFAR ResNets (He et al., arXiv:1512.03385 section 4.2)
split for MTSL, tower = the stem and the first `split_layers` stages.

Weights: convs N(0, 2/fan_in) (He); head N(0, 1/fan_in), bias 0.

Model FLOPs per image: every convolution and the head (2 per
multiply-add).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from reference import resnet as ref
from weights import F32, normal

_KEYS = ("resnet_stages", "image_size", "image_channels", "num_classes")


def ref_cfg(config):
    """The reference's view of the configuration file."""
    return {k: config[k] for k in _KEYS + ("split_layers",)}


def program_want(config):
    """The program config's fields that must equal the file's."""
    return dict({k: config[k] for k in _KEYS},
                resnet_stages=tuple(map(tuple, config["resnet_stages"])))


def _conv(key, lead, k, cin, cout):
    return {"w": normal(key, lead + (k, k, cin, cout),
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


def make_params(key, cfg, M):
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
    server["head"] = {"w": normal(ks[-1], (cin, cfg["num_classes"]),
                                  1.0 / math.sqrt(cin)),
                      "b": jnp.zeros((cfg["num_classes"],), F32)}
    return {"towers": towers, "server": server}


def _conv_flops(hw, k, cin, cout):
    return 2 * hw * hw * k * k * cin * cout


def forward_per_image(config):
    size, stages = config["image_size"], config["resnet_stages"]
    c0 = stages[0][0]
    total = _conv_flops(size, 3, config["image_channels"], c0)
    cin, hw = c0, size
    for s, (cout, nblocks) in enumerate(stages):
        hw = hw if s == 0 else hw // 2
        for i in range(nblocks):
            c = cin if i == 0 else cout
            total += (_conv_flops(hw, 3, c, cout)
                      + _conv_flops(hw, 3, cout, cout))
            if c != cout:
                total += _conv_flops(hw, 1, c, cout)
        cin = cout
    return total + 2 * cin * config["num_classes"]


def train_round_flops(config, traffic):
    images = traffic["clients"] * traffic["batch_per_client"]
    return 3 * images * forward_per_image(config)


loss_and_grads = ref.loss_and_grads
