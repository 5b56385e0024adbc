"""Plain float32 reference of a CIFAR ResNet split for MTSL.

Written from He et al., arXiv:1512.03385 section 4.2 (stages of 3x3 convs
with 16/32/64 filters, global pool, dense head), with nothing imported from
the program. LayerNorm over channels stands in for BatchNorm and a 1x1
projection for the zero-padded shortcut, as in the program (its documented
choices; LayerNorm makes the loss independent of the batch). Parameters
come in the program's tree layout (made by `bench/weights.py`):

  tower   3x3 stem conv -> LN -> relu, then stages 0..split-1
  server  stages split.., global average pool, dense head
  block   relu(LN(conv2(relu(LN(conv1(x, stride))))) + shortcut), the
          shortcut a 1x1 strided conv where the width changes

Convolutions run at "highest" precision on float32 activations. `cdt`
rounds conv operands and block outputs to a lower precision: the control.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from reference.mamba2 import F32, HIGHEST, _q


def conv(x, w, stride, cdt=None):
    return jax.lax.conv_general_dilated(
        _q(x, cdt), _q(w, cdt), (stride, stride), "SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"), precision=HIGHEST,
        preferred_element_type=F32)


def layernorm(x, eps=1e-5):
    m = jnp.mean(x, axis=-1, keepdims=True)
    v = jnp.mean(jnp.square(x - m), axis=-1, keepdims=True)
    return (x - m) * jax.lax.rsqrt(v + eps)


def block(p, x, stride, cdt=None):
    h = jax.nn.relu(layernorm(conv(x, p["conv1"]["w"], stride, cdt)))
    h = layernorm(conv(h, p["conv2"]["w"], 1, cdt))
    sc = conv(x, p["proj"]["w"], stride, cdt) if "proj" in p else x
    return _q(jax.nn.relu(h + sc), cdt)


def stage(p, x, first_stride, cdt=None):
    for i in range(len(p)):
        x = block(p[f"b{i}"], x, first_stride if i == 0 else 1, cdt)
    return x


def tower(tp, image, cfg, cdt=None):
    x = jax.nn.relu(layernorm(conv(image, tp["stem"]["w"], 1, cdt)))
    for s in range(cfg["split_layers"]):
        x = stage(tp[f"stage{s}"], x, 1 if s == 0 else 2, cdt)
    return x


def server_logits(sp, h, cfg, cdt=None):
    for s in range(cfg["split_layers"], len(cfg["resnet_stages"])):
        h = stage(sp[f"stage{s}"], h, 1 if s == 0 else 2, cdt)
    pooled = jnp.mean(h, axis=(1, 2))
    return (jnp.dot(_q(pooled, cdt), _q(sp["head"]["w"], cdt),
                    precision=HIGHEST) + sp["head"]["b"])


def client_loss(tp, sp, batch, cfg, cdt=None):
    """Mean cross-entropy of one client's images [b,H,W,C] and labels [b]."""
    lg = server_logits(sp, tower(tp, batch["image"], cfg, cdt), cfg, cdt)
    gold = jnp.take_along_axis(lg, batch["label"][:, None], axis=-1)[:, 0]
    return jnp.mean(jax.nn.logsumexp(lg, axis=-1) - gold)


def loss_and_grads(params, batch, cfg, cdt=None):
    """Sum over clients of their mean losses, and its gradient."""
    def total(p):
        per = jax.vmap(lambda tp, b: client_loss(tp, p["server"], b, cfg, cdt))(
            p["towers"], batch)
        return jnp.sum(per)

    return jax.value_and_grad(total)(params)
