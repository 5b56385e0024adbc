"""Weights made by the benchmark from the seed, in the program's tree layout.

The program runs and the reference checks the same weights, and neither
makes them. Each family's plug-in (`families/<family>.py`) gives a
`make_params(key, cfg, M)` that builds every leaf in float32 from keys
split from one key, with the published initialisation of its architecture;
`make_params` here runs it as one jitted call on the device from the seed.

`unit_axes` says how many leading axes of each leaf index separate units
(client, then layer) for the per-unit norms that `check.py` compares. It
reads them from the program's own parameter template (`template`), whose
leaves carry their logical axis names.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

F32 = jnp.float32

# logical axes of the program's parameters that index units: a tower
# leaf's client axis (core/split.py) and a stacked segment's layer axis
# (models/stacks.py); a family may name more (its UNIT_AXES)
UNIT_AXES = ("client", "layers")


def seed_key(seed: int):
    """A key for any whole-number seed (PRNGKey alone keeps 32 bits)."""
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                              (seed >> 32) & 0xFFFFFFFF)


def normal(key, shape, std):
    return jax.random.normal(key, shape, F32) * std


def make_params(family, seed, cfg, M, shardings=None):
    """Every weight of the cell from the seed; `family` is the cell's
    plug-in module. On the default device, or made in place where
    `shardings` (a prefix of the weights' tree) says."""
    def fn(key):
        return family.make_params(key, cfg, M)

    jitted = (jax.jit(fn) if shardings is None
              else jax.jit(fn, out_shardings=shardings))
    return jitted(seed_key(seed))


def template(model, M):
    """The program's MTSL parameters for M clients as shapes, each leaf
    wrapped with its logical axes (`repro.utils.sharding.Annotated`);
    nothing is computed."""
    from repro.core.mtsl import init_state
    from repro.nn import abstract_params

    with abstract_params():
        return init_state(model, None, jax.random.PRNGKey(0), M)


def unit_axes(tmpl, extra=()):
    """Per leaf of a `template`, its number of leading axes named in
    UNIT_AXES or `extra`: exactly the leaves that have a client or layer
    axis count it, whatever their path."""
    from repro.utils.sharding import Annotated

    names = set(UNIT_AXES) | set(extra)

    def count(a):
        n = 0
        while n < len(a.axes) and a.axes[n] in names:
            n += 1
        return n

    return jax.tree.map(count, tmpl,
                        is_leaf=lambda x: isinstance(x, Annotated))
