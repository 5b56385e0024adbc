"""Faults planted under the timed path, to show that `correct` catches them.

Each is a context manager that patches the program while it is active:

  unchanged_state  the round runs but hands back the state it was given
  half_batch       the round sees only the first half of each client's
                   rows, so its mean is taken over the rest

`half_batch_inputs` plants the second fault in the reference instead, for
reading it at the cell's size without a program run.
"""
from __future__ import annotations

import contextlib


@contextlib.contextmanager
def _round_fn_wrapped(wrap):
    import repro.train.loop as loop

    real = loop.shard_round_fn
    loop.shard_round_fn = lambda *a, **kw: wrap(real(*a, **kw))
    try:
        yield
    finally:
        loop.shard_round_fn = real


def unchanged_state():
    def wrap(fn):
        def round_fn(state, batch, schedule=None):
            _, metrics = fn(state, batch, schedule)
            return state, metrics
        return round_fn
    return _round_fn_wrapped(wrap)


def _half(batch):
    import jax

    return jax.tree.map(lambda x: x[:, :x.shape[1] // 2], batch)


def half_batch():
    def wrap(fn):
        return lambda state, batch, schedule=None: fn(state, _half(batch),
                                                      schedule)
    return _round_fn_wrapped(wrap)


def half_batch_inputs(inputs):
    return [_half(b) for b in inputs]

