"""Batch pipeline: host-side generation -> device placement (+ sharding).

`client_batches` yields training batches with the [M, b, ...] client-leading
layout the MTSL step expects. On a mesh, pass `sharding` to place the client
axis onto ("pod","data") without a host-side gather.

With `as_numpy=True` the generator stays entirely host-side (numpy arrays,
no device transfer) — that is what the async round pipeline
(train/pipeline.py) wants: batch synthesis runs on a background thread and
the consumer stages the arrays with `jax.device_put` one round before they
are needed. Values are identical either way.

The source can be a synthesis source (`MultiTaskImageSource` /
`MultiTaskLMSource`) or any `ShardableDataset` (data/shards.py): with a
dataset, each round is a deterministic mmap'd shard READ keyed on
`(seed, round)` — the background thread stops synthesizing and the data
path stays off the critical path at massive M. Cached rounds are random
access, so `start_round` lets a resumed run seek mid-stream instead of
replaying and discarding consumed rounds.
"""
from __future__ import annotations

from typing import Iterator, Optional

import jax
import jax.numpy as jnp
import numpy as np


def client_batches(
    source,
    batch_per_client: int,
    *,
    seq_len: Optional[int] = None,
    steps: Optional[int] = None,
    seed: int = 0,
    sharding=None,
    as_numpy: bool = False,
    vectorized: bool = False,
    start_round: int = 0,
) -> Iterator[dict]:
    """Yield batches from a source or a ShardableDataset (data/shards.py).

    `vectorized=True` draws each round's batch with the sources' batched
    across-clients RNG paths — same distribution from a different seeded
    stream, host cost per client flat in M (massive-M runs; the default
    per-client loop's draw order is pinned by the parity goldens). It has
    no effect on datasets (their reads are already flat per client)."""

    def _emit(batch):
        if not as_numpy:
            batch = {k: jnp.asarray(v) for k, v in batch.items()}
        if sharding is not None:
            batch = jax.tree.map(lambda a: jax.device_put(a, sharding), batch)
        return batch

    def _draw(i):
        # the span closes before the yield: it excludes the time the
        # consumer holds the generator suspended
        return jax.profiler.TraceAnnotation("repro.draw",
                                            round=start_round + i + 1)

    if hasattr(source, "round_batch"):  # ShardableDataset: cached reads
        kwargs = {"seq_len": seq_len} if source.kind == "lm" else {}
        i = 0
        while steps is None or i < steps:
            with _draw(i):
                batch = source.round_batch(seed, start_round + i,
                                           batch_per_client, **kwargs)
            yield _emit(batch)
            i += 1
        return
    if start_round:
        raise ValueError(
            "start_round requires a ShardableDataset source: synthesis "
            "sources are sequential streams — replay them and slice off "
            "the consumed rounds instead")
    rng = np.random.default_rng(seed)
    i = 0
    is_lm = hasattr(source, "chains")
    while steps is None or i < steps:
        with _draw(i):
            if is_lm:
                toks = source.all_clients_batch(rng, batch_per_client,
                                                seq_len, vectorized=vectorized)
                batch = {"tokens": np.asarray(toks, np.int32)}
            else:
                x, y = source.all_tasks_batch(rng, batch_per_client,
                                              vectorized=vectorized)
                batch = {"image": np.asarray(x),
                         "label": np.asarray(y, np.int32)}
        yield _emit(batch)
        i += 1
