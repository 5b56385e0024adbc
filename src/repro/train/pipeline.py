"""Async round pipeline: schedule/batch prefetch + non-blocking metrics.

The synchronous loop wastes host/device overlap three ways every round:
the host (1) draws the round's ClientSchedule, (2) generates + transfers
the round batch, and (3) materializes metrics (`np.asarray` forces a
device sync) — all while the device sits idle, exactly the straggler-
shaped waste the schedule subsystem simulates for clients. This module is
the host-side fix, in three small pieces that compose with ANY algorithm
in the registry (the round math is untouched, so pipelined runs are
trajectory-identical to synchronous ones — pinned by
tests/test_pipeline.py):

  BackgroundIterator   run an iterator on a daemon thread with a bounded
                       queue: round-batch production (numpy RNG synthesis
                       in data/pipeline.client_batches — or, with a
                       cached ShardableDataset from data/shards.py, cheap
                       mmap'd shard READS, which is what keeps this
                       thread off the critical path at massive M) and the
                       seeded schedule draw for round i+1..i+depth happen
                       WHILE the device runs round i. Exceptions
                       propagate to the consumer at the matching
                       position; close() tears the thread down.
  pipeline_rounds      zip a batch iterator with a schedule iterator,
                       prefetch `depth` pairs ahead on the background
                       thread, and STAGE each pair onto the device
                       (`jax.device_put`) one round before it is consumed
                       — the classic double-buffered host->device
                       transfer. depth=0 degrades to a plain synchronous
                       zip (same values, same order).
  MetricsRing          a bounded ring of in-flight device metric payloads.
                       The loop pushes raw device values at its log/eval
                       cadence and the ring defers `np.asarray`
                       materialization until the ring overflows or is
                       flushed — the host never forces a mid-run sync, it
                       only reads back values the device has (usually)
                       already finished. depth=0 materializes immediately
                       (synchronous behavior).

Opting out: `TrainConfig.prefetch = 0` (or `--prefetch 0` on the
launcher) runs the loop fully synchronously. See train/loop.py for how
the loop wires these together.

The event-driven async engine (train/events.py, `--async`) consumes the
same `pipeline_rounds` stream: one cohort DISPATCH pulls one
(batch, schedule) pair, so the background thread keeps generation ahead
of the engine's dispatch demand exactly as it does for barrier rounds.
"""
from __future__ import annotations

import itertools
import queue
import threading
from typing import Any, Callable, Iterable, Iterator, Optional

import jax
import numpy as np


class BackgroundIterator:
    """Iterate `source` on a daemon thread, `depth` items ahead.

    The producer thread owns ALL host-side work of the source iterator
    (batch synthesis, schedule draws); the consumer just dequeues. An
    exception raised by the source is re-raised at the consumer's matching
    `next()` call, preserving item order. `close()` (also called on
    garbage collection and at stream end) stops the producer; it is safe
    to call more than once.
    """

    _ITEM, _DONE, _ERROR = "item", "done", "error"

    def __init__(self, source: Iterable, depth: int = 2):
        self._q: queue.Queue = queue.Queue(maxsize=max(int(depth), 1))
        self._stop = threading.Event()
        self._thread = threading.Thread(
            target=self._produce, args=(iter(source),), daemon=True)
        self._thread.start()

    def _produce(self, it: Iterator) -> None:
        try:
            for item in it:
                if not self._put((self._ITEM, item)):
                    return
            self._put((self._DONE, None))
        except BaseException as e:  # noqa: BLE001 — relayed to the consumer
            self._put((self._ERROR, e))

    def _put(self, entry) -> bool:
        while not self._stop.is_set():
            try:
                self._q.put(entry, timeout=0.1)
                return True
            except queue.Full:
                continue
        return False

    def queued(self) -> int:
        """Items the producer has ready that the consumer has not taken."""
        return self._q.qsize()

    def __iter__(self) -> "BackgroundIterator":
        return self

    def __next__(self):
        if self._stop.is_set():
            raise StopIteration
        kind, payload = self._q.get()
        if kind is self._ITEM:
            return payload
        self.close()
        if kind is self._ERROR:
            raise payload
        raise StopIteration

    def close(self) -> None:
        self._stop.set()
        # drain so a blocked producer can observe the stop flag
        try:
            while True:
                self._q.get_nowait()
        except queue.Empty:
            pass

    def __del__(self):  # pragma: no cover — best-effort cleanup
        try:
            self.close()
        except Exception:
            pass


def _stage(item: Any, device=None) -> Any:
    """Start the host->device transfer for every array in `item`.

    `jax.device_put` dispatches asynchronously on accelerator backends, so
    staging round i+1 while round i runs overlaps the transfer with
    compute. Values are unchanged (numpy arrays land on device; arrays
    already on the right device are a no-op), so staging cannot change the
    trajectory."""
    if device is None:
        return jax.device_put(item)
    return jax.device_put(item, device)


def pipeline_rounds(
    batches: Iterable,
    schedules: Iterable,
    depth: int = 2,
    num_rounds: Optional[int] = None,
    device=None,
) -> "RoundStream":
    """Yield `(batch, schedule)` pairs with host work running ahead.

    depth=0: a plain synchronous `zip` (staged inline) — the opt-out path.
    depth>0: a BackgroundIterator generates pairs up to `depth` rounds
    ahead while the consumer-side deque keeps ONE pair staged on device
    (double buffering): when pair i is yielded, pair i+1's transfer has
    already been dispatched.

    The yielded values are identical to `zip(batches, schedules)` in value
    and order for any depth — only WHEN the host-side work happens changes.
    """
    pairs: Iterable = zip(batches, schedules)
    if num_rounds is not None:
        pairs = itertools.islice(pairs, num_rounds)
    return RoundStream(pairs, depth, device)


class RoundStream:
    """The iterator `pipeline_rounds` returns. `queued()` is the number of
    pairs its producer thread has ready (0 when synchronous); `close()`
    stops the thread."""

    def __init__(self, pairs: Iterable, depth: int, device=None):
        # the generator fills in its BackgroundIterator when it starts; a
        # box rather than self keeps the generator out of a cycle with us
        self._bg: list = [None]
        self._gen = _staged_pairs(pairs, depth, device, self._bg)

    def __iter__(self) -> "RoundStream":
        return self

    def __next__(self):
        return next(self._gen)

    def queued(self) -> int:
        bg = self._bg[0]
        return 0 if bg is None else bg.queued()

    def close(self) -> None:
        self._gen.close()


def _staged_pairs(pairs: Iterable, depth: int, device, box: list):
    if depth <= 0:
        for batch, sched in pairs:
            yield _stage(batch, device), sched
        return
    bg = box[0] = BackgroundIterator(pairs, depth=depth)
    try:
        staged = None
        for pair in bg:
            nxt = (_stage(pair[0], device), pair[1])
            if staged is not None:
                yield staged
            staged = nxt
        if staged is not None:
            yield staged
    finally:
        bg.close()


class MetricsRing:
    """Bounded ring of in-flight device metric payloads.

    `push(payload)` enqueues a dict whose leaves may be live device arrays;
    nothing is materialized until the ring exceeds `depth` entries (then
    the OLDEST is forced) or `flush()` drains everything at end of run —
    so with depth k the host stays up to k logged rounds ahead of the
    device instead of syncing on every `float(loss)`. Materialized entries
    are handed to `sink` in push order: pipelining never reorders history.

    depth=0 materializes on every push — the synchronous opt-out.
    """

    def __init__(self, depth: int,
                 sink: Callable[[dict], None]):
        self._depth = max(int(depth), 0)
        self._sink = sink
        self._ring: list = []

    @staticmethod
    def materialize(payload: dict) -> dict:
        """np.asarray every array leaf (scalars unwrap to python floats)."""
        out = {}
        for k, v in payload.items():
            if isinstance(v, dict):
                out[k] = MetricsRing.materialize(v)
            elif isinstance(v, (jax.Array, np.ndarray)):
                a = np.asarray(v)
                out[k] = float(a) if a.ndim == 0 else a
            else:
                out[k] = v
        return out

    def push(self, payload: dict) -> None:
        self._ring.append(payload)
        while len(self._ring) > self._depth:
            self._sink(self.materialize(self._ring.pop(0)))

    def flush(self) -> None:
        while self._ring:
            self._sink(self.materialize(self._ring.pop(0)))

    def __len__(self) -> int:
        return len(self._ring)
