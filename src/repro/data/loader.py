"""Prefetching host→device loader over the precomputed batch cache.

The paper fully pipelines data loading by prefetching the next batch in
parallel (Sec. 5) and observes that ONE worker suffices because loading is
memory-bandwidth-bound. We reproduce exactly that: one background thread
stages batch t+1 onto the device while step t computes — with IBMB's
contiguous cache a stage is a single sequential read + DMA.

Out-of-core plans stream through the SAME loader (DESIGN.md §13): a Plan
backed by ``repro.ooc.LazyBatchCache`` stages each batch (and, via the
cache's ``stack`` hook, each super-step) through the checksum-verified
lazy read with a bounded resident-batch budget, so one worker prefetching
batch/super-step t+1 from disk while step t computes holds O(budget) batch
payload — the paper's pipelining argument, applied to graphs bigger than
RAM.

Each stage runs under an ``ibmb/loader/stage`` profiler span (args: the
position in the order and the bytes staged) and each wait for a staged
batch under ``ibmb/loader/wait``. The spans cost nothing measurable while
no profiler trace is being taken.

Shutdown is sentinel/Event based: a consumer that abandons the iterator
early (break, exception, GC) triggers the generator's ``finally``, which
sets the cancel event; the worker only ever blocks on ``q.put`` with a
timeout and re-checks the event, so it can never be left stranded on a
full queue and the thread always joins.
"""
from __future__ import annotations

import queue
import threading
from typing import Dict, Iterator, Optional, Sequence

import jax
import numpy as np

from repro.faults import NO_FAULTS

_STOP = object()


def device_put_batch(batch: Dict[str, np.ndarray], device=None):
    return {k: jax.device_put(v, device) for k, v in batch.items()}


def batch_nbytes(batch: Dict[str, np.ndarray]) -> int:
    return sum(int(v.nbytes) for v in batch.values())


class PrefetchLoader:
    """Iterate device-resident batches in `order`, prefetch depth 1 (paper:
    more workers don't help — memory bandwidth is shared).

    `batches` is anything indexable that yields device-array dicts: a raw
    list, a `BatchCache`, or a `Plan` (DESIGN.md §8) — a Plan is staged
    straight from its contiguous cache and, when no explicit `order` is
    given, iterated in the plan's precomputed schedule order.

    `group` switches to super-step staging (DESIGN.md §9): the loader
    yields `(stacked_batch, weights)` pairs of `group` batches each —
    every field gains a leading axis of length `group`, the ragged tail
    repeats the last real batch with weight 0 — and `device` may be a
    `jax.sharding.Sharding` (e.g. the executor's data-axis sharding), so
    the stack + sharded device_put of super-step t+1 overlaps with the
    shard_map compute of super-step t."""

    def __init__(self, batches,
                 order: Optional[np.ndarray] = None, device=None,
                 prefetch: int = 1, group: Optional[int] = None,
                 faults=NO_FAULTS):
        plan_schedule = getattr(batches, "schedule", None)
        cache = getattr(batches, "cache", None)
        if cache is not None:                    # Plan → its contiguous cache
            batches = cache
        if order is None:
            order = np.asarray(plan_schedule) if plan_schedule is not None \
                else np.arange(len(batches))
        order = np.asarray(order)
        # Fail in the caller, not the worker thread: a schedule carried over
        # from a DIFFERENT plan version can reference batches this container
        # no longer holds (refreshed plans may shrink, DESIGN.md §10), and
        # an IndexError raised mid-prefetch surfaces as a cryptic re-raise.
        if len(order) and (int(order.min()) < 0
                           or int(order.max()) >= len(batches)):
            raise IndexError(
                f"order references batch {int(order.max())} but the "
                f"container holds {len(batches)} batches — is this schedule "
                f"from a different (e.g. pre-refresh) plan version?")
        self.batches = batches
        self.order = order
        self.device = device
        self.prefetch = max(1, prefetch)
        self.group = group
        self.faults = faults            # "loader" injection point (§12)
        self.failed: Optional[BaseException] = None   # last worker error
        self._worker: Optional[threading.Thread] = None  # most recent; tests

    def __len__(self) -> int:
        if self.group:
            return -(-len(self.order) // self.group)     # super-steps
        return len(self.order)

    def _items(self):
        """What the worker stages: per-batch dicts, or (stacked, weights)
        super-steps when `group` is set."""
        if not self.group:
            for i in self.order:
                self.faults.fire("loader")
                yield self.batches[int(i)]
            return
        from repro.dist.data_parallel import stack_batches, superstep_indices
        for idx, w in superstep_indices(self.order, self.group):
            self.faults.fire("loader")
            yield stack_batches(self.batches, idx), w

    def __iter__(self) -> Iterator:
        q: "queue.Queue" = queue.Queue(maxsize=self.prefetch)
        cancel = threading.Event()

        def put(item) -> bool:
            """Blocking put that aborts when the consumer cancels."""
            while not cancel.is_set():
                try:
                    q.put(item, timeout=0.05)
                    return True
                except queue.Full:
                    continue
            return False

        def worker():
            try:
                for k, item in enumerate(self._items()):
                    if cancel.is_set():
                        return
                    fields = item[0] if isinstance(item, tuple) else item
                    nbytes = batch_nbytes(fields)
                    with jax.profiler.TraceAnnotation(
                            "ibmb/loader/stage", batch=k, bytes=nbytes):
                        staged = device_put_batch(fields, self.device)
                    if isinstance(item, tuple):          # (stacked, weights)
                        item = (staged, item[1])
                    else:
                        item = staged
                    if not put(item):
                        return
                put(_STOP)
            except BaseException as e:   # surface in the consumer, never hang
                self.failed = e          # observable even if consumer is gone
                put(e)

        t = threading.Thread(target=worker, daemon=True)
        self._worker = t
        t.start()
        try:
            while True:
                with jax.profiler.TraceAnnotation("ibmb/loader/wait"):
                    item = q.get()
                if item is _STOP:
                    break
                if isinstance(item, BaseException):
                    raise item
                yield item
        finally:
            # reached on exhaustion AND on early exit (GeneratorExit)
            cancel.set()
            t.join(timeout=10.0)
