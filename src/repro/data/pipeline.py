"""Sharded, epoch-shuffled, index-carrying data pipeline.

Each worker owns a contiguous shard of the dataset (samples
[k*n/K, (k+1)*n/K)), matching the sharding of the FCCO u buffers: a worker
only ever draws indices it owns, so u updates are shard-local (paper §3
"S is partitioned evenly across K workers").

``DevicePrefetcher`` wraps any step iterator with a double-buffered
producer thread that assembles host batches and issues the host->device
transfer ``depth`` steps ahead, so H2D copy (and the numpy batch gather)
overlaps the previous step's compute instead of serializing with it.
It writes the input path's ``repro.tracing`` spans and counts, in
``InputWaits``, how often and how long the consumer waited for a batch.
"""
from __future__ import annotations

import dataclasses
import functools
import queue
import threading
import time
from typing import Callable, Iterator, Optional, Tuple

import numpy as np

from repro import tracing as TR


@dataclasses.dataclass
class ShardedLoader:
    dataset: object            # .batch(idx) -> dict, .n
    global_batch: int
    n_shards: int = 1
    seed: int = 0
    drop_last: bool = True
    # Multi-process ownership (PR 10): when set, only these shard ids'
    # rows of each global batch are assembled on this host (``steps`` /
    # ``epoch`` batches hold len(owned_shards)*local_batch rows).  The
    # yielded ``idx`` stays GLOBAL — every process sees the same index
    # plan, and the launcher maps its local rows into the global batch
    # array via their shard positions.
    owned_shards: Optional[Tuple[int, ...]] = None

    def __post_init__(self):
        self.n = self.dataset.n
        assert self.n % self.n_shards == 0, "dataset must shard evenly"
        assert self.global_batch % self.n_shards == 0
        self.shard_size = self.n // self.n_shards
        self.local_batch = self.global_batch // self.n_shards
        if self.local_batch > self.shard_size:
            # steps_per_epoch == 0 used to make steps()/epoch() spin
            # forever (the epoch-skip branch never advanced the step
            # counter); refuse the shape up front instead
            raise ValueError(
                f"local batch {self.local_batch} (global_batch "
                f"{self.global_batch} / {self.n_shards} shards) exceeds "
                f"the per-shard sample count {self.shard_size} (n "
                f"{self.n} / {self.n_shards}): steps_per_epoch would be "
                "0 and the loader could never yield a full batch.  "
                "Lower --global-batch or raise --n-samples.")
        if self.owned_shards is not None:
            bad = [s for s in self.owned_shards
                   if not 0 <= s < self.n_shards]
            assert not bad, (
                f"owned_shards {bad} outside [0, {self.n_shards})")

    @property
    def steps_per_epoch(self) -> int:
        return self.shard_size // self.local_batch

    def _epoch_perms(self, epoch: int):
        # Per-(epoch, shard) permutation keys via SeedSequence spawn
        # keys — collision-free by construction.  (The pre-PR-7 scheme
        # `seed*100003 + epoch*31 + k` collided across (epoch, shard)
        # pairs, e.g. (0, 31) vs (1, 0) drew identical permutations.
        # Compatibility note: this change re-keys every epoch shuffle,
        # so batch order differs from checkpoints recorded before it —
        # resume a pre-change run with the pre-change code.)
        per_shard = []
        for k in range(self.n_shards):
            ss = np.random.SeedSequence(self.seed, spawn_key=(epoch, k))
            rng = np.random.Generator(np.random.PCG64(ss))
            lo = k * self.shard_size
            per_shard.append(lo + rng.permutation(self.shard_size))
        return per_shard

    def _step_idx(self, per_shard, step: int) -> np.ndarray:
        return np.concatenate([
            p[step * self.local_batch:(step + 1) * self.local_batch]
            for p in per_shard])

    def _owned_rows(self, idx: np.ndarray) -> np.ndarray:
        """The rows of a global index batch this host assembles: shard s
        owns rows [s*local_batch, (s+1)*local_batch) of the
        shard-concatenated global batch (all rows when ``owned_shards``
        is unset)."""
        if self.owned_shards is None:
            return idx
        L = self.local_batch
        idx = np.asarray(idx)
        return np.concatenate([idx[s * L:(s + 1) * L]
                               for s in self.owned_shards])

    def epoch(self, epoch: int) -> Iterator[Tuple[np.ndarray, dict]]:
        """Yields (global_indices (global_batch,), batch dict) with the
        per-shard sub-batches concatenated in shard order, so that
        reshaping to (K, local_batch) matches the mesh data axis."""
        per_shard = self._epoch_perms(epoch)
        for step in range(self.steps_per_epoch):
            idx = self._step_idx(per_shard, step)
            yield idx, self.dataset.batch(self._owned_rows(idx))

    def _index_steps(self, n_steps: int, start: int = 0):
        """The index-only step plan: yields (epoch, step, idx) for steps
        [``start``, ``n_steps``) without ever touching the dataset.
        Shared by ``steps`` (which assembles batches eagerly) and the
        streaming loader (which pipelines decode over it)."""
        step = 0
        epoch = 0
        while step < n_steps:
            if step + self.steps_per_epoch <= start:
                step += self.steps_per_epoch
                epoch += 1
                continue
            per_shard = self._epoch_perms(epoch)
            for e_step in range(self.steps_per_epoch):
                if step >= n_steps:
                    return
                if step >= start:
                    yield epoch, step, self._step_idx(per_shard, e_step)
                step += 1
            epoch += 1

    def steps(self, n_steps: int, start: int = 0):
        """Infinite-ish stream over epochs, yielding (epoch, step, idx,
        batch) for steps [``start``, ``n_steps``).

        ``start`` is the resume fast-forward: the stream is positionally
        identical to filtering a full ``steps(n_steps)`` run on
        ``step >= start``, but skipped steps are *index-only* — whole
        epochs before the resume point advance counters without drawing
        a permutation, and skipped steps inside the resume epoch neither
        slice indices nor assemble a host batch (``dataset.batch``) —
        so resuming at step S costs O(1) per skipped step instead of S
        full global-batch gathers."""
        for epoch, step, idx in self._index_steps(n_steps, start):
            yield epoch, step, idx, self.dataset.batch(self._owned_rows(idx))


# ---------------------------------------------------------------------------
# Host->device prefetch
# ---------------------------------------------------------------------------

_STOP = object()


def unflatten(flat, shape):
    """``flat.reshape(shape)`` in jnp ops for the device.  An image batch
    ``(b, h, w, c)`` goes through ``(b, h, w * c)`` and ``(h, w * c, b)``:
    a TPU lays the batch out with its batch dimension minor, and a direct
    reshape first lays out a ``(b, h, w, c)`` intermediate with ``c``
    minor, padded to a whole tile (42x the batch's bytes at c = 3, more
    than a v5e has beside a model)."""
    import jax.numpy as jnp
    if len(shape) != 4:
        return flat.reshape(shape)
    b, h, w, c = shape
    x = flat.reshape(b, h, w * c).transpose(1, 2, 0)
    return jnp.moveaxis(x.reshape(h, w, c, b), -1, 0)


@functools.cache
def _unflatten_jit():
    import jax
    return jax.jit(unflatten, static_argnums=1)


def device_array(x):
    """``jnp.asarray(x)`` for a host array, copied flat and reshaped on
    the device by ``unflatten``.

    A copy in the array's own shape is transposed on the host into the
    device's layout by the runtime's threads: for an image batch, with
    the batch dimension minor.  A flat array's device layout is its host
    order, so its copy is a plain one, and the device does the relayout."""
    import jax.numpy as jnp
    x = np.asarray(x)
    return _unflatten_jit()(jnp.asarray(x.reshape(-1)), x.shape)


@dataclasses.dataclass
class InputWaits:
    """What the consumer of a ``DevicePrefetcher`` waited for: batches
    asked for, asks that found the queue empty, and seconds spent
    blocked.  Empty at most asks means the run is input-bound."""
    asks: int = 0
    empty: int = 0
    waited_s: float = 0.0

    def __str__(self):
        return (f"input: queue empty at {self.empty} of {self.asks} asks, "
                f"waited {self.waited_s:.1f} s")


class DevicePrefetcher:
    """Double-buffered host->device prefetch over any finite iterator.

    A daemon producer thread pulls items, applies ``transform`` (e.g.
    numpy -> ``jnp.asarray``, which dispatches the async H2D copy), and
    parks up to ``depth`` transformed items in a bounded queue.  The
    consumer therefore always finds the next batch already (being)
    transferred: with ``depth=2`` the copy of step t+1 runs while step t
    computes.  Producer exceptions are re-raised on the consumer side at
    the position they occurred.  Iteration order is exactly the wrapped
    iterator's.

    The producer's ``next`` runs in a ``repro.input.make`` span and its
    ``transform`` in ``repro.input.copy``; the consumer's wait runs in
    ``repro.input.wait`` and adds to ``waits`` (pass one ``InputWaits`` to
    several prefetchers to count them together)."""

    def __init__(self, iterator: Iterator, depth: int = 2,
                 transform: Optional[Callable] = None,
                 waits: Optional[InputWaits] = None):
        assert depth >= 1
        self._q: "queue.Queue" = queue.Queue(maxsize=depth)
        self._transform = transform
        self.waits = waits if waits is not None else InputWaits()
        self._stop = threading.Event()   # set by close(): unblocks producer
        self._done = False               # latched on _STOP: repeated next()
        #                                  keeps raising StopIteration

        def put(item) -> bool:
            """Bounded put that aborts when close() is called (otherwise an
            abandoned consumer would pin depth device batches forever)."""
            while not self._stop.is_set():
                try:
                    self._q.put(item, timeout=0.1)
                    return True
                except queue.Full:
                    continue
            return False

        def produce():
            try:
                items = iter(iterator)
                while True:
                    with TR.span(TR.INPUT_MAKE):
                        item = next(items, _STOP)
                    if item is _STOP:
                        break
                    if self._transform:
                        with TR.span(TR.INPUT_COPY):
                            item = self._transform(item)
                    if not put(item):
                        return
            except BaseException as e:  # surfaced on the consumer thread
                if not put(e):
                    return
            put(_STOP)  # always terminate: next() after an exception
            #             raises StopIteration instead of hanging

        self._thread = threading.Thread(target=produce, daemon=True)
        self._thread.start()

    def close(self):
        """Release the producer after early loop exit; drops queued items."""
        self._stop.set()
        self._done = True
        while True:          # drain so a mid-put producer can finish
            try:
                self._q.get_nowait()
            except queue.Empty:
                break

    def __iter__(self):
        return self

    def __next__(self):
        if self._done:
            raise StopIteration
        empty = self._q.empty()
        t = time.perf_counter()
        with TR.span(TR.INPUT_WAIT):
            item = self._q.get()
        waited = time.perf_counter() - t
        if item is _STOP:
            self._done = True
            raise StopIteration
        if isinstance(item, BaseException):
            raise item
        self.waits.asks += 1
        self.waits.empty += empty
        self.waits.waited_s += waited
        return item
