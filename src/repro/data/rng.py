"""Per-sample counter-based RNG for index-addressable data.

The data-layer contract (ROADMAP: FCCO per-sample u state, resume
bit-identity, the chaos battery) is that sample ``i``'s content is a
pure function of ``(dataset seed, i)`` — never of which other samples
share its batch, or of the order batches were drawn in.  Per-batch
``RandomState(seed + idx[0])`` seeding violates that (the bug this
module replaces): the same global index yielded different bytes under
different batch compositions.

The fix is counter-based (Philox) keying:

  * a 128-bit **key** identifies the random stream — derived from the
    dataset seed plus a stream label (``"contrastive/images"``, ...)
    via ``SeedSequence`` so distinct datasets/fields never share a
    stream (no process-salted ``hash()`` anywhere);
  * sample ``i`` draws from counter block ``[0, 0, 0, i]`` — numpy's
    Philox counter is little-endian (draws increment word 0), so each
    sample owns 2**192 draws before any overlap, and generating sample
    ``i`` is O(1) regardless of batch composition — the property the
    streaming pipeline's on-the-fly decode/augment leans on.

Both the in-memory synthetic datasets and the streaming pipeline's
augment stage call the same helpers here, which is what makes a
materialized-then-augmented stream bit-identical to the in-memory
oracle.

A batch's noise is written straight into one output array, one row per
sample.  A call whose rows each hold at least ``FAN_OUT_ROW`` normals
splits its rows into contiguous chunks on one process-wide thread pool
(numpy's fill releases the GIL, so the chunks run in parallel) and
scales each row and adds its base in place while the row is still in
cache; smaller calls stay on the caller's thread and scale and add once
over the whole batch.  The bytes do not depend on the split.
``fills()`` counts the calls of each kind.
"""
from __future__ import annotations

import concurrent.futures as cf
import dataclasses
import functools
import os
import threading
import zlib
from typing import Callable, Optional

import numpy as np

# A call fans out where each of its rows holds at least this many normals.
# On an 8-core CPU with 6 pool threads (median of 15 calls): rows of 4,096
# and 6,144 normals gained nothing even at 512 rows (0.97-1.10x; each
# row's generator set-up holds the GIL), rows of 8,192 gained 2.1x at 64
# rows and 3.3x at 512, rows of 150,528 (a 224x224x3 image) 4.6x at 32.
FAN_OUT_ROW = 8192


def stream_key(seed: int, stream: str) -> np.ndarray:
    """128-bit Philox key for the (dataset seed, stream label) pair.

    The label goes through crc32 (stable across processes, unlike
    ``hash``) into a ``SeedSequence`` so keys are well-mixed even for
    adjacent seeds."""
    ss = np.random.SeedSequence(
        [int(seed) & 0xFFFFFFFF, zlib.crc32(stream.encode("utf-8"))])
    return ss.generate_state(2, np.uint64)


def sample_generator(key, index: int) -> np.random.Generator:
    """The Generator owning global sample ``index``'s counter block."""
    return np.random.Generator(
        np.random.Philox(key=key, counter=[0, 0, 0, int(index)]))


@dataclasses.dataclass(frozen=True)
class NoiseFills:
    """Calls of the per-sample fill that split their rows over the shared
    pool (``parallel``) and that ran on the caller's thread (``serial``),
    and the pool's width."""
    serial: int = 0
    parallel: int = 0
    width: int = 1

    def since(self, start: "NoiseFills") -> "NoiseFills":
        return NoiseFills(self.serial - start.serial,
                          self.parallel - start.parallel, self.width)

    def __str__(self):
        return (f"noise: {self.parallel} of {self.serial + self.parallel} "
                f"fills on {self.width} threads")


@functools.cache
def pool_width() -> int:
    """Threads of the shared fill pool: the cores this process may run
    on, less two for the training loop and the runtime."""
    return max(1, len(os.sched_getaffinity(0)) - 2)


_lock = threading.Lock()
_counts = {"serial": 0, "parallel": 0}
_pool: Optional[cf.ThreadPoolExecutor] = None


def fills() -> NoiseFills:
    """The fill calls made in this process so far."""
    with _lock:
        return NoiseFills(width=pool_width(), **_counts)


def _shared_pool() -> cf.ThreadPoolExecutor:
    global _pool
    with _lock:
        if _pool is None:
            _pool = cf.ThreadPoolExecutor(max_workers=pool_width(),
                                          thread_name_prefix="noise")
        return _pool


def _fill(key, idx, out: np.ndarray,
          finish: Callable[[int, int], None]) -> np.ndarray:
    """Row j of ``out`` <- sample ``idx[j]``'s standard normals, then
    ``finish(lo, hi)`` over rows ``lo:hi`` of ``out``: once over the whole
    batch on the caller's thread, or row by row as each is drawn when the
    rows go to the shared pool in contiguous chunks.  Pool tasks only
    fill rows and never wait on other tasks, so callers that are
    themselves pool threads of another executor cannot deadlock it."""
    n, width = len(idx), pool_width()

    def draw(j):
        sample_generator(key, idx[j]).standard_normal(out=out[j:j + 1],
                                                      dtype=out.dtype)

    def chunk(lo, hi):
        for j in range(lo, hi):
            draw(j)
            finish(j, j + 1)

    parallel = n > 1 and width > 1 and out[0].size >= FAN_OUT_ROW
    with _lock:
        _counts["parallel" if parallel else "serial"] += 1
    if not parallel:
        for j in range(n):
            draw(j)
        finish(0, n)
        return out
    pool = _shared_pool()
    cuts = [n * k // min(width, n) for k in range(min(width, n) + 1)]
    futs = [pool.submit(chunk, lo, hi) for lo, hi in zip(cuts, cuts[1:])]
    cf.wait(futs)           # every chunk is done before out is returned
    for f in futs:
        f.result()
    return out


def per_sample_normal(key, idx, shape, dtype=np.float32) -> np.ndarray:
    """(len(idx), *shape) standard normals; row j is a pure function of
    (key, idx[j]) — independent of the rest of ``idx``."""
    idx = np.asarray(idx).reshape(-1)
    return _fill(key, idx, np.empty((len(idx),) + tuple(shape), dtype),
                 lambda lo, hi: None)


def noise_plus(scale: float, key, idx, shape,
               add_base: Callable[[int, int, np.ndarray], None]
               ) -> np.ndarray:
    """(len(idx), *shape) f32 rows ``base_j + scale * N(0, 1)``: the noise
    is drawn into the output and scaled in place, then ``add_base(lo, hi,
    block)`` adds samples ``lo:hi``'s base into ``block``, those rows of
    the output, in place.  The same bytes as ``base + np.float32(scale) *
    per_sample_normal(...)`` with no batch-sized temporary."""
    idx = np.asarray(idx).reshape(-1)
    scale = np.float32(scale)
    out = np.empty((len(idx),) + tuple(shape), np.float32)

    def finish(lo, hi):
        block = out[lo:hi]
        block *= scale
        add_base(lo, hi, block)
    return _fill(key, idx, out, finish)


def add_gaussian_noise(base, scale: float, key, idx) -> np.ndarray:
    """``base + scale * N(0, 1)`` with per-sample counter-based noise.

    The single augment primitive shared by the in-memory datasets and
    the streaming pipeline's decode stage: identical (base, scale, key,
    idx) means identical bytes, whichever side computes it.  ``base`` is
    f32, the type of the result."""
    base = np.asarray(base)
    if base.dtype != np.float32:
        raise TypeError(f"the noise's base must be float32, not {base.dtype}")
    return noise_plus(scale, key, idx, base.shape[1:],
                      lambda lo, hi, block: np.add(block, base[lo:hi],
                                                   out=block))
