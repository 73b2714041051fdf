"""The launcher's own wait counts (``DevicePrefetcher.waits``) against what
the launcher driver's queue observer records over the same stream."""
from __future__ import annotations

import threading
import time

from chipbench.drivers import launcher


def test_prefetcher_counts_match_the_queue_observer():
    from repro.data import DevicePrefetcher
    gate = threading.Semaphore(0)

    def gated():
        for i in range(6):
            gate.acquire()
            yield i

    observed, gets = launcher._queue_observer(DevicePrefetcher)
    pf = observed(gated(), depth=2)
    got = []
    for ahead in (0, 2, 0, 1, 0):
        if ahead:                         # batches queued before the ask
            for _ in range(ahead):
                gate.release()
            while pf._q.qsize() < ahead:
                time.sleep(0.01)
        else:                             # the ask finds the queue empty
            threading.Timer(0.05, gate.release).start()
        got += [next(pf) for _ in range(max(ahead, 1))]
    assert got == list(range(6))
    assert [q for q, _ in gets] == [0, 2, 1, 0, 1, 0]
    assert pf.waits.asks == len(gets)
    assert pf.waits.empty == sum(q == 0 for q, _ in gets) == 3
    # the program's wait lies inside the observer's
    assert 0 < pf.waits.waited_s <= sum(w for _, w in gets)
