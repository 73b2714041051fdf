"""``repro.launch.train.main`` in-process on the launcher's own synthetic
input path, as users start it.

Its first ``check_steps`` steps are observed through the jit it builds the
step with.  The window opens at the log line of the first step after those
whose batch, and the one before, the loop had to wait for (the prefetch
queue was empty when it asked): from then on the input path, not batches
queued while the step compiled, sets the pace.  Where the queue never
empties, the window opens ``drain_steps_max`` steps after the checked ones.
It closes by the launcher's own SIGTERM drain and counts every step logged
inside it.

Mix parameters: ``prefetch`` (the launcher's queue depth), ``steps`` (its
schedule horizon, more than a window reaches), ``drain_steps_max``,
``trace_seconds`` (with ``--trace 1``, the profiler records the window's
last so many seconds), and ``data``, which describes the synthetic dataset
the launcher builds (the reference rebuilds the first batches from it).
One chip: no mesh.
"""
from __future__ import annotations

import statistics
import time
from typing import Optional

import numpy as np

from chipbench import compare, harness, program as P, trace as TR


class _Tee:
    """A stdout for the launcher: its lines go on to ``out``, and each
    complete line is kept with its arrival time."""

    def __init__(self, out):
        self.out, self.lines, self._buf = out, [], ""

    def write(self, s):
        self.out.write(s)
        self._buf += s
        *done, self._buf = self._buf.split("\n")
        now = time.perf_counter()
        self.lines += [(now, line) for line in done]
        return len(s)

    def flush(self):
        self.out.flush()

    def steps(self):
        """(arrival time, step, metrics) of each ``step N epoch E {..}``
        line so far."""
        import json
        out = []
        for t, line in list(self.lines):
            parts = line.split(maxsplit=4)
            if len(parts) == 5 and parts[0] == "step" and parts[2] == "epoch":
                out.append((t, int(parts[1]), json.loads(parts[4])))
        return out


def _queue_observer(base):
    """A stand-in for the launcher's ``DevicePrefetcher``: the same queue,
    which also records, for each batch the loop asks for, the batches
    already queued and the seconds the loop waited."""
    gets = []

    class Observed(base):
        def __next__(self):
            queued, t = self._q.qsize(), time.perf_counter()
            item = super().__next__()
            gets.append((queued, time.perf_counter() - t))
            return item
    return Observed, gets


class _Observer:
    """Stands in for the launcher's ``donated_jit``: the step it returns is
    the program's own (or the variant's wrapper of it), and on its first
    ``n`` calls it also keeps the program's readings and the batches it was
    fed."""

    def __init__(self, c: dict, real_jit, variant):
        self.c, self.real_jit, self.variant, self.calls = c, real_jit, \
            variant, 0
        self.got = {"loss": []}
        self.fed = []
        self.jitted = self.args = None

    def __call__(self, fn, *a, **kw):
        import jax
        import jax.numpy as jnp
        from jax.profiler import TraceAnnotation as span
        jitted = self.real_jit(fn, *a, **kw)
        self.jitted = self.jitted or jitted
        step = P.timed(jitted, self.variant, self.c["global_batch"])
        n, beta1 = self.c["check_steps"], self.c["hyper"]["adam_beta1"]

        def run(state, batch, idx):
            k = self.calls
            self.calls += 1
            if k >= n:
                with span("chipbench.step_dispatch"):
                    return step(state, batch, idx)
            if k == 0:
                self.args = P.abstract((state, batch, idx))
                self.p0 = jax.tree.map(jnp.copy, state["params"])
            self.fed.append(jax.device_get((batch, idx)))
            with span("chipbench.check_step"):
                state, m = step(state, batch, idx)
            self.got["loss"].append(float(m["loss_value"]))
            if k == 0:
                self.got["grad"] = P.leaf_norms(state["opt"]["m"],
                                                scale=1 / (1 - beta1))
            if k == n - 1:
                self.got["update"] = P.leaf_norms(state["params"], self.p0)
                del self.p0
            return state, m
        return run


def reference_batches(c: dict, mix: dict, seed: int):
    """The (batch, indices) pairs of the first ``check_steps`` steps as the
    reference rebuilds them from the seed and the mix's ``data``."""
    from chipbench.reference import index_plan, synthetic_pairs
    B, n = c["global_batch"], c["n_samples"]
    out = []
    for k in range(c["check_steps"]):
        idx = index_plan(n, B, seed, k)
        out.append((synthetic_pairs(mix["data"], c, n, idx), idx))
    return out


def _batch_gap(fed, want) -> float:
    """Largest difference between what the data layer fed the step and the
    reference's batch for the same step: indices, pixels and tokens."""
    gap = 0.0
    for (batch, idx), (ref, ref_idx) in zip(fed, want, strict=True):
        gap = max(gap, float(np.max(np.abs(np.asarray(idx) - ref_idx))),
                  float(np.max(np.abs(batch["images"] - ref["images"]))),
                  float(np.max(np.abs(batch["texts"] - ref["texts"]))))
    return gap


def opens_at(gets, n_logged: int, first: int, latest: int) -> Optional[int]:
    """The step whose log line opens the window, once ``n_logged`` steps
    are logged: the first ``k >= first`` whose batch and the one before
    were waited for (an empty queue), or ``latest``; None while not yet
    known."""
    for k in range(max(first, 1), min(n_logged, len(gets), latest)):
        if gets[k - 1][0] == 0 and gets[k][0] == 0:
            return k
    return latest if n_logged > latest else None


def run(c: dict, mix: dict, *, seed: int, seconds: float, trace: bool,
        t_start: float, devs, variant: Optional[P.Variant] = None):
    import contextlib
    import os
    import signal
    import sys
    import threading
    import jax
    import jax.numpy as jnp
    from jax.profiler import TraceAnnotation as span
    from repro.configs import get_arch
    from repro.data import ShardedLoader
    from repro.launch import train as LT
    from chipbench import flops

    if len(devs) != 1:
        raise harness.Refused("the launcher mix runs on one chip; a cell on "
                              "more needs a mix that states its mesh")
    B, n = c["global_batch"], c["n_samples"]
    if P.program_arch(c) != get_arch(c["program_arch"]):
        raise harness.Refused("the launcher runs only its registered "
                              "architectures at their own widths")
    argv = (P.program_argv(c, mix, seed)
            + ["--log-every", "1", "--prefetch", str(mix["prefetch"])]
            + list(variant.argv if variant else ()))
    P.check_stated(LT.train_step_config(LT.build_parser().parse_args(argv),
                                        P.program_arch(c), n // B, False),
                   c, variant)
    tee = _Tee(sys.stderr)
    real_jit, real_prefetcher = LT.donated_jit, LT.DevicePrefetcher
    observer = _Observer(c, real_jit, variant)
    observed, gets = _queue_observer(real_prefetcher)
    first = c["check_steps"]
    latest = first + mix["drain_steps_max"]
    traced = min(seconds, mix["trace_seconds"])
    opened = {}
    stop = threading.Event()

    def watch():
        while not stop.is_set():
            logged = tee.steps()
            k = opens_at(gets, len(logged), first, latest)
            if k is not None:
                break
            time.sleep(0.01)
        else:
            return
        opened["t"], opened["step"] = logged[k][0], logged[k][1]
        end = opened["t"] + seconds
        if trace:
            # the trace covers the window's last ``traced`` seconds:
            # stopping a trace of a whole 51 s window took ~200 s on a
            # v5e host, about 4 s a traced second
            if stop.wait(max(0.0, end - traced - time.perf_counter())):
                return
            TR.begin(harness.TRACE_DIR)
            opened["traced"] = True
        window = span(TR.WINDOW)
        window.__enter__()
        stop.wait(max(0.0, end - time.perf_counter()))
        window.__exit__(None, None, None)
        if stop.is_set() or not callable(signal.getsignal(signal.SIGTERM)):
            return          # the launcher ended, or cannot drain on SIGTERM
        opened["sigterm"] = time.perf_counter()
        os.kill(os.getpid(), signal.SIGTERM)

    watcher = threading.Thread(target=watch, daemon=True)
    LT.donated_jit, LT.DevicePrefetcher = observer, observed
    watcher.start()
    try:
        with contextlib.redirect_stdout(tee):
            state = LT.main(argv)
    finally:
        stop.set()
        watcher.join()
        LT.donated_jit, LT.DevicePrefetcher = real_jit, real_prefetcher
    raw = TR.end(harness.TRACE_DIR) if "traced" in opened else None
    if "sigterm" not in opened:
        raise harness.Refused("the launcher ended before the window closed")
    memory = P.peak_bytes(devs[0])
    del state
    t0 = opened["t"]
    logged = tee.steps()
    inside = [(t, m) for t, _, m in logged if t > t0]
    if not inside:
        raise harness.Refused("no step was logged inside the window")
    t_end = inside[-1][0]
    failed = sum(not np.isfinite(m["loss_value"]) for _, m in inside)
    harness.stamp(
        f"window opened at step {opened['step']}; per step (step, s since "
        "the last log, batches queued when asked, s waited): " + " ".join(
            f"{s}:{t - tp:.3f}/{g[0]}/{g[1]:.3f}" for (tp, _, _), (t, s, _), g
            in zip(logged[:40], logged[1:41], gets[1:41])))

    # the reference: the data layer's batches for the first steps, rebuilt
    # from the seed, and the plain trainer on them; and the element types
    # of the products in the program the window ran
    t_ref = time.perf_counter()
    want_batches = reference_batches(c, mix, seed)
    want = P.reference_readings(c, mix, seed, [
        ({key: jnp.asarray(v) for key, v in b.items()}, i)
        for b, i in want_batches])
    found = compare.numbers(observer.got, want)
    found["batch_gap"] = {"value": _batch_gap(observer.fed, want_batches)}
    found["narrow_dots"] = {"value": P.narrow_dots(observer.jitted,
                                                   observer.args)}
    harness.stamp(f"reference {time.perf_counter() - t_ref:.2f}s "
                  f"readings {found}")
    ok, checks = compare.judge(found, c["limits"])

    out = {"e2e": {"setup_s": t0 - t_start,
                   "fed_pairs_per_s": len(inside) * B / (t_end - t0)},
           "correct": ok and failed == 0 and len(inside) > 0,
           "attempted": len(inside), "failed": failed,
           "memory_peak_bytes": memory, "trace": raw, "checks": checks,
           "flops_per_pair": flops.per_pair(c, B)}
    if trace:
        # the program's own loader and dataset making global batches at the
        # cell's sizes, alone on the host
        ds = LT.build_dataset(get_arch(c["program_arch"]), "contrastive", n,
                              32)
        batches = ShardedLoader(ds, global_batch=B, n_shards=1,
                                seed=seed).steps(4)
        next(batches)
        times = []
        for _ in range(3):
            t = time.perf_counter()
            next(batches)
            times.append(time.perf_counter() - t)
        out["input_batch_ms"] = statistics.median(times) * 1e3
    harness.stamp(f"steps {len(inside)} window {t_end - t0:.3f}s setup "
                  f"{t0 - t_start:.2f}s")
    return out
