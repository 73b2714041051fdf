"""A pool of distinct batches made on the device from the seed during set-up
and cycled through the program's own jitted train step, each step ended by
``block_until_ready``.  The first ``check_steps`` steps (set-up) go through
that same call on distinct rows; the plain reference follows them once the
window has closed.

Mix parameters: ``pool_batches`` (distinct batches in the pool),
``image_scale`` (standard deviation of the pixels) and ``steps`` (the
launcher's schedule horizon).  One chip: the step is built unsharded.
"""
from __future__ import annotations

import statistics
import time
from typing import Optional

import numpy as np

from chipbench import compare, harness, program as P, trace as TR


def make_pool(seed: int, c: dict, mix: dict):
    """``pool_batches`` distinct batches and their sample indices, made on
    the device in one jitted call from the seed: standard normal images,
    tokens uniform over [1, vocab), indices distinct across the pool (one
    per stratum of [0, n_samples), in a random order)."""
    import jax
    import jax.numpy as jnp
    nb, B, n = mix["pool_batches"], c["global_batch"], c["n_samples"]
    S, ctx = c["image_size"], c["context_length"]
    stride = n // (nb * B)
    if stride < 1:
        raise harness.Refused("n_samples is smaller than the pool")

    def gen(key):
        ki, kt, ks, kp = jax.random.split(key, 4)
        images = mix["image_scale"] * jax.random.normal(
            ki, (nb, B, S, S, 3), jnp.float32)
        texts = jax.random.randint(kt, (nb, B, ctx), 1, c["vocab_size"],
                                   jnp.int32)
        idx = (jnp.arange(nb * B, dtype=jnp.int32) * stride
               + jax.random.randint(ks, (nb * B,), 0, stride, jnp.int32))
        idx = jax.random.permutation(kp, idx).reshape(nb, B)
        return [({"images": images[i], "texts": texts[i]}, idx[i])
                for i in range(nb)]

    key = jax.random.fold_in(jax.random.PRNGKey(seed), 1)
    return jax.jit(gen)(key)




def run(c: dict, mix: dict, *, seed: int, seconds: float, trace: bool,
        t_start: float, devs, variant: Optional[P.Variant] = None):
    import jax
    from jax.profiler import TraceAnnotation as span
    from chipbench import flops

    if len(devs) != 1:
        raise harness.Refused("step_pool drives one chip; a cell on more "
                              "needs a driver that builds the mesh")
    B = c["global_batch"]
    since = lambda: f"{time.perf_counter() - t_start:.2f}s"  # noqa: E731
    clock = harness.CompileClock()
    harness.stamp("jax up", since())
    tc, jitted = P.build_step(c, mix, seed, variant)
    step = P.timed(jitted, variant, B)
    with span("chipbench.init"):
        state = jax.block_until_ready(P.init_state(tc, seed))
    harness.stamp("state made", since())
    with span("chipbench.pool_build"):
        pool = jax.block_until_ready(make_pool(seed, c, mix))
    harness.stamp("pool made", since())
    args = P.abstract((state,) + tuple(pool[0]))
    # set-up ends with the first steps through the window's own call: they
    # compile the step and give the program's readings
    state, prog = P.program_readings(c, step, state, pool)
    setup_s = time.perf_counter() - t_start
    harness.stamp("program steps checked", since(), clock.take())

    if trace:
        TR.begin(harness.TRACE_DIR)
    times, losses = [], []
    k = c["check_steps"]
    with span(TR.WINDOW):
        w0 = time.perf_counter()
        while True:
            t = time.perf_counter()
            with span("chipbench.step_dispatch"):
                state, m = step(state, *pool[k % len(pool)])
            with span("chipbench.step_wait"):
                jax.block_until_ready((state, m))
            end = time.perf_counter()
            times.append(end - t)
            losses.append(m["loss_value"])
            k += 1
            if end - w0 >= seconds:
                break
    window = end - w0
    harness.stamp("in the window:", clock.take())
    raw = TR.end(harness.TRACE_DIR) if trace else None
    losses = [float(x) for x in jax.device_get(losses)]
    failed = sum(not np.isfinite(x) for x in losses)
    memory = P.peak_bytes(devs[0])
    del state, m

    # the plain reference, once the window has closed and the program's
    # state is freed, over the same first batches; and the element types
    # of the products in the program the window ran
    t_ref = time.perf_counter()
    found = compare.numbers(prog, P.reference_readings(c, mix, seed, pool))
    found["narrow_dots"] = {"value": P.narrow_dots(jitted, args)}
    harness.stamp(f"reference {time.perf_counter() - t_ref:.2f}s "
                  f"({clock.take()}) readings {found}")
    ok, checks = compare.judge(found, c["limits"])
    harness.stamp(f"steps {len(times)} window {window:.3f}s median step "
                  f"{statistics.median(times) * 1e3:.2f}ms slowest "
                  f"{[round(t * 1e3, 1) for t in sorted(times)[-3:]]}ms "
                  f"setup {setup_s:.2f}s")
    return {"e2e": {"setup_s": setup_s,
                    "pairs_per_s": len(times) * B / window,
                    "step_ms_p95": float(np.percentile(times, 95)) * 1e3},
            "correct": ok and failed == 0 and len(times) > 0,
            "attempted": len(times), "failed": failed,
            "memory_peak_bytes": memory, "trace": raw, "checks": checks,
            "flops_per_pair": flops.per_pair(c, B)}
