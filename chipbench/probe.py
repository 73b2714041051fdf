"""Readings behind the limits of ``correct``: the comparison's numbers for the
program, its control and planted faults, on many seeds in one process,
without a measured window.  The benchmark's own runs do not run this.

    python3 chipbench/probe.py --workload vitb32.step \
        --variants program,control,half_batch --seeds 11,12,13

``control`` is the program's own bf16 tower policy (``--precision bf16``),
the precision below the float32 the configuration states.  Prints one JSON
line per (variant, seed).
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from chipbench import compare, harness, program as P  # noqa: E402


def _half_batch(step, batch_size):
    """Half of the batch left out; the step takes its mean over the rest."""
    h = batch_size // 2
    return lambda s, b, i: step(s, {k: v[:h] for k, v in b.items()}, i[:h])


def _unchanged(step, batch_size):
    """A step that returns its state unchanged."""
    import jax
    import jax.numpy as jnp

    def run(s, b, i):
        _, m = step(jax.tree.map(jnp.copy, s), b, i)
        return s, m
    return run


VARIANTS = {
    "program": None,
    "control": P.Variant(argv=("--precision", "bf16")),
    "half_batch": P.Variant(wrap=_half_batch),
    "unchanged": P.Variant(wrap=_unchanged),
}


def readings(cell: str, c: dict, mix: dict, variant, seeds):
    """Yields (seed, numbers) for each seed.  Step-pool cells build the
    step once per variant; other cells run whole (short) runs."""
    if mix["driver"] != "step_pool":
        for seed in seeds:
            out = harness.run_cell(cell, seed, 5.0, False,
                                   t_start=time.perf_counter(),
                                   variant=variant)
            yield seed, out["checks"]
        return
    from chipbench.drivers.step_pool import make_pool
    tc, jitted = P.build_step(c, mix, seeds[0], variant)
    step = P.timed(jitted, variant, c["global_batch"])
    narrow = None
    for seed in seeds:
        state = P.init_state(tc, seed)
        pool = make_pool(seed, c, mix)
        if narrow is None:
            narrow = P.narrow_dots(jitted, (state,) + tuple(pool[0]))
        state, got = P.program_readings(c, step, state, pool)
        del state
        want = P.reference_readings(c, mix, seed, pool)
        found = compare.numbers(got, want)
        found["narrow_dots"] = {"value": narrow}
        yield seed, found


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--variants", default="program")
    ap.add_argument("--seeds", required=True)
    args = ap.parse_args(argv)
    harness.use_compile_cache()
    man = harness.manifest()
    cell = harness.workload(man, args.workload)
    harness.chip(cell["chips"])
    c = dict(harness.config(man, cell["config"]),
             limits=harness.limits(args.workload))
    mix = harness.traffic(cell["traffic"])
    seeds = [int(s) for s in args.seeds.split(",")]
    for name in args.variants.split(","):
        t = time.perf_counter()
        for seed, found in readings(args.workload, c, mix, VARIANTS[name],
                                    seeds):
            ok, _ = compare.judge(found, c["limits"])
            print(json.dumps({"workload": args.workload, "variant": name,
                              "seed": seed, "correct": ok,
                              "seconds": time.perf_counter() - t, **found}),
                  flush=True)
            t = time.perf_counter()
    return 0


if __name__ == "__main__":
    sys.exit(main())
