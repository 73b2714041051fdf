# One function per paper table. Prints ``name,us_per_call,derived`` CSV.
"""Benchmark harness: every row maps to a paper table/figure.

    table3_inner_lr   -> Table 3 (gamma schedule)
    table4_temperature-> Table 4 (tau update rules v0-v3)
    table5_optimizer  -> Table 5 (AdamW/LAMB/Lion/SGDM)
    fig3_comm         -> Fig. 3 (communication bytes of the reductions)
    scaling_model     -> Fig. 4 / Tables 15-16 (scaling time model)
    kernel_bench      -> loss-layer micro-bench
    step_bench        -> end-to-end step throughput (f32-dense vs
                         bf16-flash-fused; also emits BENCH_step.json via
                         ``python -m benchmarks.step_bench``)
    retrieval_bench   -> eval-engine streaming top-k vs dense oracle
    data_bench        -> host data pipeline samples/s (streaming shard
                         decode vs in-memory synthetic)
    serve_bench       -> serving-engine offered-load sweep: p50/p99
                         latency, shed rate, cache hit rate (also
                         emits BENCH_serve.json via
                         ``python -m benchmarks.serve_bench``)
    roofline_table    -> deliverable (g) table from the dry-run sweep
                         (errors loudly when experiments/dryrun/ is
                         empty — never an empty table)
    autotune_bench    -> kernel tile/chunk sweep w/ oracle parity gates
                         (``python -m benchmarks.autotune_bench`` also
                         persists the tuning table the kernels consult)
    modeled_cost      -> HLOCostModel columns for the lowered step/eval/
                         serve/fsdp modules (``python -m
                         benchmarks.modeled_cost --check`` gates them
                         against benchmarks/goldens/modeled_cost.json)

Run: PYTHONPATH=src python -m benchmarks.run [--quick] [--only rx]
"""
import argparse
import re
import sys
import time
import traceback


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="fewer train steps per table")
    ap.add_argument("--only", default=None)
    args = ap.parse_args()
    steps = 40 if args.quick else 120

    from benchmarks import (autotune_bench, data_bench, fig3_comm,
                            kernel_bench, modeled_cost, retrieval_bench,
                            roofline_table, scaling_model, serve_bench,
                            step_bench, table3_inner_lr,
                            table4_temperature, table5_optimizer)
    benches = [
        ("table3_inner_lr", lambda: table3_inner_lr.run(steps=steps)),
        ("table4_temperature", lambda: table4_temperature.run(steps=steps)),
        ("table5_optimizer", lambda: table5_optimizer.run(steps=steps)),
        ("fig3_comm", fig3_comm.run),
        ("scaling_model", scaling_model.run),
        ("kernel_bench", kernel_bench.run),
        ("step_bench", lambda: step_bench.run(steps=5 if args.quick
                                              else 12)),
        ("retrieval_bench", retrieval_bench.run),
        ("data_bench", lambda: data_bench.run(steps=8 if args.quick
                                              else 32)),
        ("serve_bench", lambda: serve_bench.run(quick=args.quick)),
        ("roofline_table", roofline_table.run),
        ("autotune_bench", lambda: autotune_bench.run(quick=True)),
        ("modeled_cost", modeled_cost.run),
    ]
    print("name,us_per_call,derived")
    failed = []
    for name, fn in benches:
        if args.only and not re.search(args.only, name):
            continue
        t0 = time.time()
        try:
            rows = fn()
        except Exception as e:  # run the remaining benches, fail at exit
            traceback.print_exc()
            print(f"{name},0.0,ERROR:{type(e).__name__}:{e}",
                  file=sys.stdout)
            failed.append(name)
            continue
        for rname, us, derived in rows:
            print(f"{rname},{us:.1f},{derived}")
        print(f"# {name} done in {time.time()-t0:.0f}s", file=sys.stderr)
    if failed:
        sys.exit(f"benches failed: {', '.join(failed)}")


if __name__ == '__main__':
    main()
