"""End-to-end train-step throughput: the f32 dense baseline vs the bf16
flash+fused fast path, plus the sharded-state (data, fsdp) step.

Times full optimizer steps (towers fwd/bwd + FCCO loss + AdamW update,
state donated) of the reduced ViT-B/32-family CLIP on synthetic data and
emits ``BENCH_step.json`` with one row per variant:

    f32-dense   : precision=f32,  impl=chunked, loss_impl=dense
    bf16-flash  : precision=bf16, impl=flash,   loss_impl=fused
    fsdp-d2f2   : f32-dense on a (data=2, fsdp=2) mesh — the sharded
                  train state (core.shard_state): reports steps/s plus
                  per-device param+moment bytes vs the replicated bytes.
                  Runs in a subprocess with 4 forced host devices (the
                  main process keeps 1), so per-step time measures the
                  correctness surface on CPU, not mesh speed.

On CPU the Pallas kernels run in interpret mode, so absolute times measure
the correctness surface, not TPU speed — the row schema and the loss-parity
column are the durable part (the ``delta_loss_vs_f32`` field bounds the
bf16 policy drift after ``steps`` real optimizer steps; it is null for the
sharded row, whose 4-shard loader draws differently-ordered batches).

Every row also carries modeled-cost columns from ``HLOCostModel`` over the
step's post-optimization HLO: ``modeled_flops``, ``modeled_hbm_bytes``,
``modeled_collective_bytes``, ``modeled_collective_counts``.  These are
machine-independent (a property of the lowered module, not the host), so
they regress meaningfully on CPU CI — ``benchmarks/modeled_cost.py``
snapshots them as goldens and the perf-model-smoke CI job fails on drift.

Run: PYTHONPATH=src python -m benchmarks.step_bench [--quick] [--steps N]
     [--out BENCH_step.json]
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_arch
from repro.core import fastclip as FC
from repro.core import shard_state as SS
from repro.core import train_step as TS
from repro.core.schedules import lr_warmup_cosine
from repro.data import ContrastiveDataset, ShardedLoader
from repro.launch.steps import donated_jit
from repro.optim import adamw

N_SAMPLES = 256
GLOBAL_BATCH = 64
SHARDED_MESH = (2, 2)    # (data, fsdp)
_ROW_MARK = "SHARDED-ROW "

VARIANTS = [
    # (name, precision, attention impl, loss impl)
    ("f32-dense", "f32", "chunked", "dense"),
    ("bf16-flash", "bf16", "flash", "fused"),
]


def _build(precision, impl, loss_impl, steps, seed=0, n_shards=1,
           fsdp=False):
    cfg = get_arch("clip-vitb32-cc12m").reduced()
    ds = ContrastiveDataset(n=N_SAMPLES, image_size=cfg.clip.image_size,
                            context_length=cfg.clip.context_length,
                            vocab_size=cfg.vocab_size, n_classes=32,
                            seed=seed)
    loader = ShardedLoader(ds, global_batch=GLOBAL_BATCH, seed=seed,
                           n_shards=n_shards)
    fc = FC.FastCLIPConfig(version="v3", n_samples=N_SAMPLES,
                           steps_per_epoch=loader.steps_per_epoch,
                           gamma_decay_epochs=2)
    tc = TS.TrainStepConfig(arch=cfg, fc=fc, optimizer=adamw(),
                            lr_fn=lr_warmup_cosine(1e-3, 4, max(steps, 8)),
                            wd=0.1, impl=impl, loss_impl=loss_impl,
                            precision=precision,
                            mesh_axes=SS.TRAIN_AXES if fsdp else None,
                            fsdp=fsdp)
    return tc, loader


def _time_steps(name, tc, loader, state, steps):
    """The shared compile/step timing loop + row assembly (identical
    protocol for the local variants and the sharded worker).

    The step is compiled ahead-of-time (``.lower().compile()``) so the
    same executable serves both the timing loop and the modeled-cost
    columns: its post-optimization HLO goes through ``HLOCostModel``
    (trip-count-aware flops / HBM bytes / collective counts — the numbers
    ``benchmarks.modeled_cost`` snapshots as goldens and CI gates on)."""
    from repro.roofline.hlo_cost import HLOCostModel

    jit_fn = donated_jit(TS.make_train_step(tc))
    compiled = None
    t_compile = t_steps = 0.0
    n_timed = 0
    losses = []
    for epoch, step, idx, batch in loader.steps(steps):
        batch = {k: jnp.asarray(v) for k, v in batch.items()}
        idx = jnp.asarray(idx)
        if compiled is None:
            t0 = time.perf_counter()
            compiled = jit_fn.lower(state, batch, idx).compile()
            t_compile = time.perf_counter() - t0
            hlo_text = compiled.as_text()
        t0 = time.perf_counter()
        state, m = compiled(state, batch, idx)
        jax.block_until_ready(m["loss"])
        dt = time.perf_counter() - t0
        if step > 0:          # step 0 is the warmup call
            t_steps += dt
            n_timed += 1
        losses.append(float(m["loss"]))
    TS.check_state_dtypes(state)  # f32 masters under any policy
    # fallback group size for collectives with no parseable replica_groups
    # (both SHARDED_MESH axes have size 2; unused on the 1-device variants)
    cm = HLOCostModel(hlo_text, default_group=2)
    mflops, mbytes, mcoll = cm.totals()
    s_per_step = t_steps / max(n_timed, 1)
    row = {
        "name": name,
        "precision": tc.precision or "f32",
        "impl": tc.impl,
        "loss_impl": tc.loss_impl or "dense",
        "steps_timed": n_timed,
        "steps_per_s": round(1.0 / max(s_per_step, 1e-9), 3),
        "ms_per_step": round(1e3 * s_per_step, 2),
        "compile_s": round(t_compile, 2),
        "loss_first": round(losses[0], 6),
        "loss_final": round(losses[-1], 6),
        "sat_rate": float(m["sat_rate"]),
        "modeled_flops": mflops,
        "modeled_hbm_bytes": mbytes,
        "modeled_collective_bytes": mcoll,
        "modeled_collective_counts": {
            k: int(v) for k, v in sorted(cm.collective_counts().items())},
    }
    return row, state


def bench_variant(name, precision, impl, loss_impl, steps, seed=0):
    tc, loader = _build(precision, impl, loss_impl, steps, seed)
    state = TS.init_train_state(jax.random.PRNGKey(seed), tc)
    row, _ = _time_steps(name, tc, loader, state, steps)
    return row


def bench_sharded_worker(steps, seed=0):
    """Runs inside the 4-forced-host-device subprocess: time the fsdp
    train step on the (data=2, fsdp=2) mesh and report per-device state
    bytes alongside throughput.  Same _build/_time_steps protocol as the
    local variants, plus mesh setup and the byte columns."""
    data_sz, fsdp_sz = SHARDED_MESH
    mesh = SS.make_train_mesh(data_sz, fsdp_sz)
    TS.set_mesh(mesh)
    tc, loader = _build("f32", "chunked", "dense", steps, seed,
                        n_shards=data_sz * fsdp_sz, fsdp=True)
    state = TS.init_train_state(jax.random.PRNGKey(seed), tc)
    state, _shardings = SS.shard_train_state(state, mesh)
    row, state = _time_steps(f"fsdp-d{data_sz}f{fsdp_sz}", tc, loader,
                             state, steps)
    heavy = {"params": state["params"], "m": state["opt"]["m"],
             "v": state["opt"]["v"]}
    row["mesh"] = f"data:{data_sz},fsdp:{fsdp_sz}"
    row["param_bytes_per_device"] = SS.per_device_bytes(heavy)
    row["param_bytes_replicated"] = sum(
        int(np.prod(l.shape)) * 4 for l in jax.tree.leaves(heavy))
    return row


def _sharded_row(steps, seed=0):
    """Spawn the 4-device worker (the main process keeps one device)."""
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    # a forced-host-device harness: it must never reach for a chip the
    # parent process may hold
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "") +
                        " --xla_force_host_platform_device_count=4").strip()
    env["PYTHONPATH"] = os.path.join(root, "src") + (
        os.pathsep + env["PYTHONPATH"] if env.get("PYTHONPATH") else "")
    p = subprocess.run(
        [sys.executable, "-m", "benchmarks.step_bench",
         "--sharded-worker", "--steps", str(steps), "--seed", str(seed)],
        capture_output=True, text=True, env=env, cwd=root, timeout=900)
    for line in p.stdout.splitlines():
        if line.startswith(_ROW_MARK):
            return json.loads(line[len(_ROW_MARK):])
    raise RuntimeError(f"sharded step_bench worker failed "
                       f"(rc={p.returncode}): {p.stderr[-2000:]}")


def collect(steps=12, seed=0):
    rows = []
    for name, precision, impl, loss_impl in VARIANTS:
        rows.append(bench_variant(name, precision, impl, loss_impl,
                                  steps, seed))
    base = rows[0]
    for r in rows:
        r["delta_loss_vs_f32"] = round(
            abs(r["loss_final"] - base["loss_final"]), 6)
        r["speedup_vs_f32"] = round(
            base["ms_per_step"] / max(r["ms_per_step"], 1e-9), 3)
    sharded = _sharded_row(steps, seed)
    # the sharded loader draws per-shard-permuted batches: its loss path
    # is parity-tested bit-exactly elsewhere, not comparable here
    sharded["delta_loss_vs_f32"] = None
    sharded["speedup_vs_f32"] = round(
        base["ms_per_step"] / max(sharded["ms_per_step"], 1e-9), 3)
    rows.append(sharded)
    return rows


def run(steps=None, seed=0):
    """benchmarks.run harness entry: (name, us_per_call, derived) rows."""
    rows = collect(steps=steps or 12, seed=seed)
    return [(f"step_bench/{r['name']}", 1e3 * r["ms_per_step"],
             f"steps_per_s={r['steps_per_s']};"
             f"delta_loss_vs_f32={r['delta_loss_vs_f32']};"
             f"sat_rate={r['sat_rate']}") for r in rows]


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="4 timed steps (CI smoke)")
    ap.add_argument("--steps", type=int, default=None)
    ap.add_argument("--out", default="BENCH_step.json")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--sharded-worker", action="store_true",
                    help=argparse.SUPPRESS)   # internal: 4-device child
    args = ap.parse_args(argv)
    steps = args.steps or (5 if args.quick else 12)

    if args.sharded_worker:
        row = bench_sharded_worker(steps, seed=args.seed)
        print(_ROW_MARK + json.dumps(row))
        return row

    rows = collect(steps=steps, seed=args.seed)
    doc = {
        "bench": "step_bench",
        "arch": "clip-vitb32-cc12m (reduced)",
        "global_batch": GLOBAL_BATCH,
        "backend": jax.default_backend(),
        "interpret_kernels": jax.default_backend() != "tpu",
        "steps": steps,
        "rows": rows,
    }
    with open(args.out, "w") as f:
        json.dump(doc, f, indent=2)
        f.write("\n")
    for r in rows:
        print(f"{r['name']:>11}: {r['ms_per_step']:8.1f} ms/step "
              f"({r['steps_per_s']:.2f} steps/s)  "
              f"dloss_vs_f32={r['delta_loss_vs_f32']}")
    print(f"wrote {args.out}")
    return doc


if __name__ == "__main__":
    main()
