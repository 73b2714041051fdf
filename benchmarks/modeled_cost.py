"""Modeled-cost regression gate: run ``HLOCostModel`` over the lowered
production modules and compare against checked-in goldens.

Modules covered (all on the reduced ViT-B/32-family CLIP):

    step-dense   : f32 / chunked-attention / dense-loss train step
    step-fused   : bf16 / flash-attention / fused-Pallas-loss train step
    eval-extract : ``eval.extraction.make_extract_fn`` tower-pair forward
    serve-encode : ``eval.extraction.make_serve_encode_fn`` image encode
                   at the serving engine's max batch bucket
    step-fsdp    : the train step on a (data=2, fsdp=2) mesh — runs in a
                   subprocess with 4 forced host devices; its collective
                   counts are the PR 5 sharding contract (reduce-scatters
                   present, bounded all-reduces) expressed as numbers
    step-fsdp-microbatch : the same step with the PR 10 comm/compute-
                   overlap pipeline (TrainStepConfig.microbatch=2); the
                   extra per-micro-step reduce-scatters and the
                   still-bounded all-reduces are the overlap contract
                   expressed as numbers

Per module the row records modeled flops, HBM bytes, collective bytes and
per-kind collective counts — machine-independent properties of the lowered
HLO, so they regress meaningfully on CPU CI.  ``--write-golden`` snapshots
``benchmarks/goldens/modeled_cost.json``; ``--check`` (the CI mode,
perf-model-smoke job) fails when collective counts differ at all or when
flops/bytes drift beyond ``--rel-tol`` (default 5%).  ``BENCH_step.json``
rows (``benchmarks/step_bench.py``) carry the same columns per timed
variant.

Usage:
    PYTHONPATH=src python -m benchmarks.modeled_cost --check
    PYTHONPATH=src python -m benchmarks.modeled_cost --write-golden
        [--skip-fsdp] [--golden PATH] [--rel-tol 0.05]
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp

REL_TOL = 0.05
GOLDEN_PATH = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "goldens", "modeled_cost.json")
_ROW_MARK = "FSDP-MODELED-ROW "


def _model_row(module, hlo_text, default_group=2):
    from repro.roofline.hlo_cost import HLOCostModel
    cm = HLOCostModel(hlo_text, default_group=default_group)
    flops, hbm, coll = cm.totals()
    return {
        "module": module,
        "modeled_flops": flops,
        "modeled_hbm_bytes": hbm,
        "modeled_collective_bytes": coll,
        "collective_counts": {
            k: int(v) for k, v in sorted(cm.collective_counts().items())},
    }


def _step_row(module, precision, impl, loss_impl):
    """Lower the train step with abstract state/batch (no init compute)."""
    from benchmarks.step_bench import GLOBAL_BATCH, _build
    from repro.core import train_step as TS
    from repro.launch.steps import donated_jit
    tc, _ = _build(precision, impl, loss_impl, steps=8)
    c = tc.arch.clip
    state = jax.eval_shape(lambda k: TS.init_train_state(k, tc),
                           jax.random.PRNGKey(0))
    batch = {
        "images": jax.ShapeDtypeStruct(
            (GLOBAL_BATCH, c.image_size, c.image_size, 3), jnp.float32),
        "texts": jax.ShapeDtypeStruct(
            (GLOBAL_BATCH, c.context_length), jnp.int32),
    }
    idx = jax.ShapeDtypeStruct((GLOBAL_BATCH,), jnp.int32)
    compiled = donated_jit(TS.make_train_step(tc)).lower(
        state, batch, idx).compile()
    return _model_row(module, compiled.as_text())


def _eval_extract_row(batch_size=64):
    from repro.configs import get_arch
    from repro.eval import extraction as EX
    from repro.models import backbones as BB
    cfg = get_arch("clip-vitb32-cc12m").reduced()
    c = cfg.clip
    params = jax.eval_shape(lambda k: BB.init_params(k, cfg),
                            jax.random.PRNGKey(0))
    batch = {
        "images": jax.ShapeDtypeStruct(
            (batch_size, c.image_size, c.image_size, 3), jnp.float32),
        "texts": jax.ShapeDtypeStruct(
            (batch_size, c.context_length), jnp.int32),
    }
    jfn = EX.make_extract_fn(lambda p, b: BB.encode_pair(p, cfg, b))
    compiled = jfn.lower(params, batch).compile()
    return _model_row("eval-extract", compiled.as_text())


def _serve_encode_row(max_batch=8):
    from repro.configs import get_arch
    from repro.eval import extraction as EX
    from repro.models import backbones as BB, clip as CL
    cfg = get_arch("clip-vitb32-cc12m").reduced()
    c = cfg.clip
    params = jax.eval_shape(lambda k: BB.init_params(k, cfg),
                            jax.random.PRNGKey(0))
    images = jax.ShapeDtypeStruct(
        (max_batch, c.image_size, c.image_size, 3), jnp.float32)
    jfn = EX.make_serve_encode_fn(
        lambda p, imgs: CL.encode_image(p, cfg, imgs))
    compiled = jfn.lower(params, images).compile()
    return _model_row("serve-encode", compiled.as_text())


def fsdp_worker():
    """Runs in the 4-forced-host-device subprocess (see ``_fsdp_rows``):
    shard the train state on the (data=2, fsdp=2) mesh, lower the step
    unpipelined and with microbatch=2, model both HLOs, print the rows."""
    import dataclasses

    from benchmarks.step_bench import SHARDED_MESH, _build
    from repro.core import shard_state as SS
    from repro.core import train_step as TS
    from repro.launch.steps import donated_jit
    data_sz, fsdp_sz = SHARDED_MESH
    mesh = SS.make_train_mesh(data_sz, fsdp_sz)
    TS.set_mesh(mesh)
    tc, loader = _build("f32", "chunked", "dense", steps=8,
                        n_shards=data_sz * fsdp_sz, fsdp=True)
    state = TS.init_train_state(jax.random.PRNGKey(0), tc)
    state, _ = SS.shard_train_state(state, mesh)
    _, _, idx, batch = next(iter(loader.steps(1)))
    batch = {k: jnp.asarray(v) for k, v in batch.items()}
    idx = jnp.asarray(idx)
    for module, cfg in (
            (f"step-fsdp-d{data_sz}f{fsdp_sz}", tc),
            ("step-fsdp-microbatch", dataclasses.replace(tc, microbatch=2))):
        compiled = donated_jit(TS.make_train_step(cfg)).lower(
            state, batch, idx).compile()
        row = _model_row(module, compiled.as_text(), default_group=fsdp_sz)
        print(_ROW_MARK + json.dumps(row))


def _fsdp_rows():
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
        [sys.executable, "-m", "benchmarks.modeled_cost", "--fsdp-worker"],
        capture_output=True, text=True, env=env, cwd=root, timeout=900)
    rows = [json.loads(line[len(_ROW_MARK):])
            for line in p.stdout.splitlines() if line.startswith(_ROW_MARK)]
    if not rows:
        raise RuntimeError(f"fsdp modeled-cost worker failed "
                           f"(rc={p.returncode}): {p.stderr[-2000:]}")
    return rows


def collect(skip_fsdp=False):
    rows = [
        _step_row("step-dense", "f32", "chunked", "dense"),
        _step_row("step-fused", "bf16", "flash", "fused"),
        _eval_extract_row(),
        _serve_encode_row(),
    ]
    if not skip_fsdp:
        rows.extend(_fsdp_rows())
    return rows


def compare(rows, golden, rel_tol=REL_TOL):
    """Drift report: [] when everything matches.  Collective counts must
    match EXACTLY (a changed count is a changed communication pattern);
    flops/bytes may drift up to rel_tol (minor fusion-shape churn)."""
    gold = {r["module"]: r for r in golden["rows"]}
    problems = []
    for row in rows:
        g = gold.get(row["module"])
        if g is None:
            problems.append(f"{row['module']}: no golden entry "
                            f"(run --write-golden)")
            continue
        if row["collective_counts"] != g["collective_counts"]:
            problems.append(
                f"{row['module']}: collective counts "
                f"{row['collective_counts']} != golden "
                f"{g['collective_counts']}")
        for key in ("modeled_flops", "modeled_hbm_bytes",
                    "modeled_collective_bytes"):
            cur, ref = float(row[key]), float(g[key])
            if ref == 0.0:
                drift = 0.0 if cur == 0.0 else float("inf")
            else:
                drift = abs(cur - ref) / ref
            if drift > rel_tol:
                problems.append(f"{row['module']}: {key} {cur:.4g} vs "
                                f"golden {ref:.4g} ({100 * drift:.1f}% "
                                f"> {100 * rel_tol:.0f}%)")
    missing = set(gold) - {r["module"] for r in rows}
    for m in sorted(missing):
        problems.append(f"{m}: in golden but not produced this run")
    return problems


def run(steps=None, seed=None):
    """benchmarks.run harness entry (no golden gate, just the rows)."""
    return [(f"modeled_cost/{r['module']}", 0.0,
             f"flops={r['modeled_flops']:.3e};"
             f"hbm_bytes={r['modeled_hbm_bytes']:.3e};"
             f"coll={r['collective_counts']}") for r in collect()]


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--check", action="store_true",
                    help="compare against the golden; exit 1 on drift")
    ap.add_argument("--write-golden", action="store_true")
    ap.add_argument("--golden", default=GOLDEN_PATH)
    ap.add_argument("--rel-tol", type=float, default=REL_TOL)
    ap.add_argument("--skip-fsdp", action="store_true",
                    help="skip the 4-device subprocess row")
    ap.add_argument("--fsdp-worker", action="store_true",
                    help=argparse.SUPPRESS)   # internal: 4-device child
    args = ap.parse_args()

    if args.fsdp_worker:
        fsdp_worker()
        return

    rows = collect(skip_fsdp=args.skip_fsdp)
    for r in rows:
        print(f"{r['module']:>16}: flops={r['modeled_flops']:.3e} "
              f"hbm={r['modeled_hbm_bytes']:.3e} "
              f"coll_bytes={r['modeled_collective_bytes']:.3e} "
              f"counts={r['collective_counts']}")

    if args.write_golden:
        os.makedirs(os.path.dirname(args.golden), exist_ok=True)
        doc = {"bench": "modeled_cost",
               "arch": "clip-vitb32-cc12m (reduced)",
               "rel_tol": args.rel_tol, "rows": rows}
        with open(args.golden, "w") as f:
            json.dump(doc, f, indent=2, sort_keys=True)
            f.write("\n")
        print(f"wrote {args.golden}")
        return

    if args.check:
        try:
            with open(args.golden) as f:
                golden = json.load(f)
        except OSError:
            print(f"FAIL: golden {args.golden} missing — run "
                  f"--write-golden first", file=sys.stderr)
            sys.exit(1)
        if args.skip_fsdp:
            golden = dict(golden)
            golden["rows"] = [r for r in golden["rows"]
                              if not r["module"].startswith("step-fsdp")]
        problems = compare(rows, golden, rel_tol=args.rel_tol)
        if problems:
            print("FAIL: modeled-cost drift vs golden:", file=sys.stderr)
            for p in problems:
                print(f"  {p}", file=sys.stderr)
            sys.exit(1)
        print(f"OK: {len(rows)} modules within tolerance "
              f"(counts exact, flops/bytes <= {100 * args.rel_tol:.0f}%)")


if __name__ == "__main__":
    main()
