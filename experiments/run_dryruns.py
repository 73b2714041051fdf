#!/usr/bin/env python
"""Drive the full dry-run matrix as subprocesses (each compile isolated).

    python experiments/run_dryruns.py [--multi-pod] [--jobs N] [--only rx]

Writes experiments/dryrun/<arch>__<shape>__<mesh>[__obj][__red].json.
Skips combos whose JSON already exists.
"""
import argparse
import json
import os
import re
import subprocess
import sys
import time
from concurrent.futures import ThreadPoolExecutor

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
OUT = os.path.join(ROOT, "experiments", "dryrun")

ARCHS = [
    "qwen3-1.7b", "xlstm-125m", "granite-3-8b", "yi-6b",
    "seamless-m4t-large-v2", "llama4-scout-17b-a16e", "llama-3.2-vision-11b",
    "zamba2-1.2b", "qwen3-moe-30b-a3b", "qwen1.5-32b",
]
SHAPES = ["train_4k", "prefill_32k", "decode_32k", "long_500k"]

# extras: the paper's own CLIP arch + the contrastive objective under both
# gradient reductions (the paper's Fig. 3 comparison, at dry-run scale)
EXTRAS = [
    ("clip-vitb16-laion", "train_4k", "contrastive", "fastclip"),
    ("qwen3-1.7b", "train_4k", "contrastive", "fastclip"),
    ("qwen3-1.7b", "train_4k", "contrastive", "allgather_ad"),
]


def job_name(arch, shape, mesh, obj, red):
    n = f"{arch}__{shape}__{mesh}"
    if obj != "lm":
        n += f"__{obj}__{red}"
    return n


def run_one(arch, shape, multi_pod, obj="lm", red="fastclip", timeout=1500):
    mesh = "2x16x16" if multi_pod else "16x16"
    name = job_name(arch, shape, mesh, obj, red)
    out_json = os.path.join(OUT, name + ".json")
    if os.path.exists(out_json):
        return name, "cached"
    cmd = [sys.executable, "-m", "repro.launch.dryrun", "--arch", arch,
           "--shape", shape, "--objective", obj, "--reduction", red,
           "--out", out_json]
    if multi_pod:
        cmd.append("--multi-pod")
    env = dict(os.environ, PYTHONPATH=os.path.join(ROOT, "src"),
               JAX_PLATFORMS="cpu")  # dryrun forces host devices
    t0 = time.time()
    try:
        p = subprocess.run(cmd, capture_output=True, text=True,
                           timeout=timeout, env=env)
    except subprocess.TimeoutExpired:
        with open(out_json + ".err", "w") as f:
            f.write("TIMEOUT")
        return name, "TIMEOUT"
    if p.returncode != 0:
        with open(out_json + ".err", "w") as f:
            f.write(p.stdout[-4000:] + "\n----\n" + p.stderr[-8000:])
        return name, f"FAIL rc={p.returncode}"
    return name, f"ok {time.time()-t0:.0f}s"


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--jobs", type=int, default=5)
    ap.add_argument("--only", default=None)
    ap.add_argument("--skip-extras", action="store_true")
    args = ap.parse_args()
    os.makedirs(OUT, exist_ok=True)

    combos = [(a, s, "lm", "fastclip") for a in ARCHS for s in SHAPES]
    if not args.skip_extras and not args.multi_pod:
        combos += EXTRAS
    if not args.skip_extras and args.multi_pod:
        combos += [EXTRAS[0]]
    if args.only:
        rx = re.compile(args.only)
        combos = [c for c in combos if rx.search(f"{c[0]}__{c[1]}")]

    with ThreadPoolExecutor(max_workers=args.jobs) as ex:
        futs = {ex.submit(run_one, a, s, args.multi_pod, o, r): (a, s)
                for a, s, o, r in combos}
        for fut in futs:
            pass
        for fut in list(futs):
            name, status = fut.result()
            print(f"{name:60s} {status}", flush=True)


if __name__ == "__main__":
    main()
