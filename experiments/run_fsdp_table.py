import os, subprocess, sys, time
from concurrent.futures import ThreadPoolExecutor
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ARCHS = ["qwen3-1.7b", "xlstm-125m", "granite-3-8b", "yi-6b",
         "seamless-m4t-large-v2", "llama4-scout-17b-a16e",
         "llama-3.2-vision-11b", "zamba2-1.2b", "qwen3-moe-30b-a3b",
         "qwen1.5-32b"]
def run(arch, mp=False):
    out = os.path.join(ROOT, "experiments", "perf",
                       f"{arch}__train_4k__fsdp{'__2pod' if mp else ''}.json")
    if os.path.exists(out):
        return arch, "cached"
    cmd = [sys.executable, "-m", "repro.launch.dryrun", "--arch", arch,
           "--shape", "train_4k", "--sharding", "fsdp", "--out", out]
    if mp: cmd.append("--multi-pod")
    env = dict(os.environ, PYTHONPATH=os.path.join(ROOT, "src"),
               JAX_PLATFORMS="cpu")  # dryrun forces host devices
    t0=time.time()
    p = subprocess.run(cmd, capture_output=True, text=True, timeout=900, env=env)
    if p.returncode: open(out+".err","w").write(p.stderr[-5000:])
    return arch, ("ok %.0fs"%(time.time()-t0)) if p.returncode==0 else "FAIL"
with ThreadPoolExecutor(max_workers=5) as ex:
    jobs = [ex.submit(run, a) for a in ARCHS]
    jobs += [ex.submit(run, a, True) for a in ("qwen3-1.7b","qwen3-moe-30b-a3b")]
    for j in jobs: print(*j.result(), flush=True)
