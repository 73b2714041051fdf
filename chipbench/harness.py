"""One run of one benchmark cell: resolve it by name, check the chip, drive
the traffic, compare against the plain reference, print the result line.

    python3 chipbench/run.py --workload <name> --seed <n> --seconds <s> \
        --trace <0|1>

Everything that belongs to a configuration, a traffic mix, a cell's
correctness limits or a per-layer metric is found by name:
``configs/<config>.json``, ``traffic/<mix>.json`` (whose ``driver`` names
a module ``drivers/<driver>.py``), ``limits/<cell>.json`` and
``metrics/<metric>.py`` beside this file, named in ``BENCHMARK.json`` at
the root of the checkout.
"""
from __future__ import annotations

import argparse
import importlib
import importlib.util
import json
import os
import re
import sys
import time
from typing import Callable, Optional

from chipbench import trace as TR

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
CACHE_DIR = os.path.join(ROOT, ".jax_cache")
TRACE_DIR = os.path.join(ROOT, ".chipbench_trace")


class Refused(SystemExit):
    """A run that cannot give a result: it exits nonzero, with no result
    line."""

    def __init__(self, why: str):
        super().__init__(f"chipbench: {why}")


def load_json(path: str):
    with open(path) as f:
        return json.load(f)


def manifest(root: str = ROOT) -> dict:
    return load_json(os.path.join(root, "BENCHMARK.json"))


def workload(man: dict, name: str) -> dict:
    for w in man["workloads"]:
        if w["name"] == name:
            return w
    raise Refused(f"no workload {name!r} in BENCHMARK.json")


def config(man: dict, name: str, root: str = ROOT) -> dict:
    for c in man["configs"]:
        if c["name"] == name:
            return load_json(os.path.join(root, c["file"]))
    raise Refused(f"no config {name!r} in BENCHMARK.json")


def traffic(name: str) -> dict:
    path = os.path.join(HERE, "traffic", f"{name}.json")
    if not os.path.exists(path):
        raise Refused(f"no traffic mix file {path}")
    return load_json(path)


def limits(cell: str) -> dict:
    """``limits/<cell>.json``: the limit of each number ``correct``
    compares in that cell."""
    path = os.path.join(HERE, "limits", f"{cell}.json")
    if not os.path.exists(path):
        raise Refused(f"no limits file {path}")
    return load_json(path)


def driver(name: str) -> Callable[..., dict]:
    """``drivers/<name>.py``'s ``run``."""
    path = os.path.join(HERE, "drivers", f"{name}.py")
    if not re.fullmatch(r"[A-Za-z_][A-Za-z0-9_]*", name) \
            or not os.path.exists(path):
        raise Refused(f"no driver {path}")
    return importlib.import_module(f"chipbench.drivers.{name}").run


def reader(name: str) -> Callable[[dict], Optional[float]]:
    """``metrics/<name>.py``'s ``read``.  It is given the run's readings
    (``e2e``, ``flops_per_pair``, ``peak``, ``chips`` and the driver's
    own keys), the reduced trace (``trace``: ``trace.reduce``'s summary)
    and the raw one (``ops``: device plane -> operations, ``spans``: the
    benchmark's host spans), and returns None where it finds nothing."""
    path = os.path.join(HERE, "metrics", f"{name}.py")
    spec = importlib.util.spec_from_file_location(
        f"chipbench_metric_{name.replace('.', '_')}", path)
    if spec is None or not os.path.exists(path):
        raise Refused(f"no reader {path} for metric {name!r}")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def metrics_of(man: dict, cell: str, trace: bool):
    """The cell's end-to-end metrics, or with ``trace`` its per-layer ones."""
    group = man["per_layer" if trace else "end_to_end"]
    return [m for m in group if cell in m.get("workloads", [cell])]


def peaks(kind: str) -> dict:
    table = load_json(os.path.join(HERE, "peaks.json"))
    if kind not in table:
        raise Refused(f"device kind {kind!r} is not in peaks.json")
    return table[kind]


def chip(chips: int):
    """The devices to run on; refuses without a TPU or with fewer chips."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise Refused(f"JAX found no TPU (platform {devs[0].platform!r})")
    if len(devs) < chips:
        raise Refused(f"the cell needs {chips} chips, JAX sees {len(devs)}")
    return devs[:chips]


def use_compile_cache() -> None:
    """JAX's persistent cache at a fixed path inside the checkout, kept
    whole (no eviction: an evicting cache set up by the machine lost
    entries); set before JAX is imported, so the program's own cache set-up
    takes it."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = CACHE_DIR
    os.environ["JAX_COMPILATION_CACHE_MAX_SIZE"] = "-1"
    os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0")
    os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES", "0")
    src = os.path.join(ROOT, "src")
    if src not in sys.path:
        sys.path.insert(0, src)


def run_cell(name: str, seed: int, seconds: float, trace: bool, *,
             t_start: float, man: Optional[dict] = None,
             cfg: Optional[dict] = None, require_chip: bool = True,
             variant=None) -> dict:
    """One run of cell ``name``; returns the result line as a dict.

    ``cfg`` replaces the cell's configuration file, ``require_chip=False``
    skips the look for a chip, and ``variant`` changes the timed path (the
    program's lower-precision path, or a planted fault): the correctness
    tests use them."""
    man = man if man is not None else manifest()
    cell = workload(man, name)
    cfg = dict(cfg if cfg is not None else config(man, cell["config"]),
               limits=limits(name))
    mix = traffic(cell["traffic"])
    if require_chip:
        devs = chip(cell["chips"])
        peak = peaks(devs[0].device_kind)
    else:
        import jax
        devs, peak = jax.devices()[:cell["chips"]], None
    drive = driver(mix["driver"])
    out = drive(cfg, mix, seed=seed, seconds=seconds, trace=trace,
                t_start=t_start, devs=devs, variant=variant)
    ops, spans = out.pop("trace", None) or ({}, [])
    summary = TR.reduce(ops, spans)
    wanted = metrics_of(man, name, trace)
    if trace:
        ctx = dict(out, trace=summary, ops=ops, spans=spans, peak=peak,
                   chips=len(devs))
        values = {}
        for m in wanted:
            v = reader(m["name"])(ctx)
            if v is not None:
                values[m["name"]] = v
    else:
        values = {m["name"]: out["e2e"][m["name"]] for m in wanted}
    result = {
        "correct": out["correct"],
        "attempted": out["attempted"],
        "failed": out["failed"],
        "metrics": {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                    for m in wanted if m["name"] in values},
        "device": {"platform": devs[0].platform, "kind": devs[0].device_kind,
                   "count": len(devs),
                   "memory_peak_bytes": out["memory_peak_bytes"]},
    }
    if trace and summary is not None:
        result["device"]["busy_s"] = summary["busy_s"]
        result["device"]["window_s"] = summary["window_s"]
        result["breakdown"] = {"device_ops": summary["device_ops"],
                               "idle_gaps": summary["idle_gaps"]}
    result["checks"] = out["checks"]
    return result


def main(argv=None, t_start: Optional[float] = None) -> int:
    t_start = time.perf_counter() if t_start is None else t_start
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    use_compile_cache()
    result = run_cell(args.workload, args.seed, args.seconds,
                      bool(args.trace), t_start=t_start)
    for k, c in result["checks"].items():
        print(f"check {k}: {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr, flush=True)
    print(json.dumps(result, allow_nan=True), flush=True)
    return 0


class CompileClock:
    """Sums JAX's backend-compile seconds (a persistent-cache hit counts its
    retrieval) and counts persistent-cache hits and misses."""

    def __init__(self):
        import jax.monitoring as M
        self.seconds, self.hits, self.misses = 0.0, 0, 0
        M.register_event_duration_secs_listener(self._duration)
        M.register_event_listener(self._event)

    def _duration(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += secs

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def take(self) -> str:
        out = (f"compile {self.seconds:.2f}s, cache hits {self.hits}, "
               f"misses {self.misses}")
        self.seconds, self.hits, self.misses = 0.0, 0, 0
        return out


def stamp(*parts) -> None:
    """A diagnostic line on standard error."""
    print(f"chipbench: [{time.perf_counter():.2f}]", *parts,
          file=sys.stderr, flush=True)


if __name__ == "__main__":
    sys.exit(main())
