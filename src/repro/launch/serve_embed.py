"""Online embedding serving launcher (``repro.serve`` engine).

Restores tower params from a checkpoint and serves embedding requests
through the full robustness stack — admission control, continuous
micro-batching, retry over the in-jit finiteness guard, circuit
breaker, digest-verified cache, hot checkpoint reload — then drives a
self-generated open-loop load against it and prints one
``SERVE_STATS {json}`` accounting line (submitted == completed +
rejected; nothing dropped silently).  Sibling launcher:
``repro.launch.serve`` is the *autoregressive decode* demo (KV-cache
token generation for the generative archs); this one serves *CLIP
embeddings* online.

    # known-answer mode: planted closed-form image tower
    PYTHONPATH=src python -m repro.launch.serve_embed --planted \
        --ckpt-dir /tmp/planted --requests 64 --deadline-ms 200

    # real tower from a train checkpoint, with hot reload + chaos
    PYTHONPATH=src python -m repro.launch.serve_embed \
        --arch clip-vitb32-cc12m --reduced --ckpt-dir ckpts \
        --modality image [--impl flash --precision bf16] \
        --watch-ckpt 1.0 --chaos compute_nan@2

SIGTERM mid-run stops the load generator, drains every admitted
request (each future resolves or gets a typed rejection), writes the
final heartbeat, and exits 0 — the preemption contract.
"""
from __future__ import annotations

import argparse
import json
import os
import signal
import time

import jax
import numpy as np

from repro import checkpoint as CK
from repro.configs import get_arch
from repro.eval import planted as PL
from repro.launch.compile_cache import init_compile_cache
from repro.launch.eval import build_eval_dataset
from repro.models import backbones as BB
from repro.models import precision as PR
from repro.models.precision import POLICIES
from repro.resilience import Heartbeat, StepWatchdog, parse_chaos
from repro.serve import (
    CheckpointWatcher, EmbedServer, RetryPolicy, ServeConfig, ServeRejection,
)


def build_server(args, chaos=None, heartbeat=None, watchdog=None):
    """(server, watcher-or-None, dataset) per the CLI flags."""
    ds = None
    if args.planted:
        ds = build_eval_dataset(args)
        if CK.latest_step(args.ckpt_dir) is None:
            path = PL.make_planted_checkpoint(args.ckpt_dir, ds)
            print(f"wrote reference planted checkpoint: {path}")
        like = jax.device_get(PL.planted_params(ds))
        params, step, _meta = CK.restore(args.ckpt_dir, like,
                                         step=args.step)
        prefix = ""

        def encode(params, batch):
            return PL.encode_image(params, batch["images"])
    else:
        cfg = get_arch(args.arch)
        if args.reduced:
            cfg = cfg.reduced()
        like = BB.param_shapes(cfg)
        params, step, _meta = CK.restore_subtree(
            args.ckpt_dir, like, "params", step=args.step)
        prefix = "params"
        ds = build_eval_dataset(args, cfg)
        prec = PR.get_precision(args.precision or cfg.precision)
        from repro.models import clip as C
        tower = C.encode_image if args.modality == "image" else C.encode_text
        key = "images" if args.modality == "image" else "texts"

        def encode(params, batch):
            return tower(params, cfg, batch[key], impl=args.impl,
                         precision=prec)
    params = jax.tree.map(jax.numpy.asarray, params)
    print(f"restored params at step {step} from {args.ckpt_dir}")
    cfg_srv = ServeConfig(
        max_batch=args.max_batch, max_wait=args.max_wait_ms / 1000.0,
        queue_capacity=args.queue_capacity,
        default_deadline=(args.deadline_ms / 1000.0
                          if args.deadline_ms else None),
        retry=RetryPolicy(max_retries=args.max_retries),
        breaker_failures=args.breaker_failures,
        breaker_reset=args.breaker_reset,
        cache_capacity=args.cache_capacity, seed=args.seed)
    server = EmbedServer(encode, params, step, cfg_srv, chaos=chaos,
                         heartbeat=heartbeat, watchdog=watchdog)
    watcher = None
    if args.watch_ckpt is not None:
        watcher = CheckpointWatcher(
            args.ckpt_dir, like, server.store, prefix=prefix,
            poll_interval=args.watch_ckpt,
            fault_hook=(chaos.on_reload if chaos is not None else None))
        watcher.start()
    return server, watcher, ds


def run_load(server, ds, args, stop_flag):
    """Open-loop offered load from the eval split's images; returns the
    client-side outcome counters (by typed rejection code)."""
    rng = np.random.default_rng(args.seed)
    out = {"completed": 0, "OVERLOADED": 0, "DEADLINE": 0, "UNAVAILABLE": 0,
           "offered": 0}
    pool = min(args.payload_pool, ds.n)
    key = "texts" if (not args.planted and args.modality == "text") \
        else "images"
    rows = np.asarray(getattr(ds, key)(np.arange(pool)))
    futures = []
    interval = 1.0 / args.offered_rate if args.offered_rate else 0.0
    next_t = time.monotonic()
    for i in range(args.requests):
        if stop_flag["sig"] is not None:
            break
        if interval:
            delay = next_t - time.monotonic()
            if delay > 0:
                time.sleep(delay)
            next_t += interval
        payload = {key: rows[int(rng.integers(pool))]}
        out["offered"] += 1
        try:
            futures.append(server.submit(payload))
        except ServeRejection as e:
            out[e.code] += 1
    for fut in futures:
        try:
            fut.result(timeout=60.0)
            out["completed"] += 1
        except ServeRejection as e:
            out[e.code] += 1
    return out


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--ckpt-dir", required=True)
    ap.add_argument("--step", type=int, default=None)
    ap.add_argument("--planted", action="store_true",
                    help="known-answer mode: planted closed-form image "
                         "tower (writes the reference checkpoint on "
                         "first run)")
    ap.add_argument("--arch", default="clip-vitb32-cc12m")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--modality", default="image",
                    choices=["image", "text"])
    ap.add_argument("--impl", default="chunked",
                    choices=["chunked", "flash", "naive"])
    ap.add_argument("--precision", default=None, choices=sorted(POLICIES))
    ap.add_argument("--classes", type=int, default=8)
    ap.add_argument("--per-class", type=int, default=8)
    ap.add_argument("--flip-frac", type=float, default=0.0)
    ap.add_argument("--seed", type=int, default=0)
    # engine knobs
    ap.add_argument("--max-batch", type=int, default=8)
    ap.add_argument("--max-wait-ms", type=float, default=2.0)
    ap.add_argument("--queue-capacity", type=int, default=64)
    ap.add_argument("--deadline-ms", type=float, default=None)
    ap.add_argument("--max-retries", type=int, default=2)
    ap.add_argument("--breaker-failures", type=int, default=3)
    ap.add_argument("--breaker-reset", type=float, default=1.0)
    ap.add_argument("--cache-capacity", type=int, default=1024)
    ap.add_argument("--watch-ckpt", type=float, default=None,
                    help="hot-reload poll interval in seconds")
    ap.add_argument("--chaos", default=None)
    # load generator
    ap.add_argument("--requests", type=int, default=32)
    ap.add_argument("--offered-rate", type=float, default=0.0,
                    help="requests/s (0 = as fast as possible)")
    ap.add_argument("--payload-pool", type=int, default=16,
                    help="distinct payloads to draw from (cache hits)")
    ap.add_argument("--watchdog-timeout", type=float, default=60.0)
    ap.add_argument("--json-out", default=None)
    args = ap.parse_args(argv)
    init_compile_cache()

    # SIGTERM: note it, stop offering; the drain below finishes every
    # admitted request before exit (same contract as launch.train).
    stop_flag = {"sig": None}

    def on_term(signum, frame):
        stop_flag["sig"] = signum
        print(f"[serve] received signal {signum}; draining", flush=True)
    signal.signal(signal.SIGTERM, on_term)

    chaos = parse_chaos(args.chaos, seed=args.seed)
    heartbeat = Heartbeat(os.path.join(args.ckpt_dir,
                                       "serve_heartbeat.json"),
                          interval=1.0)
    watchdog = StepWatchdog(args.watchdog_timeout, label="served batch")
    server, watcher, ds = build_server(args, chaos=chaos,
                                       heartbeat=heartbeat,
                                       watchdog=watchdog)
    try:
        client = run_load(server, ds, args, stop_flag)
    finally:
        if watcher is not None:
            watcher.stop()
        server.close()
        watchdog.close()
        heartbeat.close()
    stats = server.snapshot_stats()
    if watcher is not None:
        stats.update(watcher.stats)
    stats["client"] = client
    terminated = (client["completed"] + client["OVERLOADED"]
                  + client["DEADLINE"] + client["UNAVAILABLE"])
    stats["dropped"] = client["offered"] - terminated
    stats["sigterm"] = stop_flag["sig"] is not None
    print("SERVE_STATS " + json.dumps(stats, sort_keys=True))
    if args.json_out:
        with open(args.json_out, "w") as f:
            json.dump(stats, f)
    if stats["dropped"]:
        raise SystemExit(f"{stats['dropped']} requests dropped silently")
    return stats


if __name__ == "__main__":
    main()
