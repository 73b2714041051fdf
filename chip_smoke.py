"""Smoke run of the FastCLIP trainer on TPU: the main path, once, at
ViT-B/32 widths, checked against references computed on the chip.

    python chip_smoke.py              # one chip: phases `train`, `kernels`
    python chip_smoke.py --chips 4    # only the (data, fsdp) mesh phase

Phases:

  train    ``repro.launch.train.main`` in-process: clip-vitb32-cc12m at its
           published widths, FastCLIP-v3, AdamW, f32 towers, chunked
           attention, dense loss, global batch 512.  Every logged loss is
           finite, ``sat_rate`` is 0, the params moved and the master
           state stays f32.  Then the same step, timed on a device-resident
           batch.
  kernels  each Pallas kernel compiled for the chip against a plain JAX
           reference run on the chip, under "highest" matmul precision:
           the fused FCCO loss op against the dense one (b = d = 512, f32
           and bf16 embeddings) and flash attention against chunked
           attention at the image (S=50) and text (S=77) shapes; then one
           bf16 / flash / fused train step whose compiled program holds
           the kernels as ``tpu_custom_call``s.
  mesh     (``--chips 4``) 3 steps on ``--mesh data:2,fsdp:2`` at global
           batch 1024 against the same 3 steps, same seed and same batches,
           on one device, both under "highest" matmul precision: loss,
           params and log-u agree to 5e-3, and the params are sharded over
           4 devices.

Diagnostics go to earlier lines; the last line of standard output is
``{"ok": true, "device": {"platform", "kind", "count"}}``, printed only
when every phase passed.  Any failure raises, so the exit code is nonzero
and no such line appears; so does a run where JAX finds no TPU.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import math
import os
import statistics
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))

ARCH = "clip-vitb32-cc12m"
SEED = 0
TRAIN_BATCH = 512          # one chip
TRAIN_STEPS = 6
TIMED_STEPS = 5
MESH = "data:2,fsdp:2"
MESH_BATCH = 1024          # global; 256 per chip
MESH_STEPS = 3
MESH_TOL = 5e-3            # across layouts that are not bitwise
LOSS_B, LOSS_D = 512, 512  # FCCO loss op parity shape
ATTN_BATCH = 512
EPS, GAMMA, TAU = 1e-14, 0.5, 0.07
# (name, heads, seq, causal): the image and the text tower
ATTN_SHAPES = (("vit", 12, 50, False), ("text", 8, 77, True))


class CheckFailed(AssertionError):
    pass


def check(ok: bool, what: str) -> None:
    if not ok:
        raise CheckFailed(what)


def report(phase: str, **kv) -> None:
    print(f"[{phase}] " + json.dumps(kv, default=float), flush=True)


class CompileClock:
    """Sums JAX's backend-compile durations (a persistent-cache hit counts
    its retrieval time) and counts persistent-cache hits and misses."""

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

    def take(self) -> dict:
        out = {"compile_s": round(self.seconds, 3),
               "cache_hits": self.hits, "cache_misses": self.misses}
        self.seconds, self.hits, self.misses = 0.0, 0, 0
        return out


class _Tee:
    """stdout that also keeps each complete line with its arrival time."""

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


def run_launcher(argv):
    """``repro.launch.train.main(argv)`` -> (final state, [(t, step,
    metrics)]) from its ``step N epoch E {json}`` log lines."""
    from repro.launch import train as LT
    tee = _Tee(sys.stdout)
    with contextlib.redirect_stdout(tee):
        state = LT.main(argv)
    logged = []
    for t, line in tee.lines:
        parts = line.split(maxsplit=4)
        if len(parts) == 5 and parts[0] == "step" and parts[2] == "epoch":
            logged.append((t, int(parts[1]), json.loads(parts[4])))
    return state, logged


def peak_bytes(dev) -> int:
    return int((dev.memory_stats() or {}).get("peak_bytes_in_use", -1))


def max_diff(a, b) -> float:
    """Max |a - b| over matching leaves; equal entries (the -inf log-u of
    rows no batch touched) count as 0."""
    import jax
    import numpy as np
    out = 0.0
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b), strict=True):
        x = np.asarray(x, np.float64)
        y = np.asarray(y, np.float64)
        with np.errstate(invalid="ignore"):
            d = np.abs(x - y)
        d[x == y] = 0.0
        out = max(out, float(np.max(d)))
    return out


def step_setup(argv, n_shards):
    """The launcher's single-device step config and its batches for
    ``argv``, built from its own parser and config function, with a loader
    of ``n_shards``."""
    from repro.configs import get_arch
    from repro.data import ShardedLoader
    from repro.launch import train as LT
    args = LT.build_parser().parse_args(argv)
    cfg = get_arch(args.arch)
    ds = LT.build_dataset(cfg, "contrastive", args.n_samples, args.seq_len)
    loader = ShardedLoader(ds, global_batch=args.global_batch,
                           n_shards=n_shards, seed=args.seed)
    tc = LT.train_step_config(args, cfg, loader.steps_per_epoch,
                              sharded=False)
    return tc, loader


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------

def phase_train(dev, clock):
    import jax
    import jax.numpy as jnp
    from repro.configs import get_arch
    from repro.core import train_step as TS
    from repro.launch.steps import donated_jit
    from repro.models import backbones as BB
    argv = ["--arch", ARCH, "--version", "v3", "--global-batch",
            str(TRAIN_BATCH), "--steps", str(TRAIN_STEPS), "--log-every",
            "1", "--seed", str(SEED)]
    t0 = time.perf_counter()
    state, logged = run_launcher(argv)
    wall = time.perf_counter() - t0
    launch_compile = clock.take()
    check([s for _, s, _ in logged] == list(range(TRAIN_STEPS)),
          f"train: logged steps {[s for _, s, _ in logged]}")
    losses = [m["loss"] for _, _, m in logged]
    check(all(math.isfinite(x) for x in losses),
          f"train: non-finite loss in {losses}")
    sat = [m["sat_rate"] for _, _, m in logged]
    check(all(x == 0.0 for x in sat), f"train: sat_rate {sat}")
    TS.check_state_dtypes(state)
    init = BB.init_params(jax.random.PRNGKey(SEED), get_arch(ARCH))
    moved = max_diff(jax.device_get(state["params"]), init)
    check(moved > 0.0, "train: params did not move")
    # host clock between consecutive logged steps (each log line reads the
    # step's metrics, so it waits for the device): the loop as launched,
    # host input generation included
    gaps = [b[0] - a[0] for a, b in zip(logged[1:], logged[2:])]
    report("train", losses=losses, params_max_change=moved,
           wall_s=round(wall, 3), logged_step_s_median=round(
               statistics.median(gaps), 4), **launch_compile,
           peak_bytes_in_use=peak_bytes(dev))

    # the same step on a device-resident batch: device time, no host input
    tc, loader = step_setup(argv, n_shards=1)
    _, _, idx, batch = next(iter(loader.steps(1)))
    batch = {k: jnp.asarray(v) for k, v in batch.items()}
    idx = jnp.asarray(idx)
    step = donated_jit(TS.make_train_step(tc))
    t = time.perf_counter()
    state, m = step(state, batch, idx)
    jax.block_until_ready(state)
    first = time.perf_counter() - t
    warm_compile = clock.take()
    times = []
    for _ in range(TIMED_STEPS):
        t = time.perf_counter()
        state, m = step(state, batch, idx)
        jax.block_until_ready((state, m))
        times.append(time.perf_counter() - t)
    check(math.isfinite(float(m["loss"])), "train: timed step loss")
    report("train-timed", batch=TRAIN_BATCH,
           step_s=[round(x, 5) for x in times],
           step_s_median=round(statistics.median(times), 5),
           pairs_per_s=round(TRAIN_BATCH / statistics.median(times), 1),
           first_call_s=round(first, 3), **warm_compile,
           peak_bytes_in_use=peak_bytes(dev))


def _fcco_parity(dtype):
    """The FCCO loss op, fused Pallas kernels against the dense jnp math:
    loss, feature grads, new log-u rows and stats."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.core import distributed as D
    from repro.core import losses as LS
    ks = jax.random.split(jax.random.PRNGKey(SEED), 4)
    e1 = LS.l2_normalize(jax.random.normal(ks[0], (LOSS_B, LOSS_D)))
    e2 = LS.l2_normalize(jax.random.normal(ks[1], (LOSS_B, LOSS_D)))
    lu1 = jnp.log(jax.random.uniform(ks[2], (LOSS_B,)) + 0.1)
    lu2 = jnp.log(jax.random.uniform(ks[3], (LOSS_B,)) + 0.1)
    e1, e2 = e1.astype(dtype), e2.astype(dtype)
    outs, texts = {}, {}
    for impl in ("dense", "fused"):
        op = D.make_fcco_loss_op(None, EPS, True, loss_impl=impl)

        def run(a, c, op=op):
            loss, grads = jax.value_and_grad(
                lambda x, y: op(x, y, lu1, lu2, TAU, TAU, GAMMA)[0],
                argnums=(0, 1))(a, c)
            _, (lu1n, lu2n, stats, sat) = op(a, c, lu1, lu2, TAU, TAU,
                                             GAMMA)
            return loss, grads, lu1n, lu2n, stats, sat

        compiled = jax.jit(run).lower(e1, e2).compile()
        texts[impl] = compiled.as_text()
        outs[impl] = jax.device_get(compiled(e1, e2))
    check("tpu_custom_call" in texts["fused"],
          "kernels: fused loss op holds no tpu_custom_call")
    (ld, gd, l1d, l2d, std, satd), (lf, gf, l1f, l2f, stf, satf) = (
        outs["dense"], outs["fused"])
    errs = {"loss_abs": abs(float(lf) - float(ld)),
            "de_abs": max_diff(gf, gd),
            "de_max": max(float(np.max(np.abs(g))) for g in gd),
            "log_u_abs": max_diff((l1f, l2f), (l1d, l2d)),
            "stats_abs": max_diff(stf, std)}
    check(float(np.max(satf)) == 0.0 and float(np.max(satd)) == 0.0,
          "kernels: FCCO sat_rate")
    if dtype == jnp.float32:   # tests/test_fused_loss.py, f32 parity
        np.testing.assert_allclose(lf, ld, rtol=1e-5)
        for a, b in zip(gf, gd):
            np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6)
        # looser than the CPU test: on a TPU v5e the kernels' exp and
        # tile-wise sums put log-u up to 7.5e-6 from the dense path
        for a, b in zip((l1f, l2f), (l1d, l2d)):
            np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-5)
        for a, b in zip(stf, std):
            np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6)
    else:                      # tests/test_fused_loss.py, bf16 tolerance
        tol = 1e-2
        np.testing.assert_allclose(lf, ld, rtol=tol)
        for a, b in zip((l1f, l2f), (l1d, l2d)):
            np.testing.assert_allclose(a, b, atol=tol)
        for a, b in zip(gf, gd):
            np.testing.assert_allclose(
                np.asarray(a, np.float32), np.asarray(b, np.float32),
                atol=tol * float(np.max(np.abs(np.asarray(b, np.float32)))))
    return errs


def _attention_parity(heads, seq, causal, dtype):
    """flash_mha (Pallas forward, chunked-remat backward) against the
    chunked pure-JAX attention: forward, and for f32 the grads."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.kernels.flash_attention import flash_mha
    from repro.models.attention import chunked_attention
    ks = jax.random.split(jax.random.PRNGKey(SEED), 3)
    q, k, v = (jax.random.normal(kk, (ATTN_BATCH, seq, heads, 64))
               .astype(dtype) for kk in ks)
    fwd_flash = jax.jit(lambda a, b, c: flash_mha(a, b, c, causal=causal))
    text = fwd_flash.lower(q, k, v).compile().as_text()
    check("tpu_custom_call" in text,
          "kernels: flash holds no tpu_custom_call")
    o = fwd_flash(q, k, v)
    r = jax.jit(lambda a, b, c: chunked_attention(
        a.astype(jnp.float32), b.astype(jnp.float32),
        c.astype(jnp.float32), causal=causal))(q, k, v)
    fwd_err = float(jnp.max(jnp.abs(o.astype(jnp.float32) - r)))
    if dtype == jnp.float32:
        tol = dict(atol=1e-5)
    else:
        # tests/test_precision_flash.py's 1e-2, plus the bf16 rounding of
        # the output itself (half an ulp, 2**-8 relative): at the text
        # shape |o| reaches 4.9 and that rounding alone is 0.0128
        tol = dict(atol=1e-2, rtol=2 ** -8)
    np.testing.assert_allclose(np.asarray(o, np.float32), np.asarray(r),
                               **tol)
    out = {"fwd_abs": fwd_err}
    if dtype == jnp.float32:
        def grads(fn):
            return jax.jit(jax.grad(
                lambda a, b, c: jnp.sum(fn(a, b, c) ** 2),
                argnums=(0, 1, 2)))(q, k, v)
        gf = grads(lambda a, b, c: flash_mha(a, b, c, causal=causal))
        gc = grads(lambda a, b, c: chunked_attention(a, b, c,
                                                     causal=causal))
        out["grad_abs"] = max_diff(gf, gc)
        for a, b in zip(gf, gc):
            np.testing.assert_allclose(a, b, atol=1e-5)
    return out


def phase_kernels(dev, clock):
    import jax
    import jax.numpy as jnp
    from repro.core import train_step as TS
    from repro.launch.steps import donated_jit
    with jax.default_matmul_precision("highest"):
        for dtype in (jnp.float32, jnp.bfloat16):
            report("kernels", check="fcco_fused_vs_dense",
                   dtype=jnp.dtype(dtype).name, b=LOSS_B, d=LOSS_D,
                   **_fcco_parity(dtype))
        for name, heads, seq, causal in ATTN_SHAPES:
            for dtype in (jnp.float32, jnp.bfloat16):
                report("kernels", check=f"flash_vs_chunked/{name}",
                       dtype=jnp.dtype(dtype).name, seq=seq, heads=heads,
                       **_attention_parity(heads, seq, causal, dtype))

    # one train step with every kernel of the main path compiled in
    argv = ["--arch", ARCH, "--version", "v3", "--global-batch",
            str(TRAIN_BATCH), "--steps", "1", "--seed", str(SEED),
            "--precision", "bf16", "--impl", "flash", "--loss-impl",
            "fused"]
    tc, loader = step_setup(argv, n_shards=1)
    state = TS.init_train_state(jax.random.PRNGKey(SEED), tc)
    _, _, idx, batch = next(iter(loader.steps(1)))
    batch = {k: jnp.asarray(v) for k, v in batch.items()}
    idx = jnp.asarray(idx)
    clock.take()
    compiled = donated_jit(TS.make_train_step(tc)).lower(
        state, batch, idx).compile()
    n_kernels = compiled.as_text().count("tpu_custom_call")
    check(n_kernels > 0, "kernels: bf16/flash/fused step holds no "
                         "tpu_custom_call (kernels ran interpreted)")
    state, m = compiled(state, batch, idx)
    loss = float(m["loss"])
    check(math.isfinite(loss) and float(m["sat_rate"]) == 0.0,
          f"kernels: bf16/flash/fused step loss {loss} sat "
          f"{float(m['sat_rate'])}")
    TS.check_state_dtypes(state)
    report("kernels", check="bf16_flash_fused_step", loss=loss,
           tpu_custom_calls=n_kernels, **clock.take(),
           peak_bytes_in_use=peak_bytes(dev))


def phase_mesh(devs, clock):
    import jax
    import jax.numpy as jnp
    from repro.core import train_step as TS
    from repro.launch.steps import donated_jit
    argv = ["--arch", ARCH, "--version", "v3", "--global-batch",
            str(MESH_BATCH), "--steps", str(MESH_STEPS), "--log-every",
            "1", "--seed", str(SEED)]
    # full-f32 matmuls on both sides: the check is that the two layouts
    # compute the same step, not how two bf16-pass roundings differ
    with jax.default_matmul_precision("highest"):
        state, logged = run_launcher(argv + ["--mesh", MESH])
    TS.set_mesh(None)      # the launcher leaves its mesh in the module
    mesh_compile = clock.take()
    leaves = jax.tree.leaves(state["params"])
    on = {d for x in leaves for d in x.devices()}
    sharded = [x for x in leaves
               if x.addressable_shards[0].data.shape != x.shape]
    check(len(on) == 4 and sharded and all(
        len({s.device for s in x.addressable_shards}) == 4
        for x in sharded), "mesh: params are not sharded over 4 devices")
    n_sharded = len(sharded)
    mesh_loss = [m["loss"] for _, _, m in logged]
    mesh_params = jax.device_get(state["params"])
    mesh_u = jax.device_get((state["fc"]["u1"], state["fc"]["u2"]))
    del state, leaves, sharded

    # reference: the same steps and batches (the 4-shard loader's order)
    # on one device
    tc, loader = step_setup(argv, n_shards=4)
    ref = TS.init_train_state(jax.random.PRNGKey(SEED), tc)
    step = donated_jit(TS.make_train_step(tc))
    ref_loss = []
    with jax.default_matmul_precision("highest"):
        for _, _, idx, batch in loader.steps(MESH_STEPS):
            ref, m = step(ref, {k: jnp.asarray(v)
                                for k, v in batch.items()},
                          jnp.asarray(idx))
            ref_loss.append(float(m["loss"]))
    diffs = {"loss": max(abs(a - b) for a, b in zip(mesh_loss, ref_loss)),
             "params": max_diff(mesh_params, jax.device_get(ref["params"])),
             "log_u": max_diff(mesh_u, jax.device_get(
                 (ref["fc"]["u1"], ref["fc"]["u2"])))}
    report("mesh", mesh=MESH, global_batch=MESH_BATCH, mesh_loss=mesh_loss,
           ref_loss=ref_loss, max_diff=diffs, tol=MESH_TOL,
           sharded_param_leaves=n_sharded,
           **mesh_compile, ref_compile=clock.take(),
           peak_bytes_in_use=[peak_bytes(d) for d in devs[:4]])
    check(len(mesh_loss) == MESH_STEPS and all(
        math.isfinite(x) for x in mesh_loss), f"mesh: losses {mesh_loss}")
    for k, v in diffs.items():
        check(v <= MESH_TOL, f"mesh: {k} differs by {v} > {MESH_TOL}")


# ---------------------------------------------------------------------------

def require_tpu(chips: int):
    """The devices to run on; raises SystemExit without a TPU."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SystemExit(f"chip_smoke: JAX found no TPU (platform "
                         f"{devs[0].platform!r}); nothing was run")
    if len(devs) < chips:
        raise SystemExit(f"chip_smoke: --chips {chips} but JAX sees "
                         f"{len(devs)} TPU device(s)")
    return devs


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: run only the (data, fsdp) mesh phase and its "
                         "single-device reference")
    args = ap.parse_args(argv)
    devs = require_tpu(args.chips)
    sys.path.insert(0, os.path.join(ROOT, "src"))
    from repro.launch.compile_cache import init_compile_cache
    report("setup", platform=devs[0].platform, kind=devs[0].device_kind,
           devices=len(devs), compile_cache=init_compile_cache())
    clock = CompileClock()
    t0 = time.perf_counter()
    if args.chips == 4:
        phase_mesh(devs, clock)
    else:
        phase_train(devs[0], clock)
        phase_kernels(devs[0], clock)
    report("done", wall_s=round(time.perf_counter() - t0, 1))
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": args.chips}}), flush=True)


if __name__ == "__main__":
    main()
