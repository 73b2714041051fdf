"""Training launcher.

Single-process CPU/TPU entry point for the contrastive (FastCLIP) and LM
objectives on synthetic data, with checkpointing and periodic eval.

    PYTHONPATH=src python -m repro.launch.train \
        --arch clip-vitb32-cc12m --version v3 --steps 200 --reduced \
        [--objective contrastive|lm] [--ckpt-dir ckpts] [--resume]

``--mesh data:N[,fsdp:M]`` runs the contrastive trainer on the named
(data, fsdp) mesh (``core.shard_state`` contract): batch + FCCO u state
sharded by sample ownership over all N*M devices, params and optimizer
moments ZeRO-sharded over fsdp with reduce-scatter gradient reduction,
per-shard checkpoints (restorable at any other mesh shape), and the
periodic eval consuming the sharded params in place.

Multi-host (PR 10): ``--coordinator HOST:PORT --num-processes N
--process-id K`` joins the launcher to a ``jax.distributed`` process
group before any device use — the mesh then covers every *global*
device (node-aware: the ``fsdp`` axis never spans processes, so the
weight all-gathers and gradient reduce-scatters stay intra-node and
only shard-sized data-axis psums cross nodes — the hierarchical
reduction).  Each process assembles only its own rows of the global
batch (``ShardedLoader.owned_shards``), checkpoints go through the
rank-tagged multi-process format (every rank writes its sample-sharded
blocks; rank 0 commits the sidecar + ``latest`` after a cross-rank
barrier), and only process 0 writes the heartbeat file.  On CPU,
``--local-devices L`` forces L host devices per process — ``python -m
repro.launch.multiprocess --nproc 2 --local-devices 2 -- <train args>``
spawns the whole group locally, and a 2-process x 2-device run tracks
the single-process ``--mesh data:2,fsdp:2`` run to 5e-3 in
loss/params/log-u over the test horizon (not bitwise: batch assembly,
init, placement and the all-gathers are proven bit-identical across
topologies, but XLA:CPU compiles a topology-dependent executable and
the gloo collective runtime combines chunked reductions in completion
order — see tests/helpers/multihost_check.py).  Leaving the flags
unset is the single-process fallback — bit-identical to pre-PR-10
behavior.

``--microbatch N`` splits each device batch into N micro-steps inside
the fsdp train step so that micro-step i's weight all-gather and
gradient reduce-scatter overlap micro-step i±1's tower compute
(comm/compute overlap); gradients accumulate shard-locally and the
FCCO log-u state still updates exactly once per global step from the
full batch's embeddings, so the per-sample contract is unchanged.
``--microbatch 1`` (default) is the unpipelined step, bit-identical to
pre-PR-10; N > 1 matches it within accumulation-order rounding.

Training resilience (PR 6, ``repro.resilience``) — the limited-resource
contract: runs on preemptible/shared machines survive kills, corrupt
disks and numerically bad steps.

  ``--guard``
      In-jit non-finite step guard: an all-finite check over the step
      loss and the global gradient norm turns a bad step into a no-op
      update.  **Invariant: a skipped step leaves the whole train state
      bit-identical to its pre-step value** — params, optimizer
      moments, the FCCO log-u buffers, and every counter (the schedules
      replay the same lr/gamma on the next batch).  The ``skipped`` and
      ``nonfinite_rate`` metrics report it; the loader/prefetch stream
      is keyed on its own step index, so a skipped step never desyncs
      data from state.
  ``--rollback-after N``
      Host-side escalation (implies ``--guard``): a robust-EMA loss
      spike detector counts consecutive bad steps (skipped, non-finite,
      or spiking); at N it restores the last verified checkpoint and
      rebuilds the deterministic loader stream at that step (O(1)
      index-only fast-forward), so the replay reproduces the
      uninterrupted trajectory.
  ``--ckpt-async``
      Durable async checkpoints: leaves snapshot to host synchronously,
      compression + the atomic tmp-file/``os.replace`` writes (array
      files, CRC32-digest sidecar, ``latest`` marker — in that order)
      run on a background thread, so the step loop never blocks on
      ``np.savez_compressed``.  ``--resume`` only ever restores a step
      that passes digest verification, falling back to the newest
      verified one past any crash-truncated write.
  ``--ckpt-keep K [--ckpt-keep-every N]``
      Retention: keep the newest K checkpoints (plus every N-th),
      delete the rest after each save.
  SIGTERM / SIGINT (preemption)
      The loop finishes the in-flight step, writes a final synchronous
      checkpoint, shuts the prefetcher down cleanly, and exits 0.
  ``--heartbeat-file F`` / ``--hang-timeout S``
      Liveness: F is atomically rewritten with {step, time, pid} every
      few seconds (default: ``<ckpt-dir>/heartbeat.json``); with S > 0
      a watchdog thread dumps all stacks to stderr when no step
      completes for S seconds (it never kills the run).
  ``--chaos SPEC``
      Deterministic fault injection (``repro.resilience.chaos``) for
      the crash-recovery battery: NaN-poison a batch, raise in the
      loader or a streaming decode worker, SIGKILL before a step or
      mid-checkpoint-write.

Streaming data + curricula (PR 7, ``repro.data.streaming`` /
``repro.data.curriculum``) — feeding scales past host memory:

  ``--data streaming:<dir>``
      Read (index, batch) streams from a shard directory (fixed-size
      records + index sidecar; write one with ``python -m
      repro.data.streaming``) instead of the in-memory synthetic
      dataset.  Decode/augment runs on a bounded worker pool
      (``--decode-workers``/``--decode-ahead``) with per-sample
      counter-based RNG; the loader keeps the exact ShardedLoader
      index contract — sample ownership (the FCCO u-shard layout),
      O(1)-per-step resume fast-forward and SIGKILL+``--resume``
      bit-identity all survive unchanged, and a stream materialized
      from the synthetic dataset trains bit-identically to the
      in-memory run.  ``--n-samples`` is taken from the shard index.
      The default ``--prefetch`` deepens to 4 (decode pipelines behind
      the H2D double-buffer).
  ``--image-size-schedule 0:16,300:32`` / ``--context-schedule 0:8``
      Step-keyed curricula (RECLIP-style small-image training and
      inverse-scaling-law token-length reduction): host-side exact
      block-mean image pooling / context truncation; the towers adapt
      their positional tables (pooled patch grid, sliced text prefix).
      Scheduled values must divide the native sizes; each stage is one
      extra jit compile.

With ``--prefetch`` > 0 the launcher ends (also after a SIGTERM drain)
with ``input: queue empty at E of A asks, waited S s``: of the A batches
the loop asked for, E found the prefetch queue empty, and the loop spent
S seconds blocked on it.  E close to A means the run is input-bound.
A line of its own follows, ``noise: P of F fills on W threads``: of the
F per-sample noise fills that made this run's batches, P split their
rows over the data layer's W-thread pool (``repro.data.rng``); the
others were too small to gain from it.
The input path's profiler spans and the towers', loss op's and
optimizer's scopes are listed in ``repro.tracing``.
"""
from __future__ import annotations

import argparse
import json
import signal
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import checkpoint as CK
from repro import resilience as RS
from repro.configs import INPUT_SHAPES, get_arch
from repro.core import fastclip as FC
from repro.core import shard_state as SS
from repro.core import train_step as TS
from repro.core.schedules import lr_warmup_cosine
from repro.data import (ContrastiveDataset, DevicePrefetcher, InputWaits,
                        LMDataset, PairedEmbeddingDataset, ShardedLoader,
                        StreamingDataset, StreamingLoader, device_array)
from repro.data import curriculum as CU
from repro.data import rng as DR
from repro.launch import multiprocess as MP
from repro.launch.compile_cache import init_compile_cache
from repro.launch.steps import donated_jit
from repro.models import backbones as BB
from repro.models.precision import POLICIES
from repro.optim import get_optimizer


def build_dataset(cfg, objective, n, seq_len, data="synthetic"):
    if data.startswith("streaming:"):
        return StreamingDataset(data.split(":", 1)[1])
    if data != "synthetic":
        raise SystemExit(f"--data {data!r}: want 'synthetic' or "
                         "'streaming:<shard-dir>'")
    if cfg.family == "clip":
        return ContrastiveDataset(n=n, image_size=cfg.clip.image_size,
                                  context_length=cfg.clip.context_length,
                                  vocab_size=cfg.vocab_size, n_classes=64)
    if objective == "contrastive":
        return PairedEmbeddingDataset(n=n, seq_len=seq_len,
                                      vocab_size=cfg.vocab_size)
    return LMDataset(n=n, seq_len=seq_len, vocab_size=cfg.vocab_size)


def check_resume_metadata(meta, arch: str, version: str) -> None:
    """Refuse to restore a checkpoint written by a different run shape.

    Restoring a v2 checkpoint into a v3 run (or another --arch) fails
    late with an opaque shape error at best and silently mis-trains at
    worst; compare the sidecar metadata up front and exit with a clear
    message.  Checkpoints without the keys (foreign writers) are let
    through on the old shape-check-only behavior."""
    for key, want in (("arch", arch), ("version", version)):
        got = meta.get(key)
        if got is not None and got != want:
            raise SystemExit(
                f"--resume: checkpoint metadata has {key}={got!r} but "
                f"this run was launched with --{key} {want}; restoring "
                "would mismatch the state layout.  Relaunch with "
                f"--{key} {got} or point --ckpt-dir at a fresh "
                "directory.")


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="clip-vitb32-cc12m")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--version", default="v3", choices=FC.VERSIONS)
    ap.add_argument("--objective", default="contrastive",
                    choices=["contrastive", "lm"])
    ap.add_argument("--optimizer", default="adamw")
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--global-batch", type=int, default=64)
    ap.add_argument("--seq-len", type=int, default=32)
    ap.add_argument("--n-samples", type=int, default=2048)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--wd", type=float, default=0.1)
    ap.add_argument("--rho", type=float, default=6.5)
    ap.add_argument("--eps", type=float, default=1e-14)
    ap.add_argument("--gamma-min", type=float, default=0.2)
    ap.add_argument("--reduction", default="fastclip",
                    choices=["fastclip", "allgather_ad"])
    ap.add_argument("--loss-impl", default=None,
                    choices=["dense", "fused"],
                    help="loss-layer math: dense jnp or fused Pallas "
                         "kernels (interpret mode off-TPU); unset defers "
                         "to FastCLIPConfig.loss_impl (dense)")
    ap.add_argument("--precision", default=None, choices=sorted(POLICIES),
                    help="tower mixed-precision policy (bf16 compute, f32 "
                         "masters + f32 loss layer); unset defers to "
                         "ArchConfig.precision (f32)")
    ap.add_argument("--impl", default="chunked",
                    choices=["chunked", "flash", "naive"],
                    help="training attention: pure-JAX chunked online "
                         "softmax, the Pallas flash kernel (interpret "
                         "mode off-TPU), or the O(S^2) oracle")
    ap.add_argument("--data", default="synthetic",
                    help="'synthetic' (in-memory, default) or "
                         "'streaming:<dir>' — a shard directory written "
                         "by `python -m repro.data.streaming` (decode/"
                         "augment on the fly, same ownership contract)")
    ap.add_argument("--decode-workers", type=int, default=4,
                    help="streaming decode worker threads")
    ap.add_argument("--decode-ahead", type=int, default=4,
                    help="streaming batches decoded ahead of the step "
                         "loop (bounded pipeline depth)")
    ap.add_argument("--image-size-schedule", default=None,
                    help="resolution curriculum 'STEP:SIZE[,...]' "
                         "(block-mean shrink; sizes must divide the "
                         "native image size)")
    ap.add_argument("--context-schedule", default=None,
                    help="text-context curriculum 'STEP:LEN[,...]' "
                         "(prefix truncation)")
    ap.add_argument("--prefetch", type=int, default=None,
                    help="host->device prefetch depth (0 disables; "
                         "default 2, or 4 under --data streaming)")
    ap.add_argument("--mesh", default=None,
                    help="data:N[,fsdp:M] — run the contrastive step on "
                         "the named (data, fsdp) mesh: batch/u sharded "
                         "over all N*M devices, params+moments ZeRO-"
                         "sharded over fsdp (reduce-scatter grads, "
                         "sharded checkpoints); unset = single-device")
    ap.add_argument("--microbatch", type=int, default=1,
                    help="split each device batch into N micro-steps in "
                         "the fsdp step so the next micro-step's weight "
                         "all-gather / grad reduce-scatter overlaps the "
                         "current one's compute; 1 = unpipelined "
                         "(bit-identical baseline)")
    ap.add_argument("--coordinator", default=None,
                    help="HOST:PORT of process 0: join a jax.distributed "
                         "process group before any device use "
                         "(repro.launch.multiprocess spawns CPU groups)")
    ap.add_argument("--num-processes", type=int, default=1,
                    help="total processes in the jax.distributed group")
    ap.add_argument("--process-id", type=int, default=0,
                    help="this process's rank in [0, --num-processes)")
    ap.add_argument("--local-devices", type=int, default=None,
                    help="force this many host (CPU) devices per process "
                         "(--xla_force_host_platform_device_count) — the "
                         "CPU multi-process harness and test batteries "
                         "set this")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=100)
    ap.add_argument("--ckpt-async", action="store_true",
                    help="write checkpoints on a background thread "
                         "(synchronous host snapshot, async compress + "
                         "atomic write); the step loop never blocks")
    ap.add_argument("--ckpt-keep", type=int, default=0,
                    help="retention: keep only the newest K checkpoints "
                         "(0 = keep all)")
    ap.add_argument("--ckpt-keep-every", type=int, default=0,
                    help="with --ckpt-keep: additionally keep every N-th "
                         "step forever")
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--guard", action="store_true",
                    help="in-jit non-finite step guard: a bad step "
                         "(non-finite loss or grad norm) becomes a "
                         "bitwise no-op update, reported via the "
                         "skipped/nonfinite_rate metrics")
    ap.add_argument("--rollback-after", type=int, default=0,
                    help="roll back to the last checkpoint after N "
                         "consecutive bad steps (robust-EMA spike "
                         "detector; 0 disables; implies --guard)")
    ap.add_argument("--heartbeat-file", default=None,
                    help="liveness file, atomically rewritten with "
                         "{step, time, pid} (default: <ckpt-dir>/"
                         "heartbeat.json when --ckpt-dir is set)")
    ap.add_argument("--hang-timeout", type=float, default=0.0,
                    help="watchdog: dump all thread stacks when no step "
                         "completes for this many seconds (0 disables)")
    ap.add_argument("--chaos", default=None,
                    help="fault-injection spec (repro.resilience.chaos), "
                         "e.g. 'nan_batch@5,kill_save@mid_npz' — test "
                         "battery use only")
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--eval-every", type=int, default=0,
                    help="run the zero-shot/retrieval eval engine every N "
                         "steps (clip family; 0 disables).  Uses the same "
                         "--impl/--precision fast path as training")
    ap.add_argument("--eval-classes", type=int, default=8)
    ap.add_argument("--eval-per-class", type=int, default=8)
    ap.add_argument("--eval-batch", type=int, default=64)
    ap.add_argument("--seed", type=int, default=0)
    return ap


def train_step_config(args, cfg, steps_per_epoch: int,
                      sharded: bool) -> TS.TrainStepConfig:
    """The contrastive train step the flags describe; ``sharded`` selects
    the (data, fsdp) mesh step over the single-device one."""
    fc = FC.FastCLIPConfig(
        version=args.version, n_samples=args.n_samples, rho=args.rho,
        eps=args.eps, gamma_min=args.gamma_min,
        tau_init=0.07 if args.version == "v3" else 0.03,
        lr_tau=2e-4 if args.version == "v3" else 1e-2,
        steps_per_epoch=steps_per_epoch,
        gamma_decay_epochs=max(1, args.steps // (2 * steps_per_epoch)))
    return TS.TrainStepConfig(
        arch=cfg, fc=fc, optimizer=get_optimizer(args.optimizer),
        lr_fn=lr_warmup_cosine(args.lr, min(500, args.steps // 10 + 1),
                               args.steps),
        wd=args.wd, reduction=args.reduction,
        loss_impl=args.loss_impl, impl=args.impl,
        precision=args.precision,
        mesh_axes=SS.TRAIN_AXES if sharded else None,
        fsdp=sharded, microbatch=args.microbatch,
        guard=args.guard or args.rollback_after > 0)


def main(argv=None):
    args = build_parser().parse_args(argv)

    multiproc = args.num_processes > 1 or bool(args.coordinator)
    if multiproc:
        if not args.mesh:
            raise SystemExit(
                "--num-processes > 1 requires --mesh data:N[,fsdp:M]: "
                "the multi-host trainer is the sharded contrastive step")
        if args.eval_every:
            raise SystemExit(
                "--eval-every is not supported under multi-process runs "
                "yet; run the eval launcher against the saved "
                "checkpoints instead")
    # must happen before any jax device use (backend init is lazy)
    MP.initialize(args.coordinator, args.num_processes, args.process_id,
                  args.local_devices)
    init_compile_cache()
    devs = jax.devices()
    # kernels run compiled only on a TPU; say which backend this run got
    print(f"devices: platform={devs[0].platform} "
          f"kind={devs[0].device_kind} count={len(devs)}", flush=True)

    cfg = get_arch(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    streaming = args.data.startswith("streaming:")
    ds = build_dataset(cfg, args.objective, args.n_samples, args.seq_len,
                       data=args.data)
    if streaming:
        args.n_samples = ds.n    # FCCO u sizing follows the shard index
    if args.prefetch is None:
        args.prefetch = 4 if streaming else 2
    image_sched = CU.parse_schedule(args.image_size_schedule)
    context_sched = CU.parse_schedule(args.context_schedule)
    chaos = RS.parse_chaos(args.chaos, seed=args.seed)

    mesh = None
    shardings = None
    if args.mesh:
        if args.objective == "lm" and cfg.family != "clip":
            raise SystemExit("--mesh drives the contrastive trainer; the "
                             "LM shapes run on the production mesh via "
                             "repro.launch.dryrun")
        data_sz, fsdp_sz = SS.parse_mesh_arg(args.mesh)
        mesh = SS.make_train_mesh(data_sz, fsdp_sz)
        TS.set_mesh(mesh)
    n_shards = data_sz * fsdp_sz if mesh is not None else 1
    mp_mesh = mesh is not None and SS.is_multiprocess(mesh)
    pidx = jax.process_index() if mp_mesh else 0
    pcnt = jax.process_count() if mp_mesh else 1
    owned = None
    if mp_mesh:
        # global shard s lives on jax.devices()[s] (the mesh covers every
        # global device, process-grouped): this process owns one
        # contiguous run of shards — and so of global batch rows
        lcl = jax.local_device_count()
        owned = tuple(range(pidx * lcl, (pidx + 1) * lcl))
    if streaming:
        loader = StreamingLoader(
            ds, global_batch=args.global_batch, n_shards=n_shards,
            seed=args.seed, owned_shards=owned,
            workers=args.decode_workers,
            decode_ahead=args.decode_ahead,
            fault_hook=chaos.on_decode if chaos is not None else None)
    else:
        loader = ShardedLoader(ds, global_batch=args.global_batch,
                               n_shards=n_shards, seed=args.seed,
                               owned_shards=owned)

    if args.objective == "lm" and cfg.family != "clip":
        from repro.launch.steps import make_lm_train_step
        step_fn, opt = make_lm_train_step(cfg, lr=args.lr, wd=args.wd,
                                          total_steps=args.steps,
                                          impl=args.impl,
                                          precision=args.precision)
        params = BB.init_params(jax.random.PRNGKey(args.seed), cfg)
        state = {"params": params, "opt": opt.init(params),
                 "step": jnp.zeros((), jnp.int32)}
        jit_step = donated_jit(step_fn)

        def run_step(state, idx, batch):
            return jit_step(state, batch)
    else:
        tc = train_step_config(args, cfg, loader.steps_per_epoch,
                               sharded=mesh is not None)
        state = TS.init_train_state(jax.random.PRNGKey(args.seed), tc)
        if mesh is not None:
            from jax.sharding import NamedSharding
            state, shardings = SS.shard_train_state(state, mesh)
            sample_sh = NamedSharding(mesh, SS.SAMPLE_SPEC)
            rep_sh = NamedSharding(mesh, jax.sharding.PartitionSpec())
            jit_step = donated_jit(
                TS.make_train_step(tc),
                in_shardings=(shardings, sample_sh, sample_sh),
                out_shardings=(shardings, rep_sh))
        else:
            jit_step = donated_jit(TS.make_train_step(tc))

        def run_step(state, idx, batch):
            return jit_step(state, batch, jnp.asarray(idx))

    def relayout(host_state):
        """Host-restored state back onto this run's devices/mesh (the
        reshard round-trip: any saving mesh shape restores bit-exactly).
        ``put_global`` handles cross-process shardings (every rank reads
        the same merged checkpoint from the shared filesystem) and is a
        plain per-leaf device_put on a single-process mesh."""
        if mesh is not None:
            return SS.put_global(host_state, shardings)
        return jax.tree.map(jnp.asarray, host_state)

    start = 0
    if args.resume and args.ckpt_dir and CK.latest_step(args.ckpt_dir):
        like = jax.tree.map(jnp.zeros_like, state)
        state, start, ck_meta = CK.restore(args.ckpt_dir, like)
        check_resume_metadata(ck_meta, args.arch, args.version)
        state = relayout(state)
        print(f"resumed from step {start}")

    evaluator = None
    if args.eval_every and cfg.family == "clip":
        from repro.data import ZeroShotEvalDataset
        from repro.eval import ClipEvaluator
        eval_ds = ZeroShotEvalDataset(
            n_classes=args.eval_classes, n_per_class=args.eval_per_class,
            image_size=cfg.clip.image_size,
            context_length=cfg.clip.context_length,
            vocab_size=cfg.vocab_size, seed=args.seed + 1)
        evaluator = ClipEvaluator(
            cfg, eval_ds, impl=args.impl, precision=args.precision,
            batch_size=args.eval_batch,
            loss_impl=args.loss_impl or "dense",
            param_shardings=shardings["params"] if shardings else None)

    def run_eval(step):
        em = evaluator.evaluate(state["params"], cache_key=int(step))
        print(f"eval  {step:5d} " + json.dumps(
            {k: round(v, 5) for k, v in sorted(em.items())}), flush=True)

    def to_device(item):
        epoch, step, idx, batch = item
        if mp_mesh:
            # every process holds the full (global) index plan but only
            # its own rows of the batch: assemble global device arrays
            # from the process-local pieces
            idx_np = np.asarray(idx)
            idx_dev = jax.make_array_from_callback(
                idx_np.shape, sample_sh, lambda i, a=idx_np: a[i])
            dev_batch = {
                k: jax.make_array_from_process_local_data(
                    sample_sh, np.asarray(v),
                    (len(idx_np),) + v.shape[1:])
                for k, v in batch.items()}
            return epoch, step, idx_dev, dev_batch
        # the async H2D copies are dispatched on the producer thread
        return (epoch, step, jnp.asarray(idx),
                {k: device_array(v) for k, v in batch.items()})

    def host_stream(from_step):
        for epoch, step, idx, batch in loader.steps(args.steps,
                                                    start=from_step):
            if chaos is not None:
                chaos.on_loader(step)
                batch = chaos.poison_batch(step, batch)
            batch = CU.apply_curriculum(batch, step, image_sched,
                                        context_sched)
            yield epoch, step, idx, batch

    # every stream's waits for a batch, for the ``input:`` line at the end
    waits = InputWaits()

    def make_stream(from_step):
        it = host_stream(from_step)
        if args.prefetch > 0:
            return DevicePrefetcher(it, depth=args.prefetch,
                                    transform=to_device, waits=waits)
        return map(to_device, it)

    def close_stream(s):
        if isinstance(s, DevicePrefetcher):
            s.close()   # release the producer on early exit too

    # -- resilience plumbing ------------------------------------------------
    meta = {"arch": args.arch, "version": args.version}
    saver = (CK.AsyncCheckpointer(args.ckpt_dir, keep_last=args.ckpt_keep,
                                  keep_every=args.ckpt_keep_every,
                                  process_index=pidx, process_count=pcnt)
             if args.ckpt_dir and args.ckpt_async else None)
    if chaos is not None:
        CK.set_fault_hook(chaos.checkpoint_event)

    def save_ckpt(step_no, sync=False):
        if saver is not None and not sync:
            saver.save(state, step_no, metadata=meta,
                       sharded=mesh is not None)
        else:
            if saver is not None:
                saver.wait()
            if mesh is not None:
                CK.save_sharded(args.ckpt_dir, state, step_no,
                                metadata=meta, process_index=pidx,
                                process_count=pcnt)
            else:
                CK.save(args.ckpt_dir, jax.device_get(state), step_no,
                        metadata=meta)
            if args.ckpt_keep > 0 and pidx == 0:
                CK.prune_checkpoints(args.ckpt_dir,
                                     keep_last=args.ckpt_keep,
                                     keep_every=args.ckpt_keep_every)

    hb_path = args.heartbeat_file or (
        f"{args.ckpt_dir}/heartbeat.json" if args.ckpt_dir else None)
    # only the primary writes the heartbeat: ranks sharing a filesystem
    # would otherwise clobber each other's {step, time, pid} records
    hb = RS.Heartbeat(hb_path) if hb_path and pidx == 0 else None
    wd = (RS.StepWatchdog(args.hang_timeout)
          if args.hang_timeout > 0 else None)
    detector = RS.SpikeDetector(rollback_after=args.rollback_after)
    received = {"sig": None}

    def on_signal(signum, frame):
        received["sig"] = signum    # honored between steps: clean exit

    prev_handlers = {}
    for s in (signal.SIGTERM, signal.SIGINT):
        try:
            prev_handlers[s] = signal.signal(s, on_signal)
        except ValueError:          # not the main thread (embedded call)
            pass

    t0 = time.time()
    first = True
    done = start
    preempted = False
    fills0 = DR.fills()
    stream = make_stream(start)
    try:
        running = True
        while running:
            running = False         # re-armed only by a rollback
            for epoch, step, idx, batch in stream:
                if received["sig"] is not None:
                    preempted = True
                    break
                if chaos is not None:
                    chaos.pre_step(step)
                state, m = run_step(state, idx, batch)
                done = step + 1
                if first:
                    # params/opt/FCCO-u must stay f32 masters under any
                    # policy
                    TS.check_state_dtypes(state)
                    first = False
                if hb is not None:
                    hb.beat(step)
                if wd is not None:
                    wd.beat()
                if step % args.log_every == 0 or step == args.steps - 1:
                    msg = {k: round(float(v), 5) for k, v in m.items()}
                    print(f"step {step:5d} epoch {epoch} "
                          f"{json.dumps(msg)}", flush=True)
                if detector.update(float(m["loss"]),
                                   float(m.get("skipped", 0.0)) >= 0.5):
                    if saver is not None:
                        saver.wait()
                    rb = (CK.latest_step(args.ckpt_dir)
                          if args.ckpt_dir else None)
                    if rb is None:
                        print(f"step {step:5d} {detector.consecutive_bad}"
                              " consecutive bad steps but no checkpoint "
                              "to roll back to; continuing", flush=True)
                        detector.reset()
                    else:
                        like = jax.tree.map(jnp.zeros_like, state)
                        state, rb, _ = CK.restore(args.ckpt_dir, like)
                        state = relayout(state)
                        detector.reset()
                        close_stream(stream)
                        stream = make_stream(rb)
                        done = rb
                        print(f"rollback: {args.rollback_after} "
                              f"consecutive bad steps; restored verified "
                              f"step {rb}, replaying the deterministic "
                              "stream from there", flush=True)
                        running = True
                        break
                if (evaluator is not None
                        and (step + 1) % args.eval_every == 0):
                    run_eval(step + 1)
                if args.ckpt_dir and (step + 1) % args.ckpt_every == 0:
                    save_ckpt(step + 1)
    finally:
        close_stream(stream)
        if wd is not None:
            wd.close()
        if hb is not None:
            hb.close()
        if chaos is not None:
            CK.set_fault_hook(None)
        for s, h in prev_handlers.items():
            signal.signal(s, h)
    if args.prefetch > 0:
        print(waits, flush=True)
    print(DR.fills().since(fills0), flush=True)

    if preempted:
        # preemption contract: final synchronous checkpoint, clean
        # shutdown, exit 0 — the resumed run replays from `done`
        if args.ckpt_dir:
            save_ckpt(done, sync=True)
        if saver is not None:
            saver.close()
        print(f"preempted (signal {received['sig']}): saved synchronous "
              f"checkpoint at step {done}, exiting cleanly", flush=True)
        return state

    dt = time.time() - t0
    print(f"trained {args.steps - start} steps in {dt:.1f}s "
          f"({(args.steps - start) / max(dt, 1e-9):.2f} steps/s)")

    if cfg.family == "clip" or args.objective == "contrastive":
        eval_batch = {k: jnp.asarray(v)
                      for k, v in ds.batch(np.arange(
                          min(128, args.n_samples))).items()}
        # the ad-hoc metric runs eagerly on one device; merge the shards
        # from this process's addressable pieces (params are fsdp-sharded
        # + data-replicated, so every rank can recover them locally —
        # jax.device_get would raise on a multi-process mesh)
        params = (jax.tree.map(SS.host_local_value, state["params"])
                  if mesh is not None else state["params"])
        acc = float(TS.retrieval_accuracy(params, cfg, eval_batch))
        print(f"retrieval accuracy: {acc:.4f}")
    if evaluator is not None and args.steps % args.eval_every != 0:
        run_eval(args.steps)   # final eval unless the loop just ran it
    if args.ckpt_dir:
        save_ckpt(args.steps, sync=True)
    if saver is not None:
        saver.close()
    return state


if __name__ == "__main__":
    main()
