"""Train-step assembly: model towers + FastCLIP objective + optimizers.

Composition, three mesh settings:
  - ``mesh_axes=None``: single-device reference semantics (unit tests,
    CPU-scale experiments);
  - ``mesh_axes`` set, ``fsdp=False``: the *model* forward/backward runs
    under pjit/GSPMD (batch sharded over the axes, weights per the
    sharding rules in repro.launch.mesh) while the *contrastive loss*
    runs in a shard_map island over the batch axes, using either the
    paper's communication-efficient reduction or the OpenCLIP-style
    autodiff reduction (repro.core.distributed);
  - ``fsdp=True``: the production (data, fsdp) named-mesh path
    (``make_fsdp_train_step``): the WHOLE step — towers, loss island,
    gradient reduction, optimizer — runs inside one shard_map with the
    train state ZeRO-sharded per repro.core.shard_state (weight
    all-gather at use, psum_scatter gradient reduction, shard-local
    optimizer update).

In every setting the FCCO u state (and v2's individual temperatures) is
sharded by sample ownership and updated shard-locally.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Callable, Optional, Sequence

import jax
import jax.numpy as jnp

from repro import tracing as TR
from repro.configs.base import ArchConfig
from repro.core import distributed as D
from repro.core import fastclip as FC
from repro.core import losses as LS
from repro.models import backbones as BB
from repro.models import precision as PR
from repro.optim import Optimizer, clip_by_global_norm, global_norm
from repro.resilience import guard as RG

sg = jax.lax.stop_gradient


# ---------------------------------------------------------------------------
# Loss core: (normalized embeddings, fc state pieces) -> loss + aux
# ---------------------------------------------------------------------------

def make_loss_core(fc: FC.FastCLIPConfig, mesh_axes: Optional[Sequence[str]],
                   reduction: str = "fastclip", loss_impl: str = "dense"):
    """Returns loss_core(e1n, e2n, lu1, lu2, tau1, tau2, idx, gamma)
    -> (loss, aux) with aux = {u1_new, u2_new (full log-domain arrays),
    u1_rows/u2_rows (log-domain batch rows), stats (shifted RowStats),
    sat (per-row guard indicators)}.  Inputs e1n/e2n are the *normalized*
    global-batch embeddings (sharded over mesh_axes in the distributed
    case); lu1/lu2 the full (n,) log-domain state; tau1/tau2 scalars or
    full (n,) arrays (v2); idx the (B,) global sample indices.

    Both mesh settings of the ``fastclip`` reduction run through one
    custom-vjp op (repro.core.distributed.make_fcco_loss_op): the row
    stats are computed exactly once per step inside the op, and
    ``loss_impl`` selects the dense jnp math or the fused Pallas kernels.
    ``reduction="allgather_ad"`` keeps the OpenCLIP-style autodiff
    baseline (with its extra stats pre-pass) for comparison benches."""

    if mesh_axes is None:
        op = D.make_fcco_loss_op(None, fc.eps, fc.scale_by_tau,
                                 loss_impl=loss_impl)

        def local_core(e1n, e2n, lu1, lu2, tau1, tau2, idx, gamma):
            t1 = tau1[idx] if jnp.ndim(tau1) else tau1
            t2 = tau2[idx] if jnp.ndim(tau2) else tau2
            loss, (lu1_rows, lu2_rows, stats, sat) = op(
                e1n, e2n, lu1[idx], lu2[idx], t1, t2, gamma)
            aux = {"u1_new": lu1.at[idx].set(sg(lu1_rows)),
                   "u2_new": lu2.at[idx].set(sg(lu2_rows)),
                   "u1_rows": sg(lu1_rows), "u2_rows": sg(lu2_rows),
                   "stats": LS.RowStats(*jax.tree.map(sg, stats)),
                   "sat": sg(sat)}
            return loss, aux
        return local_core

    axes = tuple(mesh_axes)
    from jax.sharding import PartitionSpec as P
    pspec = P(axes)
    shard_loss = make_shard_loss(fc, axes, reduction, loss_impl)

    def dist_core(e1n, e2n, lu1, lu2, tau1, tau2, idx, gamma):
        tau_is_arr = jnp.ndim(tau1) > 0

        def inner(e1l, e2l, u1s, u2s, idxs, t1in, t2in):
            return _shard_fcco_inner(shard_loss, axes, tau_is_arr, e1l,
                                     e2l, u1s, u2s, idxs, t1in, t2in,
                                     gamma)

        in_specs = (pspec, pspec, pspec, pspec, pspec,
                    pspec if tau_is_arr else P(),
                    pspec if tau_is_arr else P())
        out_specs = (P(), pspec, pspec, pspec, pspec,
                     (pspec,) * 6, pspec)
        fn = jax.shard_map(inner, mesh=_current_mesh(),
                           in_specs=in_specs, out_specs=out_specs,
                           check_vma=False)
        loss, lu1_new, lu2_new, lu1r, lu2r, stats, sat = fn(
            e1n, e2n, lu1, lu2, idx, tau1, tau2)
        aux = {"u1_new": sg(lu1_new), "u2_new": sg(lu2_new),
               "u1_rows": sg(lu1r), "u2_rows": sg(lu2r),
               "stats": LS.RowStats(*jax.tree.map(sg, stats)),
               "sat": sg(sat)}
        return loss, aux

    return dist_core


def make_shard_loss(fc: FC.FastCLIPConfig, axes, reduction: str,
                    loss_impl: str, reduce: str = "mean"):
    """The per-shard loss callable shared by the shard_map island
    (``dist_core``) and the sharded-state step: shard_loss(e1l, e2l,
    lu1rows, lu2rows, t1, t2, gamma) -> (loss, lu1r, lu2r, stats, sat)
    on local (b,)-rows.  ``reduce="local"`` returns the unreduced local
    mean contribution (see distributed.make_fcco_loss_op)."""
    if reduction == "fastclip":
        op = D.make_fcco_loss_op(axes, fc.eps, fc.scale_by_tau,
                                 loss_impl=loss_impl, reduce=reduce)

        def shard_loss(e1l, e2l, lu1rows, lu2rows, t1, t2, gamma):
            loss, (lu1r, lu2r, stats, sat) = op(e1l, e2l, lu1rows,
                                                lu2rows, t1, t2, gamma)
            return loss, sg(lu1r), sg(lu2r), tuple(stats), sat
    else:
        pair = D.make_allgather_ad_pair_loss(axes, reduce=reduce)

        def shard_loss(e1l, e2l, lu1rows, lu2rows, t1, t2, gamma):
            # stats pre-pass (stop-grad; gathers CSE with the loss pass)
            off = D._global_index(axes) * e1l.shape[0]
            e1a = D._gather(sg(e1l), axes)
            e2a = D._gather(sg(e2l), axes)
            st0 = LS.row_stats(sg(e1l), sg(e2l), e1a, e2a, t1, t2,
                               row_offset=off)
            lg1, lg2 = LS.log_g(st0)
            lu1r = LS.update_log_u(lu1rows, lg1, gamma)
            lu2r = LS.update_log_u(lu2rows, lg2, gamma)
            lw1, lw2 = LS.fcco_log_weights(lu1r, lu2r, t1, t2, fc.eps,
                                           scale_by_tau=fc.scale_by_tau)
            sat = LS.saturation_rate(st0, lw1, lw2, t1, t2)
            loss, stats = pair(e1l, e2l, lw1, lw2,
                               t1 * jnp.ones_like(lw1),
                               t2 * jnp.ones_like(lw2))
            return loss, lu1r, lu2r, tuple(stats), sat

    return shard_loss


def _shard_fcco_inner(shard_loss, axes, tau_is_arr, e1l, e2l, u1s, u2s,
                      idxs, t1in, t2in, gamma):
    """One device's FCCO step on its sample shard: relative-index the
    local u/tau shards, run the loss op, scatter the new log-u rows back.
    Returns (loss, u1s_new, u2s_new, lu1r, lu2r, stats, sat)."""
    shard = u1s.shape[0]
    rel = idxs - D._global_index(axes) * shard
    t1 = t1in[rel] if tau_is_arr else t1in
    t2 = t2in[rel] if tau_is_arr else t2in
    loss, lu1r, lu2r, stats, sat = shard_loss(
        e1l, e2l, u1s[rel], u2s[rel], t1, t2, gamma)
    return (loss, u1s.at[rel].set(lu1r), u2s.at[rel].set(lu2r),
            lu1r, lu2r, stats, sat)


_MESH = None


def set_mesh(mesh):
    global _MESH
    _MESH = mesh


def _current_mesh():
    if _MESH is None:
        raise RuntimeError("set_mesh(mesh) before building distributed steps")
    return _MESH


# ---------------------------------------------------------------------------
# Full train step
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class TrainStepConfig:
    arch: ArchConfig
    fc: FC.FastCLIPConfig
    optimizer: Optimizer
    lr_fn: Callable
    wd: float = 0.1
    grad_clip: float = 0.0
    mesh_axes: Optional[Sequence[str]] = None
    reduction: str = "fastclip"
    impl: str = "chunked"
    # loss-layer math: "dense" (jnp pair matrices in HBM) or "fused"
    # (tiled Pallas kernels); None defers to fc.loss_impl
    loss_impl: Optional[str] = None
    # tower mixed-precision policy ("f32" | "bf16"); None defers to
    # arch.precision.  The loss layer stays f32 under any policy.
    precision: Optional[str] = None
    # sharded-state mode: run the whole step inside one shard_map over a
    # (data, fsdp) mesh (core.shard_state contract) — params/moments
    # ZeRO-sharded over "fsdp", weight gathers at use, psum_scatter
    # gradient reduction.  Requires mesh_axes == ("data", "fsdp") (or
    # None, which defaults to it) and set_mesh() with a matching mesh.
    fsdp: bool = False
    # comm/compute overlap (fsdp mode only): split each device's local
    # rows into `microbatch` micro-steps, each with its own weight
    # gather + tower forward/backward — autodiff then emits one
    # psum_scatter per (micro-step, sharded leaf), so micro-step i's
    # grad reduce-scatter (and its backward re-gather under inner_remat)
    # can overlap micro-step i±1's tower compute in the latency-hiding
    # scheduler.  Grads accumulate shard-locally; the FCCO loss and its
    # log-u update run ONCE per global step over the concatenated
    # embeddings (the per-sample u contract is untouched).  microbatch=1
    # is the unpipelined step, bit-identical to PR 5 behavior.
    microbatch: int = 1
    # non-finite step guard (repro.resilience.guard): an in-jit
    # all-finite check over the loss and the global grad norm turns a
    # bad step into a bitwise no-op update (params/moments/log-u and all
    # counters unchanged via jnp.where select) and emits the
    # ``skipped``/``nonfinite_rate`` metrics.
    guard: bool = False

    @property
    def resolved_precision(self) -> PR.Precision:
        return PR.get_precision(self.precision or self.arch.precision)


def init_train_state(rng, tc: TrainStepConfig):
    params = BB.init_params(rng, tc.arch)
    return {
        "params": params,
        "opt": tc.optimizer.init(params),
        "fc": FC.init_state(tc.fc),
        "step": jnp.zeros((), jnp.int32),
    }


def make_train_step(tc: TrainStepConfig):
    if tc.microbatch < 1:
        raise ValueError(f"microbatch must be >= 1, got {tc.microbatch}")
    if tc.fsdp:
        return make_fsdp_train_step(tc)
    if tc.microbatch > 1:
        raise ValueError(
            "microbatch pipelining overlaps the fsdp weight gathers / "
            "grad reduce-scatters with tower compute; it requires the "
            "sharded-state step (fsdp=True / --mesh data:N,fsdp:M)")
    fc = tc.fc
    prec = tc.resolved_precision
    gamma_fn = fc.gamma_fn()
    loss_core = (None if fc.version == "openclip"
                 else make_loss_core(fc, tc.mesh_axes, tc.reduction,
                                     tc.loss_impl or fc.loss_impl))
    if fc.version == "openclip" and tc.mesh_axes is not None:
        mbcl_dist = None  # built lazily inside (needs mesh at trace time)

    def train_step(state, batch, idx):
        fcs = state["fc"]
        step = state["step"]
        gamma = gamma_fn(step)
        lr = tc.lr_fn(step)
        tau1, tau2 = ((fcs["tau1"], fcs["tau2"]) if fc.individual_tau
                      else (fcs["tau"], fcs["tau"]))

        def loss_fn(params, tau_diff):
            e1, e2 = BB.encode_pair(params, tc.arch, batch, impl=tc.impl,
                                    precision=prec)
            with jax.named_scope(TR.LOSS_OP):
                e1n = LS.l2_normalize(e1)
                e2n = LS.l2_normalize(e2)
                if fc.version == "openclip":
                    if tc.mesh_axes is None:
                        loss = LS.mbcl_loss(e1n, e2n, tau_diff)
                    else:
                        from jax.sharding import PartitionSpec as P
                        axes = tuple(tc.mesh_axes)
                        f = D.make_mbcl_loss(axes)
                        loss = jax.shard_map(
                            f, mesh=_current_mesh(),
                            in_specs=(P(axes), P(axes), P()),
                            out_specs=P(), check_vma=False)(e1n, e2n, tau_diff)
                    return loss, {"e1n": sg(e1n), "e2n": sg(e2n)}
                t1 = fcs["tau1"] if fc.individual_tau else sg(tau_diff)
                t2 = fcs["tau2"] if fc.individual_tau else sg(tau_diff)
                loss, aux = loss_core(e1n, e2n, fcs["u1"], fcs["u2"], t1, t2,
                                      idx, gamma)
                aux["e1n"] = sg(e1n)
                aux["e2n"] = sg(e2n)
                return loss, aux

        (loss, aux), (grads, gtau) = jax.value_and_grad(
            loss_fn, argnums=(0, 1), has_aux=True)(
                state["params"], tau1 if not fc.individual_tau else 0.0)

        with jax.named_scope(TR.OPTIMIZER):
            if tc.grad_clip:
                grads, gnorm = clip_by_global_norm(grads, tc.grad_clip)
            elif tc.guard:
                gnorm = global_norm(grads)   # the guard's all-finite probe
            else:
                gnorm = jnp.asarray(0.0)

            params, opt = tc.optimizer.update(
                state["params"], grads, state["opt"], lr=lr, wd=tc.wd)

            new_fc = dict(fcs)
            metrics = {"loss": loss, "lr": lr, "gamma": gamma,
                       "grad_norm": gnorm}
            if fc.version == "openclip":
                if fc.learnable_tau:
                    new_fc = FC.tau_update(fc, new_fc, gtau)
                metrics["tau"] = new_fc.get("tau", tau1)
            else:
                new_fc["u1"] = aux["u1_new"]
                new_fc["u2"] = aux["u2_new"]
                stats_aux = {"lu1_new": aux["u1_rows"],
                             "lu2_new": aux["u2_rows"],
                             "m1": aux["stats"].m1, "m2": aux["stats"].m2,
                             "dg1_dtau": aux["stats"].dg1_dtau,
                             "dg2_dtau": aux["stats"].dg2_dtau}
                t1r = tau1[idx] if fc.individual_tau else tau1
                t2r = tau2[idx] if fc.individual_tau else tau2
                tg = FC.tau_gradient(fc, stats_aux, t1r, t2r)
                if fc.individual_tau:
                    new_fc = FC.tau_update(fc, new_fc, tg, idx=idx)
                    metrics["tau"] = jnp.mean(new_fc["tau1"])
                elif tg is not None:
                    new_fc = FC.tau_update(fc, new_fc, tg)
                    metrics["tau"] = new_fc["tau"]
                else:
                    metrics["tau"] = tau1
                # u is log-domain; report a display-clamped linear mean
                metrics["u_mean"] = jnp.mean(
                    jnp.exp(jnp.minimum(aux["u1_rows"], 80.0)))
                # fraction of rows on which the last-resort EXP_CLAMP guard
                # would fire (exact 0 <=> no pair clamps; ~0 under the LSE
                # path on any healthy state)
                metrics["sat_rate"] = jnp.mean(aux["sat"])
                metrics["loss_value"] = FC.loss_value(
                    fc, {"lu1_new": aux["u1_rows"], "lu2_new": aux["u2_rows"]},
                    t1r, t2r)
            new_fc["step"] = fcs["step"] + 1

            new_state = {"params": params, "opt": opt, "fc": new_fc,
                         "step": step + 1}
            if tc.guard:
                ok = RG.step_ok(loss, gnorm)
                new_state = RG.select_state(ok, state, new_state)
                metrics["skipped"] = 1.0 - ok.astype(jnp.float32)
                metrics["nonfinite_rate"] = RG.grad_nonfinite_rate(grads)
            return new_state, metrics

    return train_step


# ---------------------------------------------------------------------------
# Sharded-state train step: the (data, fsdp) named-mesh contract (PR 5)
# ---------------------------------------------------------------------------

def make_fsdp_train_step(tc: TrainStepConfig, param_dims=None):
    """The whole train step inside ONE shard_map over the (data, fsdp)
    mesh (``set_mesh`` first): state enters as local shards per
    ``core.shard_state`` — params/optimizer moments ZeRO-sharded over
    ``fsdp``, FCCO u/tau buffers and the batch by sample ownership over
    both axes.

    Distribution contract (vs. the replicated ``mesh_axes`` path):

      * the forward all-gathers each sharded weight over ``fsdp`` at its
        use site; with ``models.sharding.inner_remat()`` (the default)
        the gathered weights are excluded from the residuals and
        re-gathered in the backward (re-gather vs. remat stays a knob);
      * the backward's param-gradient reduction is the all-gather's
        transpose — a **psum_scatter (reduce-scatter) onto each device's
        shard** — finished by a shard-sized psum over ``data``
        (``shard_state.reduce_grads``): no full-tree all-reduce of param
        gradients is ever emitted;
      * the FCCO loss op keeps its own comms contract untouched (feature
        gather + O(K|B|) scalar gather over both axes; its ``local``
        reduction keeps psums out of the differentiated region);
      * the optimizer updates only the local shard (requires
        ``Optimizer.shard_safe``; LAMB's whole-leaf trust ratio is not).

    ``tc.microbatch > 1`` pipelines the local rows: each micro-step
    gathers the weights and runs its tower slice, so the backward holds
    one shard-sized psum_scatter per (micro-step, sharded leaf) —
    overlappable with adjacent micro-steps' compute — while grads
    accumulate shard-locally and the FCCO loss + log-u update still run
    once per global step over the concatenated embeddings (per-sample u
    contract preserved; microbatch=1 is bitwise the unpipelined step).

    With fsdp=1 every leaf replicates and the same code path is plain
    data parallelism (gathers become identity).  ``param_dims`` overrides
    the ZeRO layout (``shard_state.param_fsdp_dims`` shape; all-None =
    fully replicated params on the same mesh — the parity oracle): the
    replicated-spec and sharded-spec runs stage their reductions
    identically (fsdp first, then data), so at axis size 2 they are
    bit-identical."""
    from jax.sharding import PartitionSpec as P
    from repro.core import shard_state as SS
    from repro.models import sharding as SH

    fc = tc.fc
    prec = tc.resolved_precision
    gamma_fn = fc.gamma_fn()
    axes = tuple(tc.mesh_axes) if tc.mesh_axes else SS.TRAIN_AXES
    if axes != SS.TRAIN_AXES:
        raise ValueError(f"fsdp step runs on mesh axes {SS.TRAIN_AXES}, "
                         f"got mesh_axes={axes}")
    mesh = _current_mesh()
    fsdp = SS.fsdp_size(mesh)
    if fsdp > 1 and not tc.optimizer.shard_safe:
        raise ValueError(
            f"optimizer {tc.optimizer.name!r} is not shard-safe (its "
            "update needs whole leaves); use adamw/sgdm/lion with fsdp>1")
    if SH.configured_batch_axes() is not None:
        raise ValueError(
            "the sharded-state step is fully manual (one shard_map): "
            "unset models.sharding.set_batch_axes (GSPMD constraints "
            "don't apply inside it)")

    p_shapes = BB.param_shapes(tc.arch)
    p_dims = (SS.param_fsdp_dims(p_shapes, fsdp) if param_dims is None
              else param_dims)
    loss_impl = tc.loss_impl or fc.loss_impl
    if fc.version == "openclip":
        mbcl = D.make_mbcl_loss(axes, reduce="local")
        shard_loss = None
    else:
        mbcl = None
        shard_loss = make_shard_loss(fc, axes, tc.reduction, loss_impl,
                                     reduce="local")

    # state/batch specs (shard_map in/out); metrics replicate (prefix P())
    state_like = {
        "params": p_shapes,
        "opt": jax.eval_shape(tc.optimizer.init, p_shapes),
        "fc": jax.eval_shape(lambda: FC.init_state(fc)),
        "step": jax.ShapeDtypeStruct((), jnp.int32),
    }
    state_specs = SS.train_state_specs(state_like, fsdp, param_dims=p_dims)

    def pmean(x):
        # hierarchical mean (staged_psum: fsdp first, then data) so
        # single- and multi-process runs sum in the same 2-wide stages —
        # a flat psum over both axes may reorder the f32 sum across
        # process boundaries, and the tau update feeds state
        return SS.staged_psum(x) / jax.lax.psum(1, axes)

    def step_local(state, batch, idx):
        fcs = state["fc"]
        step = state["step"]
        gamma = gamma_fn(step)
        lr = tc.lr_fn(step)
        tau1, tau2 = ((fcs["tau1"], fcs["tau2"]) if fc.individual_tau
                      else (fcs["tau"], fcs["tau"]))
        if fc.uses_fcco:
            shard = fcs["u1"].shape[0]
            rel = idx - D._global_index(axes) * shard
        else:
            rel = None

        def encode_towers(p_shards):
            """Local tower forward.  microbatch=1: one gather + one
            forward (the unpipelined PR 5 step, bit-identical).
            microbatch=N: N (gather, forward-on-a-slice) micro-steps —
            each gather call transposes to its own psum_scatter in the
            backward, giving the scheduler N independent shard-sized
            reduce-scatters to overlap with the neighboring micro-steps'
            tower compute (identical forward gathers CSE away; the
            backward's scatters cannot, their operands differ)."""
            remat = "fsdp_gather" if SH.inner_remat() else None
            if tc.microbatch == 1:
                params = SS.gather_params(p_shards, p_dims,
                                          remat_name=remat)
                return BB.encode_pair(params, tc.arch, batch,
                                      impl=tc.impl, precision=prec)
            b = next(iter(batch.values())).shape[0]
            if b % tc.microbatch != 0:
                raise ValueError(
                    f"microbatch={tc.microbatch} does not divide the "
                    f"per-device batch of {b} rows (global batch / "
                    "data*fsdp); pick a divisor")
            mb = b // tc.microbatch
            outs = []
            for j in range(tc.microbatch):
                params = SS.gather_params(p_shards, p_dims,
                                          remat_name=remat)
                bj = {k: jax.lax.slice_in_dim(v, j * mb, (j + 1) * mb,
                                              axis=0)
                      for k, v in batch.items()}
                outs.append(BB.encode_pair(params, tc.arch, bj,
                                           impl=tc.impl, precision=prec))
            return (jnp.concatenate([o[0] for o in outs]),
                    jnp.concatenate([o[1] for o in outs]))

        def loss_fn(p_shards, tau_diff):
            e1, e2 = encode_towers(p_shards)
            with jax.named_scope(TR.LOSS_OP):
                e1n = LS.l2_normalize(e1)
                e2n = LS.l2_normalize(e2)
                if fc.version == "openclip":
                    local = mbcl(e1n, e2n, tau_diff)
                    return local, {"e1n": sg(e1n), "e2n": sg(e2n)}
                t1in = fcs["tau1"] if fc.individual_tau else sg(tau_diff)
                t2in = fcs["tau2"] if fc.individual_tau else sg(tau_diff)
                local, u1n, u2n, lu1r, lu2r, stats, sat = _shard_fcco_inner(
                    shard_loss, axes, fc.individual_tau, e1n, e2n,
                    fcs["u1"], fcs["u2"], idx, t1in, t2in, gamma)
                aux = {"u1_new": sg(u1n), "u2_new": sg(u2n),
                       "u1_rows": sg(lu1r), "u2_rows": sg(lu2r),
                       "stats": LS.RowStats(*jax.tree.map(sg, stats)),
                       "sat": sg(sat), "e1n": sg(e1n), "e2n": sg(e2n)}
                return local, aux

        if SH.inner_remat():
            loss_fn = jax.checkpoint(
                loss_fn,
                policy=jax.checkpoint_policies.save_any_names_but_these(
                    "fsdp_gather"))

        (local, aux), (grads, gtau) = jax.value_and_grad(
            loss_fn, argnums=(0, 1), has_aux=True)(
                state["params"], tau1 if not fc.individual_tau else 0.0)
        loss = SS.staged_psum(local)     # local is the /B contribution
        grads = SS.reduce_grads(grads, p_dims)

        with jax.named_scope(TR.OPTIMIZER):
            if tc.grad_clip:
                grads, gnorm = clip_by_global_norm(
                    grads, tc.grad_clip, axes=("fsdp",), sharded_dims=p_dims)
            elif tc.guard:
                # axis-aware: psums sharded-leaf squares over fsdp, so every
                # shard evaluates the identical guard predicate
                gnorm = global_norm(grads, axes=("fsdp",), sharded_dims=p_dims)
            else:
                gnorm = jnp.asarray(0.0)

            params, opt = tc.optimizer.update(
                state["params"], grads, state["opt"], lr=lr, wd=tc.wd)

            new_fc = dict(fcs)
            metrics = {"loss": loss, "lr": lr, "gamma": gamma,
                       "grad_norm": gnorm}
            if fc.version == "openclip":
                if fc.learnable_tau:
                    new_fc = FC.tau_update(fc, new_fc, SS.staged_psum(gtau))
                metrics["tau"] = new_fc.get("tau", tau1)
            else:
                new_fc["u1"] = aux["u1_new"]
                new_fc["u2"] = aux["u2_new"]
                stats_aux = {"lu1_new": aux["u1_rows"],
                             "lu2_new": aux["u2_rows"],
                             "m1": aux["stats"].m1, "m2": aux["stats"].m2,
                             "dg1_dtau": aux["stats"].dg1_dtau,
                             "dg2_dtau": aux["stats"].dg2_dtau}
                t1r = tau1[rel] if fc.individual_tau else tau1
                t2r = tau2[rel] if fc.individual_tau else tau2
                tg = FC.tau_gradient(fc, stats_aux, t1r, t2r)
                if fc.individual_tau:
                    # per-row grads stay shard-local (stochastic coordinate
                    # update on the owned rows)
                    new_fc = FC.tau_update(fc, new_fc, tg, idx=rel)
                    metrics["tau"] = pmean(jnp.mean(new_fc["tau1"]))
                elif tg is not None:
                    # scalar tau grads are batch means: pmean the equal-size
                    # shard means for the global mean
                    new_fc = FC.tau_update(fc, new_fc, pmean(tg))
                    metrics["tau"] = new_fc["tau"]
                else:
                    metrics["tau"] = tau1
                metrics["u_mean"] = pmean(jnp.mean(
                    jnp.exp(jnp.minimum(aux["u1_rows"], 80.0))))
                metrics["sat_rate"] = pmean(jnp.mean(aux["sat"]))
                metrics["loss_value"] = pmean(FC.loss_value(
                    fc, {"lu1_new": aux["u1_rows"],
                         "lu2_new": aux["u2_rows"]}, t1r, t2r))
            new_fc["step"] = fcs["step"] + 1

            new_state = {"params": params, "opt": opt, "fc": new_fc,
                         "step": step + 1}
            if tc.guard:
                # loss/gnorm are already global (psum'd), so ok is identical
                # on every shard and the local-shard selects stay consistent
                ok = RG.step_ok(loss, gnorm)
                new_state = RG.select_state(ok, state, new_state)
                metrics["skipped"] = 1.0 - ok.astype(jnp.float32)
                metrics["nonfinite_rate"] = pmean(
                    RG.grad_nonfinite_rate(grads))
            return new_state, metrics

    def train_step(state, batch, idx):
        b_specs = SS.batch_specs(batch)
        fn = jax.shard_map(step_local, mesh=mesh,
                           in_specs=(state_specs, b_specs, P(axes)),
                           out_specs=(state_specs, P()), check_vma=False)
        return fn(state, batch, idx)

    return train_step


# ---------------------------------------------------------------------------
# Post-step dtype invariants
# ---------------------------------------------------------------------------

def check_state_dtypes(state) -> None:
    """Assert the master-state dtype contract after a step: every floating
    leaf of params / optimizer moments / FCCO state (log-u buffers, taus)
    is f32, under *any* tower precision policy.  Integer leaves (step
    counters) are exempt.  Raises AssertionError listing offenders."""
    bad = []
    for name in ("params", "opt", "fc"):
        if name not in state:
            continue
        flat = jax.tree_util.tree_flatten_with_path(state[name])[0]
        for path, leaf in flat:
            if (hasattr(leaf, "dtype")
                    and jnp.issubdtype(leaf.dtype, jnp.floating)
                    and leaf.dtype != jnp.float32):
                keys = "/".join(str(k) for k in path)
                bad.append(f"{name}/{keys}: {leaf.dtype}")
    if bad:  # explicit raise: survives python -O (bare assert does not)
        raise AssertionError(
            "master state must stay f32 under any precision policy; "
            "offenders: " + ", ".join(bad))


# ---------------------------------------------------------------------------
# Retrieval evaluation (synthetic-data metric for the paper-claims benches)
# ---------------------------------------------------------------------------

def retrieval_accuracy(params, cfg: ArchConfig, batch, impl="chunked",
                       classes=None):
    """Top-1 retrieval over the batch.  With ``classes`` given, a
    retrieval is correct when it lands on any same-class item (synthetic
    data has class-duplicate captions, so exact-index accuracy saturates
    at the collision ceiling)."""
    e1, e2 = BB.encode_pair(params, cfg, batch, impl=impl)
    e1n = LS.l2_normalize(e1)
    e2n = LS.l2_normalize(e2)
    s = e1n @ e2n.T
    a1 = jnp.argmax(s, axis=1)
    a2 = jnp.argmax(s, axis=0)
    if classes is None:
        i2t = jnp.mean(a1 == jnp.arange(s.shape[0]))
        t2i = jnp.mean(a2 == jnp.arange(s.shape[0]))
    else:
        classes = jnp.asarray(classes)
        i2t = jnp.mean(classes[a1] == classes)
        t2i = jnp.mean(classes[a2] == classes)
    return 0.5 * (i2t + t2i)
