"""Distributed FastCLIP: the paper's communication-efficient gradient
reduction (Section 4 / Appendix A), expressed as a ``jax.custom_vjp`` used
inside ``shard_map`` over the data axis.

``make_fcco_loss_op`` is the production loss engine: one custom-vjp op that
serves both the single-device (``axes=None``) and sharded settings, with a
``loss_impl`` knob selecting dense jnp math or the tiled Pallas kernels
(repro.kernels.gcl_loss).  Its forward computes the row stats exactly once
(stats, u update, FCCO weights and the surrogate all inside the op, so no
second stats pass survives the custom-vjp boundary) and its backward emits
the local feature grads in closed form — no collective, and in the fused
case no (b, B) pair matrix in HBM.

Log-domain stats contract (the log-sum-exp shift, see repro.core.losses):

  * the op takes and returns the FCCO u state in **log domain** (lu);
  * the row stats are shift-decomposed: per-row max ``m`` (stop-grad) +
    shift-invariant sums, so nothing overflows f32 at tau -> tau_min;
  * each shard's row maxes are private to its anchor rows (a row's max
    runs over the already-gathered columns), so no extra collective is
    needed for the shift — the per-shard maxes enter the backward only
    through the O(K|B|) scalar gather of ``lwt = lw - log(tau)`` below,
    and inside the kernels the per-tile maxes combine via the standard
    streaming-max/rescale recurrence;
  * the backward exponent is ``z_ij + lwt_i = z_ij - log(eps + u_i)``,
    bounded above by ``log(B/gamma)`` since ``u_new >= gamma * g`` — the
    closed form is the exact derivative of the *unclamped* objective
    (losses.EXP_CLAMP remains only as a last-resort guard, with the
    ``sat`` aux output counting the rows on which it would fire).

Two reductions are implemented for the same objective:

``reduction="fastclip"``
    Forward ALL_GATHERs the normalized features (unavoidable: the loss
    contrasts against the global batch, same cost as OpenCLIP's forward)
    plus O(K|B|) *scalars* (s_ii, the log-domain FCCO weights, taus).
    The backward computes the gradient w.r.t. the *local* features in
    closed form from the saved gathered tensors — it emits **no collective
    on feature gradients**.  This is the paper's replacement of OpenCLIP's
    O(K|B|d) REDUCE_SCATTER with an O(K|B|) scalar ALL_GATHER.

``reduction="allgather_ad"``
    The same surrogate differentiated straight through ``all_gather``.
    XLA's transpose of all_gather is a psum-scatter of the full
    (B_global, d) feature-gradient — exactly the OpenCLIP/DDP communication
    pattern the paper improves on.  Kept as the measurable baseline
    (benchmarks/comm_cost.py counts collective bytes of both HLOs).

Gradient math (Appendix A, both sides, per-row taus, log-domain weights):
    L = (1/B) sum_i [w1_i g1_i + w2_i g2_i]
    A1[i,j] = exp(z1_ij + lwt1_i) (0 on diag), lwt_i = lw_i - log tau_i;
    A2 likewise
    dL/de1_p = 1/(B(B-1)) [ sum_j A1[p,j](e2_j - e2_p)
                            + sum_i A2[i,p] e2_i - (sum_j A2[p,j]) e2_p ]
    dL/de2_p = 1/(B(B-1)) [ sum_j A2[p,j](e1_j - e1_p)
                            + sum_i A1[i,p] e1_i - (sum_j A1[p,j]) e1_p ]
Every term for local p needs only local rows of A, the gathered features
(forward residuals) and gathered scalars.

The repo's ``jax.shard_map`` calls pass ``check_vma=False``: the loss
islands mix replicated scalars and sharded rows, which the
varying-manual-axes check rejects.
"""
from __future__ import annotations

from typing import Sequence

import jax
import jax.numpy as jnp

from repro.core import losses as LS

sg = jax.lax.stop_gradient


def gather_axes(x, axes):
    """Tiled ALL_GATHER over possibly-multiple mesh axes, for use inside
    ``shard_map``.  Public shared helper: the loss engine gathers the
    global feature columns with it, and the eval engine's streaming
    retrieval gathers its similarity columns under the *same* axes, so
    both sides of the rectangular (local-rows x gathered-cols) contract
    shard identically.

    The loop runs *last axis first*: each later gather nests earlier
    blocks inside it, so the result rows land in first-axis-major order
    — exactly ``_global_index`` (``idx = idx * size + axis_index`` over
    ``axes``) and the row-block order of ``NamedSharding(P(axes))``.
    (Looping in axis order would put the LAST axis outermost and
    misalign ``row_offset`` diagonal masking on any multi-axis mesh,
    e.g. the (data, fsdp) train mesh; single-axis meshes can't tell.)"""
    for ax in reversed(tuple(axes)):
        x = jax.lax.all_gather(x, ax, tiled=True)
    return x


_gather = gather_axes


def _psum(x, axes):
    return jax.lax.psum(x, axes)


def _global_index(axes):
    """Flattened shard index over possibly-multiple mesh axes."""
    idx = 0
    for ax in axes:
        idx = idx * jax.lax.axis_size(ax) + jax.lax.axis_index(ax)
    return idx


def _axis_prod(axes):
    out = 1
    for ax in axes:
        out *= jax.lax.axis_size(ax)
    return out


axis_prod = _axis_prod   # public alias (shared with the eval engine)


# ---------------------------------------------------------------------------
# Closed-form local feature grads (Appendix A), dense jnp flavor
# ---------------------------------------------------------------------------

def _dense_local_grads(e1, e2, e1a, e2a, sd, sda, lwt1, lwt2, lwt1a, lwt2a,
                       t1, t2, t1a, t2a, off):
    """(de1, de2) of L = (1/B) sum_i w1_i g1_i + w2_i g2_i w.r.t. the local
    rows, from the local (b,)-quantities and the gathered (B,)-quantities.
    ``lwt* = log(w*) - log(tau*)`` per row / gathered: every pair enters as
    ``exp(z + lwt)``, which is bounded by log(B/gamma) above (exact
    unclamped gradients; ``guarded_exp`` is the last-resort guard).
    Includes the 1/(B(B-1)) factor; the caller scales by the cotangent.
    Builds four dense (b, B) matrices — the fused Pallas path avoids them.
    """
    b, d = e1.shape
    B = e1a.shape[0]
    rows = off + jnp.arange(b)
    cols = jnp.arange(B)
    offdiag = (cols[None, :] != rows[:, None]).astype(jnp.float32)
    kappa = 1.0 / (B * (B - 1.0))

    # local rows of A1, A2: (b, B)
    s1 = jnp.einsum("bd,Bd->bB", e1, e2a,
                    preferred_element_type=jnp.float32)
    s2 = jnp.einsum("bd,Bd->bB", e2, e1a,
                    preferred_element_type=jnp.float32)
    gexp = LS.guarded_exp
    A1r = gexp((s1 - sd[:, None]) / t1[:, None] + lwt1[:, None]) * offdiag
    A2r = gexp((s2 - sd[:, None]) / t2[:, None] + lwt2[:, None]) * offdiag
    # local columns: M1[p, i] = A1[i, p] (anchors i global, col p local).
    # A1[i, p] = exp((e1_i.e2_p - sd_i)/tau1_i + lwt1_i), and e1_i.e2_p is
    # s2[p, i] (likewise e2_i.e1_p = s1[p, i]) — reuse the A-side matmuls.
    M1 = gexp((s2 - sda[None, :]) / t1a[None, :] + lwt1a[None, :]) * offdiag
    M2 = gexp((s1 - sda[None, :]) / t2a[None, :] + lwt2a[None, :]) * offdiag

    e1f = e1.astype(jnp.float32)
    e2f = e2.astype(jnp.float32)
    de1 = (jnp.einsum("bB,Bd->bd", A1r, e2a.astype(jnp.float32))
           - jnp.sum(A1r, axis=1, keepdims=True) * e2f
           + jnp.einsum("bB,Bd->bd", M2, e2a.astype(jnp.float32))
           - jnp.sum(A2r, axis=1, keepdims=True) * e2f)
    de2 = (jnp.einsum("bB,Bd->bd", A2r, e1a.astype(jnp.float32))
           - jnp.sum(A2r, axis=1, keepdims=True) * e1f
           + jnp.einsum("bB,Bd->bd", M1, e1a.astype(jnp.float32))
           - jnp.sum(A1r, axis=1, keepdims=True) * e1f)
    return kappa * de1, kappa * de2


# ---------------------------------------------------------------------------
# The communication-efficient op
# ---------------------------------------------------------------------------

def make_fastclip_pair_loss(axes: Sequence[str]):
    """Returns f(e1n, e2n, lw1, lw2, t1, t2) -> (loss, stats)
    for use *inside* shard_map.  e1n/e2n: (b, d) normalized local features;
    lw1/lw2: (b,) stop-grad *log-domain* FCCO weights; t1/t2: (b,) taus.
    loss is the global surrogate (replicated).  The shift-decomposed row
    stats are returned for the u and tau updates (stop-grad)."""
    axes = tuple(axes)

    @jax.custom_vjp
    def pair_loss(e1, e2, lw1, lw2, t1, t2):
        local, stats, _ = _fwd_compute(e1, e2, lw1, lw2, t1, t2)
        return local, tuple(stats)

    def _fwd_compute(e1, e2, lw1, lw2, t1, t2):
        b = e1.shape[0]
        off = _global_index(axes) * b
        e1a = _gather(e1, axes)                 # (B, d)  feature gather
        e2a = _gather(e2, axes)
        sd = jnp.sum(e1.astype(jnp.float32) * e2.astype(jnp.float32),
                     axis=-1)                   # (b,) local s_ii
        stats = LS.row_stats(e1, e2, e1a, e2a, t1, t2, row_offset=off)
        # unreduced local sum: the psum/B runs in ``with_stats`` outside
        # the custom-vjp (see make_fcco_loss_op for why)
        local = LS.surrogate_loss(stats, lw1, lw2, 1.0)
        res = (e1, e2, e1a, e2a, sd, lw1, lw2, t1, t2, off)
        return local, stats, res

    def fwd(e1, e2, lw1, lw2, t1, t2):
        local, stats, res = _fwd_compute(e1, e2, lw1, lw2, t1, t2)
        # gather the scalars for the backward (the O(K|B|) communication)
        e1_, e2_, e1a, e2a, sd, lw1_, lw2_, t1_, t2_, off = res
        lwt1 = lw1 - jnp.log(t1)
        lwt2 = lw2 - jnp.log(t2)
        sda = _gather(sd, axes)
        lwt1a = _gather(lwt1, axes)
        lwt2a = _gather(lwt2, axes)
        t1a = _gather(t1 * jnp.ones_like(sd), axes)
        t2a = _gather(t2 * jnp.ones_like(sd), axes)
        # rank >= 1 residuals only (shard_map partial-eval requirement)
        off1 = jnp.reshape(jnp.asarray(off, jnp.int32), (1,))
        return (local, tuple(stats)), \
            (e1_, e2_, e1a, e2a, sd, sda, lwt1a, lwt2a, t1a, t2a, off1)

    def bwd(res, cts):
        ct, _ = cts   # stats are stop-grad outputs; ignore their cotangents
        e1, e2, e1a, e2a, sd, sda, lwt1a, lwt2a, t1a, t2a, off1 = res
        off = off1[0]
        b = e1.shape[0]
        lwt1 = jax.lax.dynamic_slice_in_dim(lwt1a, off, b)
        lwt2 = jax.lax.dynamic_slice_in_dim(lwt2a, off, b)
        t1 = jax.lax.dynamic_slice_in_dim(t1a, off, b)
        t2 = jax.lax.dynamic_slice_in_dim(t2a, off, b)
        de1, de2 = _dense_local_grads(e1, e2, e1a, e2a, sd, sda, lwt1,
                                      lwt2, lwt1a, lwt2a, t1, t2, t1a,
                                      t2a, off)
        # de* are grads of the global mean loss; pair_loss returns the
        # local sum (the with_stats psum/B puts 1/B on ct)
        B = e1a.shape[0]
        de1 = (ct * B * de1).astype(e1.dtype)
        de2 = (ct * B * de2).astype(e2.dtype)
        z = jnp.zeros_like(sd)
        return de1, de2, z, z, z, z

    pair_loss.defvjp(fwd, bwd)

    def with_stats(e1, e2, lw1, lw2, t1, t2):
        # make every arg axis-varying (lw derives from the sharded u state;
        # broadcast taus against it) so the custom-vjp in/out types match.
        ones = jnp.ones_like(lw1)
        local, stats = pair_loss(e1, e2, lw1, lw2, t1 * ones, t2 * ones)
        B = e1.shape[0] * _axis_prod(axes)
        loss = _psum(local, axes) / B
        return loss, LS.RowStats(*jax.tree.map(sg, stats))

    return with_stats


# ---------------------------------------------------------------------------
# The production loss engine: one custom-vjp op, dense or fused per-device
# math, single-device (axes=None) or sharded
# ---------------------------------------------------------------------------

def make_fcco_loss_op(axes, eps, scale_by_tau=True, *, loss_impl="dense",
                      interpret=None, reduce="mean"):
    """Returns op(e1n, e2n, lu1_rows, lu2_rows, t1, t2, gamma) ->
    (loss, (lu1_new_rows, lu2_new_rows,
            (g1, g2, dg1, dg2, m1, m2), sat)).

    The whole FCCO step for one batch lives inside the op's forward —
    row stats (exactly one pass), the log-domain u moving-average update,
    the log-domain FCCO weights lw = log tau - log(eps+u) and the
    surrogate — so nothing is recomputed across the custom-vjp boundary.
    The backward emits the local feature grads in closed form (Appendix
    A): with ``axes`` it communicates only the O(K|B|) scalars gathered in
    the forward, never feature gradients.

    Log-domain contract: ``lu*_rows`` are log(u) (init log(0) = -inf); the
    returned stats are shift-decomposed (true g = exp(m) * g, see
    losses.RowStats); ``sat`` is the (b,) per-row last-resort-guard
    indicator (losses.saturation_rate) — ~0 everywhere on a healthy state.

    ``loss_impl="dense"`` uses jnp math ((b, B) pair matrices in HBM);
    ``loss_impl="fused"`` streams the pair matrix through VMEM via the
    tiled Pallas kernels.  ``axes=None`` gives single-device semantics
    (columns == rows).  ``interpret=None`` auto-selects Pallas interpret
    mode off-TPU.  t1/t2 may be scalars or (b,) per-row arrays (v2);
    everything but e1n/e2n gets zero gradients (u, tau updates are
    closed-form elsewhere).

    ``reduce="mean"`` (default) returns the global mean loss (the psum/B
    runs outside the custom-vjp, as before).  ``reduce="local"`` returns
    the *local mean contribution* ``local_sum / B`` with no psum at all —
    for call sites that already sit inside a ``shard_map`` and
    differentiate the step themselves (the sharded-state train step):
    with no psum in the differentiated region the closed-form backward
    never depends on jax's psum-transpose cotangent convention, and the
    caller psums the returned scalar for the replicated loss metric.  The
    comms contract is identical in both modes (same feature gather, same
    O(K|B|) scalar gather; the mean-mode psum moved one f32 scalar)."""
    axes = tuple(axes) if axes else ()
    if loss_impl not in ("dense", "fused"):
        raise ValueError(f"loss_impl must be 'dense' or 'fused', "
                         f"got {loss_impl!r}")
    if reduce not in ("mean", "local"):
        raise ValueError(f"reduce must be 'mean' or 'local', got {reduce!r}")
    from repro.kernels.gcl_loss import gcl_pair_grads, gcl_pair_stats
    from repro.kernels.ops import default_interpret

    def _interp():
        return default_interpret() if interpret is None else interpret

    # Residuals crossing the shard_map boundary must be rank >= 1 (old-jax
    # shard_map partial-eval gives them an all-axes spec, which rejects
    # rank-0 values), so the custom-vjp core only sees (b,)-vectors and the
    # offset packed as shape (1,); the public wrapper normalizes scalars.

    def _fwd_compute(e1, e2, lu1r, lu2r, t1v, t2v, gammav):
        b = e1.shape[0]
        if axes:
            off = _global_index(axes) * b
            e1a = _gather(e1, axes)             # feature gather (fwd only)
            e2a = _gather(e2, axes)
        else:
            off = 0
            e1a, e2a = e1, e2
        B = e1a.shape[0]
        if loss_impl == "fused":
            stats = LS.RowStats(*gcl_pair_stats(
                e1, e2, t1v, t2v, e1_all=e1a, e2_all=e2a, row_offset=off,
                interpret=_interp()))
        else:
            stats = LS.row_stats(e1, e2, e1a, e2a, t1v, t2v,
                                 row_offset=off)
        lg1, lg2 = LS.log_g(stats)
        lu1n = LS.update_log_u(lu1r, lg1, gammav[0])
        lu2n = LS.update_log_u(lu2r, lg2, gammav[0])
        lw1, lw2 = LS.fcco_log_weights(lu1n, lu2n, t1v, t2v, eps,
                                       scale_by_tau=scale_by_tau)
        sat = LS.saturation_rate(stats, lw1, lw2, t1v, t2v)
        # the *unreduced* local contribution: the final psum/B runs outside
        # the custom-vjp so jax's own psum transpose pairs with its own
        # replicated-cotangent convention (version-dependent); the bwd
        # compensates with the B factor.
        local = LS.surrogate_loss(stats, lw1, lw2, 1.0)
        sd = jnp.sum(e1.astype(jnp.float32) * e2.astype(jnp.float32),
                     axis=-1)
        lwt1 = lw1 - jnp.log(t1v)
        lwt2 = lw2 - jnp.log(t2v)
        return local, (lu1n, lu2n, tuple(stats), sat), \
            (e1, e2, e1a, e2a, sd, lwt1, lwt2, off)

    @jax.custom_vjp
    def core(e1, e2, lu1r, lu2r, t1v, t2v, gammav):
        local, aux, _ = _fwd_compute(e1, e2, lu1r, lu2r, t1v, t2v, gammav)
        return local, aux

    def fwd(e1, e2, lu1r, lu2r, t1v, t2v, gammav):
        local, aux, res = _fwd_compute(e1, e2, lu1r, lu2r, t1v, t2v,
                                       gammav)
        e1_, e2_, e1a, e2a, sd, lwt1, lwt2, off = res
        if axes:
            # the O(K|B|) scalar gather for the backward (paper §4)
            sda = _gather(sd, axes)
            lwt1a, lwt2a = _gather(lwt1, axes), _gather(lwt2, axes)
            t1a, t2a = _gather(t1v, axes), _gather(t2v, axes)
        else:
            sda, lwt1a, lwt2a, t1a, t2a = sd, lwt1, lwt2, t1v, t2v
        off1 = jnp.reshape(jnp.asarray(off, jnp.int32), (1,))
        return (local, aux), (e1_, e2_, e1a, e2a, sd, sda, lwt1, lwt2,
                              lwt1a, lwt2a, t1v, t2v, t1a, t2a, off1)

    def bwd(res, cts):
        ct, _ = cts   # aux outputs are stop-grad at every call site
        (e1, e2, e1a, e2a, sd, sda, lwt1, lwt2, lwt1a, lwt2a, t1v, t2v,
         t1a, t2a, off1) = res
        off = off1[0]
        B = e1a.shape[0]
        if loss_impl == "fused":
            de1, de2 = gcl_pair_grads(
                e1, e2, lwt1, lwt2, t1v, t2v, e1_all=e1a, e2_all=e2a,
                sd_all=sda, lwt1_all=lwt1a, lwt2_all=lwt2a, tau1_all=t1a,
                tau2_all=t2a, row_offset=off, interpret=_interp())
        else:
            de1, de2 = _dense_local_grads(e1, e2, e1a, e2a, sd, sda, lwt1,
                                          lwt2, lwt1a, lwt2a, t1v, t2v,
                                          t1a, t2a, off)
        # de* are grads of the *global mean* loss; ``core`` returns the
        # local sum, whose outside psum/B contributes the 1/B on ct.
        scale = ct * B
        return ((scale * de1).astype(e1.dtype),
                (scale * de2).astype(e2.dtype),
                jnp.zeros_like(lwt1), jnp.zeros_like(lwt2),
                jnp.zeros_like(t1v), jnp.zeros_like(t2v),
                jnp.zeros_like(t1v[:1]))

    core.defvjp(fwd, bwd)

    def op(e1, e2, lu1r, lu2r, t1, t2, gamma):
        b = e1.shape[0]
        t1v = jnp.broadcast_to(t1, (b,)).astype(jnp.float32)
        t2v = jnp.broadcast_to(t2, (b,)).astype(jnp.float32)
        gammav = jnp.reshape(jnp.asarray(gamma, jnp.float32), (1,))
        local, aux = core(e1, e2, lu1r, lu2r, sg(t1v), sg(t2v), sg(gammav))
        B = e1.shape[0] * (_axis_prod(axes) if axes else 1)
        if reduce == "local":
            # ct on ``local/B`` is 1/B, so bwd's ct*B*de* yields exactly
            # the closed-form grads of the global *mean* loss
            return local / B, aux
        loss = (_psum(local, axes) if axes else local) / B
        return loss, aux

    return op


# ---------------------------------------------------------------------------
# OpenCLIP-style baseline reduction: autodiff through all_gather
# ---------------------------------------------------------------------------

def make_allgather_ad_pair_loss(axes: Sequence[str], reduce: str = "mean"):
    axes = tuple(axes)

    def with_stats(e1, e2, lw1, lw2, t1, t2):
        b = e1.shape[0]
        B = b * _axis_prod(axes)
        off = _global_index(axes) * b
        e1a = _gather(e1, axes)     # differentiated: bwd = psum-scatter
        e2a = _gather(e2, axes)     # of (B, d) feature grads (DDP-style)
        stats = LS.row_stats(e1, e2, e1a, e2a, t1, t2, row_offset=off)
        local = LS.surrogate_loss(stats, sg(lw1), sg(lw2), 1.0)
        if reduce == "local":
            return local / B, jax.tree.map(sg, stats)
        loss = _psum(local, axes) / B
        return loss, jax.tree.map(sg, stats)

    return with_stats


def make_mbcl_loss(axes: Sequence[str], reduce: str = "mean"):
    """OpenCLIP objective (MBCL), gathered features, autodiff comms.

    ``reduce="local"`` returns the local mean contribution (no psum in
    the differentiated region — the sharded-state step psums it for the
    metric and autodiff still routes feature grads through the gather's
    psum-scatter transpose, the DDP-style comms this baseline measures)."""
    axes = tuple(axes)

    def loss_fn(e1, e2, tau):
        b = e1.shape[0]
        off = _global_index(axes) * b
        e1a = _gather(e1, axes)
        e2a = _gather(e2, axes)
        B = e1a.shape[0]
        # image->text: local image rows vs all texts
        s1 = jnp.einsum("bd,Bd->bB", e1, e2a,
                        preferred_element_type=jnp.float32) / tau
        # text->image: local text rows vs all images
        s2 = jnp.einsum("bd,Bd->bB", e2, e1a,
                        preferred_element_type=jnp.float32) / tau
        labels = off + jnp.arange(b)
        def ce(s):
            logz = jax.nn.logsumexp(s, axis=1)
            gold = jnp.take_along_axis(s, labels[:, None], axis=1)[:, 0]
            return jnp.sum(logz - gold)
        local = 0.5 * (ce(s1) + ce(s2))
        if reduce == "local":
            return local / B
        return _psum(local, axes) / B

    return loss_fn
