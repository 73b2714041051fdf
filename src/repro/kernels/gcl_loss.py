"""Pallas TPU kernels for the FastCLIP contrastive hot-spot.

The loss layer's compute is dominated by the (b x B) pair matrix:
similarity (MXU) -> exp -> masked row reductions, twice (image/text side),
plus the same matrix re-weighted in the backward.  These kernels stream the
matrix through VMEM in (BR x BC) tiles (flash-attention style): the b x B
matrix never touches HBM.

    gcl_pair_stats : forward statistics in shift-decomposed form —
                     per-row max m and shifted sums g, dg/dtau (true
                     estimator = exp(m) * sum; see losses.RowStats).
                     Online-softmax recurrence: the running row max is
                     carried across BC tiles and the accumulators are
                     rescaled by exp(m_old - m_new) when it grows, so no
                     exponent ever exceeds 0 — exact at tau -> tau_min.
    gcl_pair_grads : closed-form backward (de1, de2) of the FCCO
                     surrogate with log-domain weights: every pair enters
                     as exp(z + lwt), lwt = log(w) - log(tau), which is
                     bounded above by log(B/gamma) — no running max is
                     needed in the backward, and losses.EXP_CLAMP remains
                     only as the last-resort guard.

Both kernels come in the *rectangular sharded* form used by the production
loss engine (repro.core.distributed.make_fcco_loss_op): the anchor rows are
the (b, d) local pairs of one device, the columns the (B, d) gathered
global batch, and ``row_offset`` gives the global index of local row 0 so
the diagonal is masked correctly on a non-square grid.  The single-device
case is the square specialization (columns = rows, offset 0).

Row indices are passed in as an int32 vector (padded with -1) rather than
derived from the grid position because ``row_offset`` is a traced value
inside shard_map (it comes from ``axis_index``).

Per-row vectors (row ids, s_ii, taus, log-weights and the row-stat
outputs) travel as (n, 1) columns in (BR, 1) blocks and per-column
vectors as (1, n) rows in (1, BC) blocks: the TPU compiler refuses 1-D
blocks smaller than the whole array (the XLA tiling of an s32[512]
operand does not match Mosaic's), and the 2-D forms arrive already
broadcast against the (BR, BC) tile.

Tiles are 128-aligned for the MXU; inputs may be bf16 (blocks stay bf16 in
VMEM — half the feature traffic) with all accumulation in f32
(``preferred_element_type``).  For wide embeddings both kernels block the
feature dimension too (``d_block`` set, or auto above D_BLOCK_MAX):

  * the stats kernel gains an inner grid d axis, the partial similarity
    tiles accumulate in f32 VMEM scratch, and the online-softmax update
    runs once per (row, col) tile on the completed sums — (BR, d)-sized
    blocks never have to fit VMEM.  Column blocks are outside the d axis
    so output rows are still revisited sequentially.
  * the grads kernel uses a *two-phase* grid (r, c, phase, k): phase 0
    sweeps the d chunks accumulating the (BR, BC) similarity tiles in
    VMEM scratch; phase 1 forms the pair-weight tiles once (k == 0, into
    scratch) and then sweeps the d chunks again, accumulating each
    (BR, d_block) slice of de1/de2 against the matching column-feature
    chunk — so no full-d feature or gradient block is ever resident.
    The de output blocks are revisited across column tiles
    (non-consecutively, since k is the fastest grid axis), a pattern
    Pallas TPU does not guarantee to preserve across grid steps —
    validated in interpret mode only, so the grads d-blocking is
    **opt-in** (explicit ``d_block``; no auto threshold like the stats
    kernel) until the ROADMAP TPU-tuning item validates it on device.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.losses import EXP_CLAMP, MASK_NEG
from repro.kernels import autotune
from repro.kernels.flash_attention import mxu_dot

# Shipped tile defaults.  Call sites that leave ``br``/``bc``/``d_block``
# unset consult the autotune table (repro.kernels.autotune, produced by
# ``benchmarks/autotune_bench.py``) first and fall back to these.
BR = 128          # row tile
BC = 128          # col tile
D_BLOCK_MAX = 2048   # above this, the stats kernel blocks the feature dim


def _resolve_tiles(kernel, dtype, interpret, br, bc, d_block, **dims):
    """Fill unset tile knobs from the tuning table; explicit caller
    arguments always win, and with no table entry the shipped defaults
    above apply unchanged."""
    if br is None or bc is None or d_block is None:
        cfg = autotune.kernel_config(kernel, dtype=dtype,
                                     interpret=interpret, **dims)
        if br is None:
            br = cfg["br"]
        if bc is None:
            bc = cfg["bc"]
        if d_block is None:
            d_block = cfg["d_block"]
    return int(br), int(bc), d_block


def _pad_rows(x, m, value=0.0):
    pad = (-x.shape[0]) % m
    if pad:
        x = jnp.pad(x, ((0, pad),) + ((0, 0),) * (x.ndim - 1),
                    constant_values=value)
    return x


def _pad_cols(x, m):
    pad = (-x.shape[1]) % m
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad)))
    return x


def _pad_vec(x, n, m, value=0.0):
    """Broadcast ``x`` to (n,), cast f32, pad up to a multiple of m."""
    return _pad_rows(jnp.broadcast_to(x, (n,)).astype(jnp.float32), m, value)


def _row_vec(x, n, m, value=0.0):
    """``_pad_vec`` as an (n_pad, 1) column, blocked (BR, 1)."""
    return _pad_vec(x, n, m, value)[:, None]


def _col_vec(x, n, m, value=0.0):
    """``_pad_vec`` as a (1, n_pad) row, blocked (1, BC)."""
    return _pad_vec(x, n, m, value)[None, :]


# ---------------------------------------------------------------------------
# Forward stats kernel (online softmax over column tiles)
# ---------------------------------------------------------------------------

def _stats_kernel(rid_ref, e1r_ref, e2r_ref, e1c_ref, e2c_ref, sdr_ref,
                  t1_ref, t2_ref, g1_ref, g2_ref, dg1_ref, dg2_ref,
                  m1_ref, m2_ref, s1_acc, s2_acc, *, n_cols, n_d_blocks,
                  br, bc):
    c = pl.program_id(1)
    k = pl.program_id(2)

    @pl.when((c == 0) & (k == 0))
    def _init():
        g1_ref[...] = jnp.zeros_like(g1_ref)
        g2_ref[...] = jnp.zeros_like(g2_ref)
        dg1_ref[...] = jnp.zeros_like(dg1_ref)
        dg2_ref[...] = jnp.zeros_like(dg2_ref)
        m1_ref[...] = jnp.full_like(m1_ref, MASK_NEG)
        m2_ref[...] = jnp.full_like(m2_ref, MASK_NEG)

    @pl.when(k == 0)
    def _zero_acc():
        s1_acc[...] = jnp.zeros_like(s1_acc)
        s2_acc[...] = jnp.zeros_like(s2_acc)

    # partial similarity over this d chunk; f32 accumulation in scratch
    s1_acc[...] += mxu_dot(
        e1r_ref[...], e2c_ref[...], (((1,), (1,)), ((), ())))
    s2_acc[...] += mxu_dot(
        e2r_ref[...], e1c_ref[...], (((1,), (1,)), ((), ())))

    @pl.when(k == n_d_blocks - 1)
    def _online_update():
        sd = sdr_ref[...].astype(jnp.float32)            # (br, 1)
        rows = rid_ref[...]                              # (br, 1) global
        cols = c * bc + jax.lax.broadcasted_iota(jnp.int32, (br, bc), 1)
        mask = (rows != cols) & (cols < n_cols) & (rows >= 0)
        for s, t_ref, g_ref, dg_ref, m_ref in (
                (s1_acc[...], t1_ref, g1_ref, dg1_ref, m1_ref),
                (s2_acc[...], t2_ref, g2_ref, dg2_ref, m2_ref)):
            t = t_ref[...].astype(jnp.float32)
            z = jnp.where(mask, (s - sd) / t, MASK_NEG)
            m_new = jnp.maximum(m_ref[...],
                                jnp.max(z, axis=1, keepdims=True))
            # MASK_NEG - MASK_NEG == 0 (finite sentinel), so alpha == 1 on
            # still-empty rows instead of nan
            alpha = jnp.exp(m_ref[...] - m_new)
            p = jnp.where(mask, jnp.exp(z - m_new), 0.0)
            g_ref[...] = g_ref[...] * alpha + jnp.sum(p, axis=1,
                                                      keepdims=True)
            dg_ref[...] = (dg_ref[...] * alpha
                           + jnp.sum(p * -(s - sd), axis=1, keepdims=True)
                           / (t ** 2))
            m_ref[...] = m_new


def gcl_pair_stats(e1, e2, tau1, tau2, *, e1_all=None, e2_all=None,
                   row_offset=0, interpret=False, d_block=None,
                   br=None, bc=None):
    """e1/e2: (b, d) normalized anchor rows (f32 or bf16); tau1/tau2:
    scalar or (b,).

    Square case (default): columns are the rows themselves.  Rectangular
    sharded case: ``e1_all``/``e2_all`` are the (B, d) gathered batch and
    ``row_offset`` (may be traced) is the global index of local row 0.
    ``br``/``bc``/``d_block``: tile sizes — unset knobs come from the
    autotune table when it has an entry for this shape/dtype/backend, else
    the shipped defaults (BR, BC, and d_block = whole d, auto-blocked above
    D_BLOCK_MAX).  Returns the shift-decomposed stats
    (g1, g2, dg1, dg2, m1, m2), each (b,) f32, in losses.RowStats order:
    true g = exp(m) * g (sums already divided by B-1)."""
    b, d = e1.shape
    if e1_all is None:
        e1_all, e2_all = e1, e2
    B = e1_all.shape[0]
    br, bc, d_block = _resolve_tiles("gcl_stats", e1.dtype, interpret,
                                     br, bc, d_block, b=b, cols=B, d=d)
    if d_block is None:
        d_block = d if d <= D_BLOCK_MAX else D_BLOCK_MAX
    sd = jnp.sum(e1.astype(jnp.float32) * e2.astype(jnp.float32), axis=-1)
    rid = row_offset + jnp.arange(b, dtype=jnp.int32)
    ridp = _pad_rows(rid, br, value=-1)[:, None]
    e1p = _pad_cols(_pad_rows(e1, br), d_block)
    e2p = _pad_cols(_pad_rows(e2, br), d_block)
    e1cp = _pad_cols(_pad_rows(e1_all, bc), d_block)
    e2cp = _pad_cols(_pad_rows(e2_all, bc), d_block)
    sdp = _row_vec(sd, b, br)
    t1p = _row_vec(tau1, b, br, 1.0)
    t2p = _row_vec(tau2, b, br, 1.0)
    bp, Bp, dp = e1p.shape[0], e1cp.shape[0], e1p.shape[1]
    nk = dp // d_block
    grid = (bp // br, Bp // bc, nk)

    row_spec = pl.BlockSpec((br, d_block), lambda r, c, k: (r, k))
    col_spec = pl.BlockSpec((bc, d_block), lambda r, c, k: (c, k))
    vec_row = pl.BlockSpec((br, 1), lambda r, c, k: (r, 0))

    out = pl.pallas_call(
        functools.partial(_stats_kernel, n_cols=B, n_d_blocks=nk,
                          br=br, bc=bc),
        grid=grid,
        in_specs=[vec_row, row_spec, row_spec, col_spec, col_spec,
                  vec_row, vec_row, vec_row],
        out_specs=[vec_row] * 6,
        out_shape=[jax.ShapeDtypeStruct((bp, 1), jnp.float32)] * 6,
        scratch_shapes=[pltpu.VMEM((br, bc), jnp.float32)] * 2,
        interpret=interpret,
    )(ridp, e1p, e2p, e1cp, e2cp, sdp, t1p, t2p)
    denom = float(max(B - 1, 1))
    g1, g2, dg1, dg2, m1, m2 = (o[:b, 0] for o in out)
    return g1 / denom, g2 / denom, dg1 / denom, dg2 / denom, m1, m2


# ---------------------------------------------------------------------------
# Backward kernel: de1/de2 of the FCCO surrogate, log-domain weights
# ---------------------------------------------------------------------------

def _grads_kernel(rid_ref, e1r_ref, e2r_ref, e1c_ref, e2c_ref, sdr_ref,
                  sdc_ref, lwt1r_ref, lwt2r_ref, lwt1c_ref, lwt2c_ref,
                  t1r_ref, t2r_ref, t1c_ref, t2c_ref, de1_ref, de2_ref,
                  r1_ref, r2_ref, *, n_cols, br, bc):
    c = pl.program_id(1)

    @pl.when(c == 0)
    def _init():
        de1_ref[...] = jnp.zeros_like(de1_ref)
        de2_ref[...] = jnp.zeros_like(de2_ref)
        r1_ref[...] = jnp.zeros_like(r1_ref)
        r2_ref[...] = jnp.zeros_like(r2_ref)

    e1c = e1c_ref[...]
    e2c = e2c_ref[...]
    sdr = sdr_ref[...].astype(jnp.float32)
    sdc = sdc_ref[...].astype(jnp.float32)

    rows = rid_ref[...]                              # (br, 1) global ids
    cols = c * bc + jax.lax.broadcasted_iota(jnp.int32, (br, bc), 1)
    mask = (rows != cols) & (cols < n_cols) & (rows >= 0)

    s1 = mxu_dot(e1r_ref[...], e2c, (((1,), (1,)), ((), ())))
    s2 = mxu_dot(e2r_ref[...], e1c, (((1,), (1,)), ((), ())))

    def a(z):
        # exp(z + lwt) <= B/gamma by the log-domain weight bound; the
        # EXP_CLAMP min is the shared last-resort guard only
        return jnp.where(mask, jnp.exp(jnp.minimum(z, EXP_CLAMP)), 0.0)

    # row vectors are (br, 1), column vectors (1, bc)
    a1 = a((s1 - sdr) / t1r_ref[...] + lwt1r_ref[...])
    a2 = a((s2 - sdr) / t2r_ref[...] + lwt2r_ref[...])
    # transpose blocks: m1[p, j] = A1[j, p] over column anchors j
    #   A1[j, p] = exp((e1_j.e2_p - sd_j)/t1_j + lwt1_j); e1_j.e2_p = s2[p, j]
    m1 = a((s2 - sdc) / t1c_ref[...] + lwt1c_ref[...])
    #   A2[j, p] = exp((e2_j.e1_p - sd_j)/t2_j + lwt2_j); e2_j.e1_p = s1[p, j]
    m2 = a((s1 - sdc) / t2c_ref[...] + lwt2c_ref[...])

    de1_ref[...] += mxu_dot(
        (a1 + m2).astype(e2c.dtype), e2c, (((1,), (0,)), ((), ())))
    de2_ref[...] += mxu_dot(
        (a2 + m1).astype(e1c.dtype), e1c, (((1,), (0,)), ((), ())))
    r1_ref[...] += jnp.sum(a1, axis=1, keepdims=True)
    r2_ref[...] += jnp.sum(a2, axis=1, keepdims=True)


def _grads_kernel_dblocked(rid_ref, e1r_ref, e2r_ref, e1c_ref, e2c_ref,
                           sdr_ref, sdc_ref, lwt1r_ref, lwt2r_ref,
                           lwt1c_ref, lwt2c_ref, t1r_ref, t2r_ref, t1c_ref,
                           t2c_ref, de1_ref, de2_ref, r1_ref, r2_ref,
                           s1_acc, s2_acc, p1_acc, p2_acc, *, n_cols,
                           br, bc):
    """d-blocked backward: phase 0 accumulates the (br, bc) similarity
    tiles over d chunks; phase 1 forms the combined pair-weight tiles
    P1 = A1 + M2 and P2 = A2 + M1 once per (row, col) tile and streams
    the (BR, d_block) gradient chunks.  See the module docstring for the
    revisit pattern of the de output blocks."""
    c = pl.program_id(1)
    ph = pl.program_id(2)
    k = pl.program_id(3)

    # first visit of the (r, k) de block is (c == 0, phase 0)
    @pl.when((c == 0) & (ph == 0))
    def _init_de():
        de1_ref[...] = jnp.zeros_like(de1_ref)
        de2_ref[...] = jnp.zeros_like(de2_ref)

    @pl.when((c == 0) & (ph == 0) & (k == 0))
    def _init_rowsums():
        r1_ref[...] = jnp.zeros_like(r1_ref)
        r2_ref[...] = jnp.zeros_like(r2_ref)

    @pl.when(ph == 0)
    def _accum_similarity():
        @pl.when(k == 0)
        def _zero():
            s1_acc[...] = jnp.zeros_like(s1_acc)
            s2_acc[...] = jnp.zeros_like(s2_acc)

        s1_acc[...] += mxu_dot(
            e1r_ref[...], e2c_ref[...], (((1,), (1,)), ((), ())))
        s2_acc[...] += mxu_dot(
            e2r_ref[...], e1c_ref[...], (((1,), (1,)), ((), ())))

    @pl.when((ph == 1) & (k == 0))
    def _pair_weights():
        s1 = s1_acc[...]
        s2 = s2_acc[...]
        sdr = sdr_ref[...].astype(jnp.float32)
        sdc = sdc_ref[...].astype(jnp.float32)
        rows = rid_ref[...]
        cols = c * bc + jax.lax.broadcasted_iota(jnp.int32, (br, bc), 1)
        mask = (rows != cols) & (cols < n_cols) & (rows >= 0)

        def a(z):
            return jnp.where(mask, jnp.exp(jnp.minimum(z, EXP_CLAMP)), 0.0)

        a1 = a((s1 - sdr) / t1r_ref[...] + lwt1r_ref[...])
        a2 = a((s2 - sdr) / t2r_ref[...] + lwt2r_ref[...])
        m1 = a((s2 - sdc) / t1c_ref[...] + lwt1c_ref[...])
        m2 = a((s1 - sdc) / t2c_ref[...] + lwt2c_ref[...])
        p1_acc[...] = a1 + m2
        p2_acc[...] = a2 + m1
        r1_ref[...] += jnp.sum(a1, axis=1, keepdims=True)
        r2_ref[...] += jnp.sum(a2, axis=1, keepdims=True)

    @pl.when(ph == 1)
    def _accum_grads():
        e1c = e1c_ref[...]
        e2c = e2c_ref[...]
        de1_ref[...] += mxu_dot(
            p1_acc[...].astype(e2c.dtype), e2c, (((1,), (0,)), ((), ())))
        de2_ref[...] += mxu_dot(
            p2_acc[...].astype(e1c.dtype), e1c, (((1,), (0,)), ((), ())))


def gcl_pair_grads(e1, e2, lwt1, lwt2, tau1, tau2, *, e1_all=None,
                   e2_all=None, sd_all=None, lwt1_all=None, lwt2_all=None,
                   tau1_all=None, tau2_all=None, row_offset=0,
                   interpret=False, d_block=None, br=None, bc=None):
    """Closed-form (de1, de2) for L = (1/B) sum_i w1_i g1_i + w2_i g2_i
    with log-domain weights: ``lwt* = log(w*) - log(tau*)`` so that
    A[i, j] = exp(z_ij + lwt_i) — exact unclamped gradients at any tau.

    Square case: anchors == columns, all the ``*_all`` args default to the
    local ones.  Rectangular sharded case: the ``*_all`` args are the
    gathered (B,)-shaped batch quantities (features, s_ii, log-weights,
    taus) needed for the transpose terms; the returned (b, d) grads are the
    *local* rows — no collective is required on them.  Inputs may be bf16
    (f32 accumulation).  ``br``/``bc``: row/col tiles (None = table entry,
    else BR/BC).  ``d_block``: feature-dim block for the two-phase grid —
    **opt-in** (None = table entry, else whole d; unlike the stats kernel
    there is no auto threshold, since the blocked path's output-revisit
    pattern is interpret-validated only, see module docstring)."""
    b, d = e1.shape
    sd = jnp.sum(e1.astype(jnp.float32) * e2.astype(jnp.float32), axis=-1)
    if e1_all is None:
        e1_all, e2_all = e1, e2
        sd_all, lwt1_all, lwt2_all = sd, lwt1, lwt2
        tau1_all, tau2_all = tau1, tau2
    B = e1_all.shape[0]
    br, bc, d_block = _resolve_tiles("gcl_grads", e1.dtype, interpret,
                                     br, bc, d_block, b=b, cols=B, d=d)
    rid = row_offset + jnp.arange(b, dtype=jnp.int32)
    if d_block is None:
        d_block = d
    blocked = d_block < d

    e1p, e2p = _pad_rows(e1, br), _pad_rows(e2, br)
    e1cp, e2cp = _pad_rows(e1_all, bc), _pad_rows(e2_all, bc)
    if blocked:
        e1p, e2p = _pad_cols(e1p, d_block), _pad_cols(e2p, d_block)
        e1cp, e2cp = _pad_cols(e1cp, d_block), _pad_cols(e2cp, d_block)
    ridp = _pad_rows(rid, br, value=-1)[:, None]
    sdp = _row_vec(sd, b, br)
    sdcp = _col_vec(sd_all, B, bc)
    # padded rows/cols are masked out via rid/n_cols; MASK_NEG keeps their
    # exponents at -inf rather than trusting the mask alone
    lw1p = _row_vec(lwt1, b, br, MASK_NEG)
    lw2p = _row_vec(lwt2, b, br, MASK_NEG)
    lw1cp = _col_vec(lwt1_all, B, bc, MASK_NEG)
    lw2cp = _col_vec(lwt2_all, B, bc, MASK_NEG)
    t1p, t2p = _row_vec(tau1, b, br, 1.0), _row_vec(tau2, b, br, 1.0)
    t1cp = _col_vec(tau1_all, B, bc, 1.0)
    t2cp = _col_vec(tau2_all, B, bc, 1.0)
    bp, Bp, dp = e1p.shape[0], e1cp.shape[0], e1p.shape[1]

    if blocked:
        nk = dp // d_block
        grid = (bp // br, Bp // bc, 2, nk)
        row_spec = pl.BlockSpec((br, d_block), lambda r, c, p, k: (r, k))
        col_spec = pl.BlockSpec((bc, d_block), lambda r, c, p, k: (c, k))
        vrow = pl.BlockSpec((br, 1), lambda r, c, p, k: (r, 0))
        vcol = pl.BlockSpec((1, bc), lambda r, c, p, k: (0, c))
        de_spec = pl.BlockSpec((br, d_block), lambda r, c, p, k: (r, k))
        kernel = functools.partial(_grads_kernel_dblocked, n_cols=B,
                                   br=br, bc=bc)
        scratch = [pltpu.VMEM((br, bc), jnp.float32)] * 4
    else:
        grid = (bp // br, Bp // bc)
        row_spec = pl.BlockSpec((br, dp), lambda r, c: (r, 0))
        col_spec = pl.BlockSpec((bc, dp), lambda r, c: (c, 0))
        vrow = pl.BlockSpec((br, 1), lambda r, c: (r, 0))
        vcol = pl.BlockSpec((1, bc), lambda r, c: (0, c))
        de_spec = pl.BlockSpec((br, dp), lambda r, c: (r, 0))
        kernel = functools.partial(_grads_kernel, n_cols=B, br=br, bc=bc)
        scratch = []

    de1, de2, r1, r2 = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[vrow, row_spec, row_spec, col_spec, col_spec, vrow, vcol,
                  vrow, vrow, vcol, vcol, vrow, vrow, vcol, vcol],
        out_specs=[de_spec] * 2 + [vrow] * 2,
        out_shape=[jax.ShapeDtypeStruct((bp, dp), jnp.float32)] * 2
        + [jax.ShapeDtypeStruct((bp, 1), jnp.float32)] * 2,
        scratch_shapes=scratch,
        interpret=interpret,
    )(ridp, e1p, e2p, e1cp, e2cp, sdp, sdcp, lw1p, lw2p, lw1cp, lw2cp,
      t1p, t2p, t1cp, t2cp)
    kappa = 1.0 / (B * max(B - 1.0, 1.0))
    rsum = (r1 + r2)[:b]
    de1 = kappa * (de1[:b, :d] - rsum * e2.astype(jnp.float32))
    de2 = kappa * (de2[:b, :d] - rsum * e1.astype(jnp.float32))
    return de1, de2
