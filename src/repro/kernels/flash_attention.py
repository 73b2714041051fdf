"""Pallas TPU flash attention: causal / non-causal / sliding-window, online
softmax, (BQ x BK) tiles in VMEM, f32 accumulators in scratch.

Layout: q/k/v are (BH, S, hd) — batch*heads flattened to the leading grid
axis.  Rectangular (Sq != Sk) and non-multiple-of-tile shapes are handled
by padding (padded k columns are masked inside the kernel; padded q rows
are computed and sliced off).

``flash_mha`` is the *training* entry point ((B, S, H, hd) layout, matching
``repro.models.attention``): Pallas forward wrapped in ``jax.custom_vjp``
with the backward served by re-differentiating the chunked pure-JAX
online-softmax path (rematerialization — no attention matrix or softmax
residuals are saved between forward and backward).  Off-TPU the kernel runs
in interpret mode: the correctness surface, not a CPU speedup.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

BQ = 256
BK = 256
NEG = -1e30


def default_interpret() -> bool:
    """Pallas interpret mode everywhere but real TPU backends."""
    return jax.default_backend() != "tpu"


def mxu_dot(a, b, dimension_numbers):
    """In-kernel ``dot_general`` accumulating in f32.  bf16 operands take
    DEFAULT precision: their products are exact in the f32 accumulator,
    and Mosaic refuses a bf16 dot at the HIGHEST precision that a
    ``jax.default_matmul_precision("highest")`` context would request.
    f32 operands keep the context's precision."""
    precision = jax.lax.Precision.DEFAULT if a.dtype == jnp.bfloat16 \
        else None
    return jax.lax.dot_general(a, b, dimension_numbers, precision=precision,
                               preferred_element_type=jnp.float32)


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
                  scale, causal, window, n_valid_k, n_k_blocks):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG)
        l_ref[...] = jnp.zeros_like(l_ref)

    q = q_ref[0]                       # (BQ, hd)
    k = k_ref[0]                       # (BK, hd)
    s = mxu_dot(q, k, (((1,), (1,)), ((), ()))) * scale
    q_pos = qi * BQ + jax.lax.broadcasted_iota(jnp.int32, (BQ, BK), 0)
    k_pos = ki * BK + jax.lax.broadcasted_iota(jnp.int32, (BQ, BK), 1)
    mask = k_pos < n_valid_k
    if causal:
        mask &= k_pos <= q_pos
    if window:
        mask &= k_pos > q_pos - window
    s = jnp.where(mask, s, NEG)

    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
    p = jnp.where(mask, jnp.exp(s - m_new[:, None]), 0.0)
    alpha = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=1)
    acc_ref[...] = (acc_ref[...] * alpha[:, None]
                    + mxu_dot(p.astype(v_ref.dtype), v_ref[0],
                              (((1,), (0,)), ((), ()))))
    m_ref[...] = m_new

    @pl.when(ki == n_k_blocks - 1)
    def _fin():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = (acc_ref[...] / l[:, None]).astype(o_ref.dtype)


def flash_attention(q, k, v, *, causal=True, window=0, interpret=False):
    """q/k/v: (B, H, S, hd) -> (B, H, S, hd)."""
    B, H, Sq, hd = q.shape
    Sk = k.shape[2]
    scale = 1.0 / np.sqrt(hd)
    qf = q.reshape(B * H, Sq, hd)
    kf = k.reshape(B * H, Sk, hd)
    vf = v.reshape(B * H, Sk, hd)
    pq, pk = (-Sq) % BQ, (-Sk) % BK
    if pq:
        qf = jnp.pad(qf, ((0, 0), (0, pq), (0, 0)))
    if pk:
        kf = jnp.pad(kf, ((0, 0), (0, pk), (0, 0)))
        vf = jnp.pad(vf, ((0, 0), (0, pk), (0, 0)))
    nq, nk = (Sq + pq) // BQ, (Sk + pk) // BK
    grid = (B * H, nq, nk)

    out = pl.pallas_call(
        functools.partial(_flash_kernel, scale=scale, causal=causal,
                          window=window, n_valid_k=Sk, n_k_blocks=nk),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, BQ, hd), lambda b, qi, ki: (b, qi, 0)),
            pl.BlockSpec((1, BK, hd), lambda b, qi, ki: (b, ki, 0)),
            pl.BlockSpec((1, BK, hd), lambda b, qi, ki: (b, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, BQ, hd), lambda b, qi, ki: (b, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((B * H, Sq + pq, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((BQ, hd), jnp.float32),
            pltpu.VMEM((BQ,), jnp.float32),
            pltpu.VMEM((BQ,), jnp.float32),
        ],
        interpret=interpret,
    )(qf, kf, vf)
    return out[:, :Sq].reshape(B, H, Sq, hd)


# ---------------------------------------------------------------------------
# Training entry point: custom-vjp flash forward + chunked remat backward
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash_mha(q, k, v, causal, window, interpret, q_chunk, kv_chunk):
    """(B, S, H, hd) layout.  Forward = the Pallas kernel above; backward =
    autodiff through the chunked online-softmax path (its own remat)."""
    o = flash_attention(q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
                        v.transpose(0, 2, 1, 3), causal=causal,
                        window=window, interpret=interpret)
    return o.transpose(0, 2, 1, 3)


def _flash_mha_fwd(q, k, v, causal, window, interpret, q_chunk, kv_chunk):
    return (_flash_mha(q, k, v, causal, window, interpret, q_chunk,
                       kv_chunk), (q, k, v))


def _flash_mha_bwd(causal, window, interpret, q_chunk, kv_chunk, res, ct):
    # Recompute-based backward: the chunked path streams (q_chunk, kv_chunk)
    # blocks with its own online softmax + jax.checkpoint, so the (S, S)
    # matrix is never resident in the backward either.
    from repro.models.attention import chunked_attention
    q, k, v = res
    _, vjp = jax.vjp(
        lambda a, b, c: chunked_attention(a, b, c, causal=causal,
                                          window=window, q_chunk=q_chunk,
                                          kv_chunk=kv_chunk), q, k, v)
    return vjp(ct)


_flash_mha.defvjp(_flash_mha_fwd, _flash_mha_bwd)


def flash_mha(q, k, v, *, causal=True, window=0, interpret=None,
              q_chunk=None, kv_chunk=None):
    """Training flash attention.  q: (B, Sq, H, hd), k/v: (B, Sk, H, hd)
    (GQA heads already repeated), any Sq/Sk.  Returns (B, Sq, H, hd) in the
    q dtype.  ``interpret=None`` auto-selects interpret mode off-TPU;
    ``q_chunk``/``kv_chunk`` bound the remat backward's block sizes —
    unset values come from the autotune table (see repro.kernels.autotune;
    produced by ``benchmarks/autotune_bench.py``) with the shipped 512/1024
    as fallback."""
    if interpret is None:
        interpret = default_interpret()
    if q_chunk is None or kv_chunk is None:
        from repro.kernels import autotune
        cfg = autotune.kernel_config("flash_mha", dtype=q.dtype,
                                     interpret=interpret, sq=q.shape[1],
                                     sk=k.shape[1], hd=q.shape[3])
        if q_chunk is None:
            q_chunk = cfg["q_chunk"]
        if kv_chunk is None:
            kv_chunk = cfg["kv_chunk"]
    return _flash_mha(q, k, v, bool(causal), int(window), bool(interpret),
                      int(q_chunk), int(kv_chunk))
