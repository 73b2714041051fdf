"""Exact global image<->text retrieval by a streaming chunked top-k scan.

Memory contract (the eval-scale mirror of the loss engine's no-(B, B)
guarantee, PR 1): the (N_rows, N_cols) similarity matrix is **never
materialized in HBM**.  Columns stream through the scan in chunks of
``chunk``: each step computes one (rows, chunk) similarity block, merges
it into the running per-row top-k carry by one lexicographic sort of
(k + chunk) candidates, and truncates back to k.  Peak live intermediate
is O(rows * (k + chunk)) — independent of N_cols.  The test battery
checks the lowered HLO for the absence of any (N, N) buffer (with the
dense oracle as positive control).

Exactness: top-k selection under the shared (score desc, index asc) tie
rule (repro.eval.metrics) is a selection, so merge + truncate is exact —
the streaming scan equals the dense ``lex_topk`` oracle bit-for-bit, for
any chunk size, given bit-equal similarity blocks.

Sharded form: the same rectangular (local-rows x gathered-cols) shape the
loss engine uses, under the same ``shard_map`` axes — rows are sharded by
sample ownership, columns are ALL_GATHERed (``distributed.gather_axes``,
global order), and each device streams its own rows' scan.  Per-row
results depend only on that row and the gathered columns, so the K-device
output rows are identical to the single-device ones.
"""
from __future__ import annotations

from typing import Sequence

import jax
import jax.numpy as jnp

from repro.core import distributed as D
from repro.eval import metrics as M

CHUNK = 1024     # default column-chunk size of the streaming scan


def streaming_topk(rows, cols, k, *, chunk=CHUNK, n_cols=None):
    """Per-row top-k of ``rows @ cols.T`` without materializing it.

    rows: (b, d); cols: (Np, d), possibly padded — ``n_cols`` gives the
    number of valid columns (default: all).  Returns (scores (b, k),
    idx (b, k)) ordered by (score desc, index asc); padded/invalid
    columns can never appear (their sort key is (+inf, n_cols))."""
    b, d = rows.shape
    N = int(cols.shape[0]) if n_cols is None else int(n_cols)
    k = min(k, N)
    pad = (-cols.shape[0]) % chunk
    if pad:
        cols = jnp.pad(cols, ((0, pad), (0, 0)))
    n_chunks = cols.shape[0] // chunk
    rows = rows.astype(jnp.float32)
    cols = cols.astype(jnp.float32)

    init = (jnp.full((b, k), jnp.inf, jnp.float32),        # -score carry
            jnp.full((b, k), N, jnp.int32))                # index carry

    def body(c, carry):
        neg_c, idx_c = carry
        block = jax.lax.dynamic_slice_in_dim(cols, c * chunk, chunk)
        s = jnp.einsum("bd,cd->bc", rows, block,
                       preferred_element_type=jnp.float32)
        ids = c * chunk + jnp.arange(chunk, dtype=jnp.int32)
        ok = ids < N
        neg = jnp.where(ok[None, :], -s, jnp.inf)
        idb = jnp.broadcast_to(jnp.where(ok, ids, N), (b, chunk))
        sn, si = jax.lax.sort(
            (jnp.concatenate([neg_c, neg], axis=1),
             jnp.concatenate([idx_c, idb], axis=1)),
            dimension=1, num_keys=2)
        return sn[:, :k], si[:, :k]

    neg, idx = jax.lax.fori_loop(0, n_chunks, body, init)
    return -neg, idx


def retrieval_topk(e1n, e2n, k, *, chunk=CHUNK):
    """Both retrieval directions, single device.  Returns
    ((s_i2t, i_i2t), (s_t2i, i_t2i)), each (N, k)."""
    return (streaming_topk(e1n, e2n, k, chunk=chunk),
            streaming_topk(e2n, e1n, k, chunk=chunk))


def make_sharded_topk(axes, k, *, chunk=CHUNK, n_cols=None):
    """For use *inside* shard_map over ``axes``: local rows vs gathered
    columns (the loss engine's rectangular contract).  ``n_cols``: global
    number of *valid* columns (default: the full gathered count) — lets a
    padded-to-K batch exclude its zero pad rows from candidacy.  Returns
    fn(rows_local, cols_local) -> (scores, idx), row-sharded."""
    axes = tuple(axes)

    def fn(rows_local, cols_local):
        cols = D.gather_axes(cols_local, axes)
        n = (cols_local.shape[0] * D.axis_prod(axes) if n_cols is None
             else n_cols)
        return streaming_topk(rows_local, cols, k, chunk=chunk, n_cols=n)

    return fn


def sharded_retrieval_topk(mesh, axes, e1n, e2n, k, *, chunk=CHUNK,
                           n_valid=None):
    """Both directions under shard_map: rows sharded over ``axes``,
    columns gathered per device.  N must divide the axis product (pad
    upstream — see ``sharded_retrieval_recalls``; ``n_valid`` excludes
    the pad rows from column candidacy).  Output rows are in global
    order and bit-identical to ``retrieval_topk``."""
    from jax.sharding import PartitionSpec as P
    axes = tuple(axes)
    pspec = P(axes)
    topk = make_sharded_topk(axes, k, chunk=chunk, n_cols=n_valid)

    def inner(e1l, e2l):
        s1, i1 = topk(e1l, e2l)
        s2, i2 = topk(e2l, e1l)
        return s1, i1, s2, i2

    fn = jax.shard_map(inner, mesh=mesh, in_specs=(pspec, pspec),
                       out_specs=(pspec,) * 4, check_vma=False)
    s1, i1, s2, i2 = fn(e1n, e2n)
    return (s1, i1), (s2, i2)


def retrieval_recalls(e1n, e2n, ks: Sequence[int] = (1, 5, 10), *,
                      chunk=CHUNK) -> dict:
    """Exact global R@k, both directions, gold = diagonal pairing.
    Returns {"i2t_r@k": ..., "t2i_r@k": ...} for each k."""
    N = e1n.shape[0]
    (s1, i1), (s2, i2) = retrieval_topk(e1n, e2n, min(max(ks), N),
                                        chunk=chunk)
    gold = jnp.arange(N, dtype=jnp.int32)
    out = M.recall_at_k(i1, gold, ks, prefix="i2t_r@")
    out.update(M.recall_at_k(i2, gold, ks, prefix="t2i_r@"))
    return out


def sharded_retrieval_recalls(mesh, axes, e1n, e2n,
                              ks: Sequence[int] = (1, 5, 10), *,
                              chunk=CHUNK) -> dict:
    """R@k via the sharded streaming scan.  Ragged N is padded with zero
    rows up to the axis product; pad rows are excluded from column
    candidacy (``n_valid``) and masked out of the recall means, so the
    valid rows' results are bit-identical to the unpadded single-device
    scan."""
    N = e1n.shape[0]
    K = 1
    for ax in axes:
        K *= mesh.shape[ax]
    pad = (-N) % K
    if pad:
        z = jnp.zeros((pad, e1n.shape[1]), e1n.dtype)
        e1p = jnp.concatenate([e1n, z], axis=0)
        e2p = jnp.concatenate([e2n, z], axis=0)
    else:
        e1p, e2p = e1n, e2n
    (s1, i1), (s2, i2) = sharded_retrieval_topk(mesh, axes, e1p, e2p,
                                                min(max(ks), N),
                                                chunk=chunk, n_valid=N)
    gold = jnp.arange(N + pad, dtype=jnp.int32)
    valid = gold < N
    out = M.recall_at_k(i1, gold, ks, valid=valid, prefix="i2t_r@")
    out.update(M.recall_at_k(i2, gold, ks, valid=valid, prefix="t2i_r@"))
    return out
