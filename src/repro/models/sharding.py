"""Optional activation-sharding annotations.

Model code is mesh-agnostic; the launcher calls ``set_batch_axes`` so that
``constrain`` pins key activations (logits, residual stream) to the right
PartitionSpec under GSPMD.  With no mesh configured (unit tests, CPU runs)
``constrain`` is a no-op.
"""
from __future__ import annotations

from typing import Optional, Sequence, Tuple

import jax
from jax.sharding import PartitionSpec as P

_BATCH_AXES: Optional[Tuple[str, ...]] = None
_SEQ_AXIS: Optional[str] = None    # sequence parallelism (§Perf), off by default
_MOE_A2A_MESH = None               # mesh => use all-to-all expert routing
_INNER_REMAT = True                # False: fewer FSDP re-gathers, more mem


def set_inner_remat(v: bool):
    global _INNER_REMAT
    _INNER_REMAT = v


def inner_remat() -> bool:
    return _INNER_REMAT


def set_batch_axes(axes: Optional[Sequence[str]], seq_axis=None):
    global _BATCH_AXES, _SEQ_AXIS
    _BATCH_AXES = tuple(axes) if axes else None
    _SEQ_AXIS = seq_axis


def configured_batch_axes() -> Optional[Tuple[str, ...]]:
    """The GSPMD batch axes currently configured (None = constrain is a
    no-op).  The manual sharded-state train step requires None: inside
    its shard_map, sharding constraints don't apply — the (data, fsdp)
    layout is carried by the shard_map specs instead."""
    return _BATCH_AXES


def enable_moe_a2a(mesh):
    """All-to-all expert routing (§Perf).  Requires the batch to be
    sharded over the model axis too (fsdp layout)."""
    global _MOE_A2A_MESH
    _MOE_A2A_MESH = mesh


def moe_a2a_enabled() -> bool:
    return _MOE_A2A_MESH is not None and _BATCH_AXES is not None \
        and "model" in _BATCH_AXES


def apply_moe_sharded(moe_params, cfg, x):
    """shard_map island running the a2a expert router over the mesh."""
    from jax.sharding import PartitionSpec as P
    from repro.models.moe import apply_moe_a2a_local
    mesh = _MOE_A2A_MESH
    ba = _BATCH_AXES

    def inner(p, h):
        y, aux = apply_moe_a2a_local(p, cfg, h, axis="model")
        aux = jax.tree.map(
            lambda a: jax.lax.pmean(a, axis_name=ba), aux)
        return y, aux

    wspec = {k: (P("model", None, None) if v.ndim >= 3 else P())
             for k, v in moe_params.items()
             if k in ("w_gate", "w_up", "w_down")}
    pspec = {k: (wspec[k] if k in wspec else jax.tree.map(lambda _: P(), v))
             for k, v in moe_params.items()}
    xspec = P(ba, None, None)
    return jax.shard_map(inner, mesh=mesh, in_specs=(pspec, xspec),
                         out_specs=(xspec, P()),
                         check_vma=False)(moe_params, x)


def constrain(x, dims):
    """dims: tuple like ("batch", None, "model"); "batch" expands to the
    configured batch axes, "seq" to the sequence axis if enabled."""
    if _BATCH_AXES is None:
        return x
    spec = []
    for d in dims:
        if d == "batch":
            spec.append(_BATCH_AXES)
        elif d == "seq":
            spec.append(_SEQ_AXIS)   # may be None -> replicated
        elif d is not None and d in _BATCH_AXES:
            spec.append(None)        # axis already consumed by the batch
        else:
            spec.append(d)
    return jax.lax.with_sharding_constraint(x, P(*spec))
