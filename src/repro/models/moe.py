"""Mixture-of-Experts layer: top-k routing with per-row capacity dispatch.

Expert-parallel friendly formulation: experts live on the leading axis of
the expert weights (sharded over the ``model`` mesh axis); dispatch/combine
are gathers *within each batch row* so no cross-``data``-shard routing is
needed (tokens are replicated over ``model`` inside a data shard, expert
partial outputs meet in the scatter-add, and GSPMD inserts the psum over
``model``).  Capacity per (row, expert) is ``S * top_k / E * capacity_factor``
(tokens over capacity are dropped, standard Switch-style).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ArchConfig
from repro.models import layers as L


def moe_capacity(S: int, E: int, top_k: int, factor: float) -> int:
    # capped at S (top_k over the token axis requires C <= S); decode (S=1)
    # degenerates to all-experts-compute-one-token, see DESIGN.md §Perf.
    return min(S, max(top_k, int(np.ceil(S * top_k / E * factor))))


def init_moe(rng, cfg: ArchConfig):
    d = cfg.d_model
    m = cfg.moe
    r = L.split_rngs(rng, 5)
    E, dff = m.n_experts, m.d_ff

    def expert_stack(key, in_d, out_d):
        return (jax.random.normal(key, (E, in_d, out_d), jnp.float32)
                / np.sqrt(in_d))

    p = {
        "norm": L.init_rmsnorm(d),
        "router": L.dense_init(r[0], d, E, scale=0.02),
        "w_gate": expert_stack(r[1], d, dff),
        "w_up": expert_stack(r[2], d, dff),
        "w_down": expert_stack(r[3], dff, d),
    }
    if m.shared_expert:
        p["shared"] = L.init_swiglu(r[4], d, dff)
    return p


def apply_moe(params, cfg: ArchConfig, x):
    """x: (B, S, d) -> (B, S, d) + aux losses dict."""
    m = cfg.moe
    B, S, d = x.shape
    E, k = m.n_experts, m.top_k
    C = moe_capacity(S, E, k, m.capacity_factor)

    h = L.rmsnorm(params["norm"], x)
    logits = jnp.einsum("bsd,de->bse", h, params["router"].astype(h.dtype))
    logits = logits.astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)                    # (B,S,E)
    gate_vals, gate_idx = jax.lax.top_k(probs, k)              # (B,S,k)
    gate_vals = gate_vals / jnp.maximum(
        jnp.sum(gate_vals, axis=-1, keepdims=True), 1e-9)

    # score of each token for each expert (0 unless expert in its top-k)
    sel = jnp.sum(jax.nn.one_hot(gate_idx, E, dtype=jnp.float32)
                  * gate_vals[..., None], axis=2)              # (B,S,E)
    # per (row, expert): pick top-C tokens by selection weight
    picked_w, picked_t = jax.lax.top_k(sel.transpose(0, 2, 1), C)  # (B,E,C)
    # dispatch: gather token states
    disp = jnp.take_along_axis(
        h[:, None], picked_t[..., None].astype(jnp.int32), axis=2)  # (B,E,C,d)

    # expert FFN (SwiGLU), experts on leading axis
    wg = params["w_gate"].astype(h.dtype)
    wu = params["w_up"].astype(h.dtype)
    wd = params["w_down"].astype(h.dtype)
    g = jnp.einsum("becd,edf->becf", disp, wg)
    u = jnp.einsum("becd,edf->becf", disp, wu)
    eo = jnp.einsum("becf,efd->becd", jax.nn.silu(g) * u, wd)  # (B,E,C,d)

    # combine: scatter-add weighted expert outputs back to token positions
    eo = eo * picked_w[..., None].astype(eo.dtype)
    flat_out = jnp.zeros((B, S, d), eo.dtype)
    bidx = jnp.arange(B)[:, None, None]
    flat_out = flat_out.at[bidx, picked_t].add(eo)

    if "shared" in params:
        flat_out = flat_out + L.swiglu(params["shared"], h)

    # aux losses: Switch load-balance + router z-loss
    frac_tokens = jnp.mean(
        jnp.sum(jax.nn.one_hot(gate_idx, E, dtype=jnp.float32), axis=2),
        axis=(0, 1))                                           # (E,)
    frac_probs = jnp.mean(probs, axis=(0, 1))
    lb = E * jnp.sum(frac_tokens * frac_probs) / max(k, 1)
    z = jnp.mean(jnp.square(jax.nn.logsumexp(logits, axis=-1)))
    aux = {"moe_lb": m.aux_coef * lb, "moe_z": m.router_z_coef * z}
    return x + flat_out.astype(x.dtype), aux


# ---------------------------------------------------------------------------
# Expert-parallel MoE with explicit all-to-all token routing (§Perf).
#
# Runs *inside shard_map* over the model axis: tokens stay local to their
# (data, model) shard; each (token, k-slot) item is sent to the model shard
# owning its expert via all_to_all, computed there, and sent back.  Per-
# device communication is O(local_tokens * k * d) instead of GSPMD's global
# dispatch gathers (measured 59s -> sub-second on qwen3-moe train_4k).
# ---------------------------------------------------------------------------


def apply_moe_a2a_local(params, cfg: ArchConfig, x, *, axis="model"):
    """Body for shard_map.  x: (b_local, S, d) local tokens; expert weights
    in ``params`` carry only the local experts (E_local = E / axis_size).
    Returns (y, aux) like apply_moe."""
    m = cfg.moe
    K = jax.lax.axis_size(axis)
    me = jax.lax.axis_index(axis)
    bl, S, d = x.shape
    T = bl * S
    E = m.n_experts
    E_local = params["w_gate"].shape[0]
    k = m.top_k

    h = L.rmsnorm(params["norm"], x).reshape(T, d)
    logits = (h @ params["router"].astype(h.dtype)).astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)                    # (T, E)
    gate_vals, gate_idx = jax.lax.top_k(probs, k)              # (T, k)
    gate_vals = gate_vals / jnp.maximum(
        jnp.sum(gate_vals, axis=-1, keepdims=True), 1e-9)

    # (T*k) routed items
    items_e = gate_idx.reshape(T * k)                          # expert id
    items_g = gate_vals.reshape(T * k)
    dest = items_e // E_local                                  # dest shard
    # send capacity per destination shard
    C2 = min(T * k, max(1, int(np.ceil(T * k / K * m.capacity_factor))))
    # per dest: pick top-C2 items by gate weight
    w_dest = jnp.where(dest[None, :] == jnp.arange(K)[:, None],
                       items_g[None, :] + 1e-6, 0.0)           # (K, T*k)
    sel_w, sel_items = jax.lax.top_k(w_dest, C2)               # (K, C2)
    valid = sel_w > 0.0                                        # (K, C2)
    send_x = jnp.take(h, sel_items // k, axis=0) \
        * valid[..., None].astype(h.dtype)                     # (K, C2, d)
    send_le = jnp.where(valid, jnp.take(items_e, sel_items) % E_local,
                        E_local)                               # local eid
    # exchange: recv[j] = what shard j sent to me
    recv_x = jax.lax.all_to_all(send_x, axis, split_axis=0, concat_axis=0,
                                tiled=True)                    # (K*C2, d)?
    recv_le = jax.lax.all_to_all(send_le.astype(jnp.int32), axis,
                                 split_axis=0, concat_axis=0, tiled=True)
    recv_x = recv_x.reshape(K * C2, d)
    recv_le = recv_le.reshape(K * C2)

    # local dispatch to E_local experts (capacity C3)
    C3 = min(K * C2, max(1, int(np.ceil(K * C2 / max(E_local, 1)
                                        * m.capacity_factor))))
    onemask = jnp.where(recv_le[None, :] == jnp.arange(E_local)[:, None],
                        1.0, 0.0)                              # (E_l, K*C2)
    dw, ditems = jax.lax.top_k(onemask, C3)                    # (E_l, C3)
    disp = jnp.take(recv_x, ditems, axis=0) * dw[..., None].astype(h.dtype)
    g = jnp.einsum("ecd,edf->ecf", disp, params["w_gate"].astype(h.dtype))
    u = jnp.einsum("ecd,edf->ecf", disp, params["w_up"].astype(h.dtype))
    eo = jnp.einsum("ecf,efd->ecd", jax.nn.silu(g) * u,
                    params["w_down"].astype(h.dtype))          # (E_l, C3, d)
    # scatter expert outputs back to recv-item slots
    ret = jnp.zeros((K * C2 + 1, d), eo.dtype)
    ret = ret.at[jnp.where(dw > 0, ditems, K * C2)].add(
        eo * dw[..., None].astype(eo.dtype))
    ret = ret[:K * C2].reshape(K, C2, d)
    # reverse exchange: back to the senders, same slot layout
    back = jax.lax.all_to_all(ret, axis, split_axis=0, concat_axis=0,
                              tiled=True).reshape(K, C2, d)
    # combine locally: item (t, slot k) result lives at (dest, send slot)
    out_items = jnp.zeros((T * k + 1, d), back.dtype)
    ret_idx = jnp.where(valid, sel_items, T * k).reshape(K * C2)
    out_items = out_items.at[ret_idx].add(back.reshape(K * C2, d))
    out_tok = jnp.sum(out_items[:T * k].reshape(T, k, d)
                      * gate_vals[..., None].astype(back.dtype), axis=1)

    if "shared" in params:
        out_tok = out_tok + L.swiglu(params["shared"], h)

    # aux losses (local batch stats; caller may pmean)
    frac_tokens = jnp.mean(
        jnp.sum(jax.nn.one_hot(gate_idx, E, dtype=jnp.float32), axis=1),
        axis=0)
    frac_probs = jnp.mean(probs, axis=0)
    lb = E * jnp.sum(frac_tokens * frac_probs) / max(k, 1)
    z = jnp.mean(jnp.square(jax.nn.logsumexp(logits, axis=-1)))
    aux = {"moe_lb": m.aux_coef * lb, "moe_z": m.router_z_coef * z}
    return x + out_tok.reshape(bl, S, d).astype(x.dtype), aux
