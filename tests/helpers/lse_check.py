"""Subprocess helper: the tau_min acceptance check for the exact
log-sum-exp-shifted loss engine, against a *float64 autodiff* reference
(JAX_ENABLE_X64 — linear domain is representable in f64, so the reference
needs no shift and autodiff of the plain surrogate is the ground truth).

At tau = tau_min = 0.01 with a similarity gap of 1.0 the raw pair exponent
is 100 — past f32 exp overflow (~88.7) and past the old EXP_CLAMP = 60
(whose clamp silently zeroed this gradient).  The check asserts, for dense
and fused (interpret) impls at K=1 and on a K=4 forced-host shard_map:

  * the hardest-negative feature gradient is nonzero,
  * it matches the f64 autodiff reference at 1e-4,
  * the ``sat`` aux (last-resort-guard counter) reports exactly 0.

Run: python tests/helpers/lse_check.py
"""
import os
import sys

os.environ["JAX_ENABLE_X64"] = "1"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))), "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.sharding import Mesh, PartitionSpec as P  # noqa: E402

from repro.core import distributed as D  # noqa: E402
from repro.core import losses as LS  # noqa: E402

TAU, GAMMA, EPS = 0.01, 0.5, 1e-14
B, DIM = 16, 8
GAP = 1.0


def problem():
    ks = jax.random.split(jax.random.PRNGKey(1), 4)
    e1 = np.array(LS.l2_normalize(jax.random.normal(ks[0], (B, DIM))),
                  np.float64)
    e2 = np.array(LS.l2_normalize(jax.random.normal(ks[1], (B, DIM))),
                  np.float64)
    # row 0's hardest negative (col 1) sits exactly GAP above the diagonal
    c, s = GAP / 2.0, np.sqrt(1.0 - (GAP / 2.0) ** 2)
    e1[0] = 0.0
    e1[0, 0] = 1.0
    e2[0] = 0.0
    e2[0, 0], e2[0, 1] = -c, s
    e2[1] = 0.0
    e2[1, 0], e2[1, 1] = c, s
    u1 = np.array(jax.random.uniform(ks[2], (B,)), np.float64) + 0.1
    u2 = np.array(jax.random.uniform(ks[3], (B,)), np.float64) + 0.1
    return e1, e2, u1, u2


def f64_autodiff_reference(e1, e2, u1, u2):
    """Plain linear-domain FCCO surrogate in f64, jax autodiff."""
    sg = jax.lax.stop_gradient

    def loss_fn(a, b):
        sd = jnp.sum(a * b, axis=-1)
        off = ~jnp.eye(B, dtype=bool)
        s1 = a @ b.T
        s2 = b @ a.T
        h1 = jnp.where(off, jnp.exp((s1 - sd[:, None]) / TAU), 0.0)
        h2 = jnp.where(off, jnp.exp((s2 - sd[:, None]) / TAU), 0.0)
        g1 = h1.sum(1) / (B - 1)
        g2 = h2.sum(1) / (B - 1)
        u1n = (1 - GAMMA) * u1 + GAMMA * sg(g1)
        u2n = (1 - GAMMA) * u2 + GAMMA * sg(g2)
        w1 = TAU / (EPS + u1n)
        w2 = TAU / (EPS + u2n)
        return jnp.sum(sg(w1) * g1 + sg(w2) * g2) / B

    assert jnp.asarray(e1).dtype == jnp.float64   # x64 really on
    loss, grads = jax.value_and_grad(loss_fn, argnums=(0, 1))(
        jnp.asarray(e1), jnp.asarray(e2))
    return float(loss), grads


def main():
    e1, e2, u1, u2 = problem()
    ref_loss, ref_g = f64_autodiff_reference(e1, e2, u1, u2)
    ref_hard = float(jnp.linalg.norm(ref_g[0][0]))
    print(f"f64 autodiff: loss={ref_loss:.6e} |de1[0]|={ref_hard:.4e}")
    ok = ref_hard > 1e-2     # the hardest negative repels in the truth

    e1f = jnp.asarray(e1, jnp.float32)
    e2f = jnp.asarray(e2, jnp.float32)
    lu1 = jnp.asarray(np.log(u1), jnp.float32)
    lu2 = jnp.asarray(np.log(u2), jnp.float32)

    def check(tag, grads, sat):
        nonlocal ok
        hard = float(jnp.linalg.norm(grads[0][0]))
        err = max(float(jnp.max(jnp.abs(jnp.asarray(g, jnp.float64) - r)))
                  for g, r in zip(grads, ref_g))
        scale = float(max(jnp.max(jnp.abs(r)) for r in ref_g))
        rel = err / scale
        srate = float(jnp.mean(jnp.asarray(sat)))
        good = hard > 1e-2 and rel < 1e-4 and srate == 0.0
        ok &= good
        print(f"{tag}: |de1[0]|={hard:.4e} relerr={rel:.2e} "
              f"sat_rate={srate} {'ok' if good else 'BAD'}")

    # K=1, dense + fused
    for impl in ("dense", "fused"):
        op = D.make_fcco_loss_op(None, EPS, True, loss_impl=impl,
                                 interpret=True)
        grads = jax.grad(
            lambda a, b: op(a, b, lu1, lu2, TAU, TAU, GAMMA)[0],
            argnums=(0, 1))(e1f, e2f)
        _, (_, _, _, sat) = op(e1f, e2f, lu1, lu2, TAU, TAU, GAMMA)
        check(f"K=1 {impl}", grads, sat)

    # K=4 forced-host shard_map
    mesh = Mesh(np.array(jax.devices()[:4]), ("data",))
    for impl in ("dense", "fused"):
        op = D.make_fcco_loss_op(("data",), EPS, True, loss_impl=impl,
                                 interpret=True)

        def dist(a, b):
            def inner(e1l, e2l, lu1l, lu2l):
                loss, _ = op(e1l, e2l, lu1l, lu2l, TAU, TAU, GAMMA)
                return loss
            return jax.shard_map(inner, mesh=mesh,
                                 in_specs=(P("data"),) * 4, out_specs=P(),
                                 check_vma=False)(a, b, lu1, lu2)

        def dist_sat(a, b):
            def inner(e1l, e2l, lu1l, lu2l):
                _, (_, _, _, sat) = op(e1l, e2l, lu1l, lu2l, TAU, TAU,
                                       GAMMA)
                return sat
            return jax.shard_map(inner, mesh=mesh,
                                 in_specs=(P("data"),) * 4,
                                 out_specs=P("data"),
                                 check_vma=False)(a, b, lu1, lu2)

        grads = jax.grad(dist, argnums=(0, 1))(e1f, e2f)
        check(f"K=4 {impl}", grads, dist_sat(e1f, e2f))

    print("PASS" if ok else "FAIL")
    return ok


if __name__ == "__main__":
    sys.exit(0 if main() else 1)
