"""Golden-value fixtures for the loss engine (v1/v2/v3): seeded small
cases through make_fcco_loss_op (dense, f32) — loss, log-u updates,
feature grads, shifted dg/dtau and row shifts.

Regenerate (only when the numerics are *intentionally* changed):

    PYTHONPATH=src python tests/golden/regen.py

tests/test_golden.py asserts the current engine (dense AND fused)
reproduces these values, so kernel tuning can't silently drift numerics.
The inputs are rebuilt from jax.random.PRNGKey and only outputs are
stored, so the fixtures hold for the installed jax only: what
``jax.random.split``/``normal`` return changed when jax 0.5 turned
``jax_threefry_partitionable`` on by default.  After such a change,
regenerate; test_golden_fixtures_match_f64_oracle checks the new
fixtures against the f64 oracle, independently of the engine.
"""
import json
import os

import jax
import jax.numpy as jnp

GOLDEN_DIR = os.path.dirname(os.path.abspath(__file__))

B, D = 12, 8
GAMMA, EPS = 0.5, 1e-14

# (name, tau spec, scale_by_tau): v2 uses per-row taus; the taumin case
# pins the exact-LSE regime (raw exponents past the old clamp)
CASES = [
    ("v1", ("scalar", 0.07), True),
    ("v2", ("per_row", None), True),
    ("v3", ("scalar", 0.05), True),
    ("v3_taumin", ("scalar", 0.01), True),
]


def inputs(case):
    from repro.core import losses as LS
    ks = jax.random.split(jax.random.PRNGKey(0), 5)
    e1 = LS.l2_normalize(jax.random.normal(ks[0], (B, D)))
    e2 = LS.l2_normalize(jax.random.normal(ks[1], (B, D)))
    lu1 = jnp.log(jax.random.uniform(ks[2], (B,)) + 0.1)
    lu2 = jnp.log(jax.random.uniform(ks[3], (B,)) + 0.1)
    kind, val = dict((c[0], c[1]) for c in CASES)[case]
    if kind == "per_row":
        tau = jax.random.uniform(ks[4], (B,)) * 0.05 + 0.03
    else:
        tau = jnp.asarray(val, jnp.float32)
    return e1, e2, lu1, lu2, tau


def compute(case, loss_impl="dense"):
    """Run the engine on the fixture inputs; returns plain-float dict."""
    from repro.core import distributed as D_
    scale_by_tau = dict((c[0], c[2]) for c in CASES)[case]
    e1, e2, lu1, lu2, tau = inputs(case)
    op = D_.make_fcco_loss_op(None, EPS, scale_by_tau,
                              loss_impl=loss_impl, interpret=True)

    def f(a, b):
        loss, _ = op(a, b, lu1, lu2, tau, tau, GAMMA)
        return loss

    loss, (de1, de2) = jax.value_and_grad(f, argnums=(0, 1))(e1, e2)
    _, (lu1n, lu2n, stats, sat) = op(e1, e2, lu1, lu2, tau, tau, GAMMA)
    g1, g2, dg1, dg2, m1, m2 = stats
    arr = lambda x: [float(v) for v in jnp.ravel(x)]
    return {"loss": float(loss), "lu1_new": arr(lu1n), "lu2_new": arr(lu2n),
            "de1": arr(de1), "de2": arr(de2), "g1": arr(g1), "g2": arr(g2),
            "dg1_dtau": arr(dg1), "dg2_dtau": arr(dg2), "m1": arr(m1),
            "m2": arr(m2), "sat": arr(sat)}


def main():
    for case, _, _ in CASES:
        out = compute(case)
        fp = os.path.join(GOLDEN_DIR, f"fcco_{case}.json")
        with open(fp, "w") as f:
            json.dump(out, f, indent=1)
        print("wrote", fp)


if __name__ == "__main__":
    import sys
    sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
        GOLDEN_DIR)), "src"))
    main()
