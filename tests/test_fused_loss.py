"""The fused loss engine (make_fcco_loss_op): dense/fused parity, the
exact log-sum-exp-shifted numerics at tau -> tau_min, HBM-traffic shape of
the lowered HLO, and the one-stats-pass-per-step guarantee."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import distributed as D
from repro.core import losses as LS

EPS, GAMMA = 1e-14, 0.5


def _problem(B=96, d=48, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    e1 = LS.l2_normalize(jax.random.normal(ks[0], (B, d)))
    e2 = LS.l2_normalize(jax.random.normal(ks[1], (B, d)))
    lu1 = jnp.log(jax.random.uniform(ks[2], (B,)) + 0.1)
    lu2 = jnp.log(jax.random.uniform(ks[3], (B,)) + 0.1)
    return e1, e2, lu1, lu2


@pytest.mark.parametrize("tau", [0.07, "per_row"])
@pytest.mark.parametrize("scale_by_tau", [True, False])
def test_fused_matches_dense_single_device(tau, scale_by_tau):
    B = 96
    e1, e2, lu1, lu2 = _problem(B)
    if tau == "per_row":
        tau = jax.random.uniform(jax.random.PRNGKey(7), (B,)) * 0.05 + 0.03

    outs = {}
    for impl in ("dense", "fused"):
        op = D.make_fcco_loss_op(None, EPS, scale_by_tau, loss_impl=impl,
                                 interpret=True)

        def f(a, b):
            loss, _ = op(a, b, lu1, lu2, tau, tau, GAMMA)
            return loss

        loss, grads = jax.value_and_grad(f, argnums=(0, 1))(e1, e2)
        _, (lu1n, lu2n, stats, sat) = op(e1, e2, lu1, lu2, tau, tau,
                                         GAMMA)
        outs[impl] = (loss, grads, lu1n, lu2n, stats, sat)

    ld, gd, lu1d, lu2d, std, satd = outs["dense"]
    lf, gf, lu1f, lu2f, stf, satf = outs["fused"]
    np.testing.assert_allclose(lf, ld, rtol=1e-5)
    for a, b in zip(gf, gd):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(lu1f, lu1d, rtol=1e-5)
    np.testing.assert_allclose(lu2f, lu2d, rtol=1e-5)
    for a, b in zip(stf, std):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(satf, satd)


@pytest.mark.parametrize("tau", [0.07, 0.01])
def test_dense_op_matches_surrogate_autodiff(tau):
    """The custom-vjp closed form == autodiff of the log-domain surrogate.
    tau = 0.01 puts raw exponents far past the old EXP_CLAMP — under the
    LSE shift both sides keep the exact unclamped gradients and still
    agree."""
    B = 64
    e1, e2, lu1, lu2 = _problem(B, seed=3)

    def ref(a, b):
        st = LS.row_stats(a, b, a, b, tau, tau)
        lg1, lg2 = LS.log_g(st)
        lu1n = LS.update_log_u(lu1, lg1, GAMMA)
        lu2n = LS.update_log_u(lu2, lg2, GAMMA)
        lw1, lw2 = LS.fcco_log_weights(lu1n, lu2n, tau, tau, EPS)
        return LS.surrogate_loss(st, lw1, lw2, B)

    lr, gr = jax.value_and_grad(ref, argnums=(0, 1))(e1, e2)
    op = D.make_fcco_loss_op(None, EPS, True, loss_impl="dense")
    lo, go = jax.value_and_grad(
        lambda a, b: op(a, b, lu1, lu2, tau, tau, GAMMA)[0],
        argnums=(0, 1))(e1, e2)
    np.testing.assert_allclose(lo, lr, rtol=1e-6)
    for a, b in zip(go, gr):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6)


def test_tau_min_exact_and_paths_agree():
    """At tau = tau_min = 0.01 the raw exponent reaches ~200 (f32 exp
    overflows at ~88.7); the log-sum-exp shift keeps every path finite,
    *exact* (matches the f64 linear-domain oracle — the old clamp zeroed
    these gradients) and the dense/fused implementations comparable."""
    from repro.kernels.ref import fcco_step_f64
    B = 64
    e1, e2, lu1, lu2 = _problem(B, seed=5)
    tau = 0.01

    ref = fcco_step_f64(np.asarray(e1), np.asarray(e2), np.asarray(lu1),
                        np.asarray(lu2), tau, tau, GAMMA, EPS)
    outs = {}
    for impl in ("dense", "fused"):
        op = D.make_fcco_loss_op(None, EPS, True, loss_impl=impl,
                                 interpret=True)

        def f(a, b):
            loss, _ = op(a, b, lu1, lu2, tau, tau, GAMMA)
            return loss

        loss, grads = jax.value_and_grad(f, argnums=(0, 1))(e1, e2)
        assert np.isfinite(float(loss)), impl
        np.testing.assert_allclose(float(loss), ref["loss"], rtol=1e-5)
        for g, r in zip(grads, (ref["de1"], ref["de2"])):
            assert np.isfinite(np.asarray(g)).all(), impl
            np.testing.assert_allclose(g, r, rtol=1e-4, atol=1e-6,
                                       err_msg=impl)
        outs[impl] = (loss, grads)

    np.testing.assert_allclose(outs["fused"][0], outs["dense"][0],
                               rtol=1e-6)
    for a, b in zip(outs["fused"][1], outs["dense"][1]):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-6)

    # the kernel-level oracle stays finite too (shifted domain)
    from repro.kernels.ref import gcl_pair_stats_ref
    t = jnp.full((B,), tau)
    for o in gcl_pair_stats_ref(e1, e2, t, t):
        assert np.isfinite(np.asarray(o)).all()


@pytest.mark.parametrize("tau", [0.07, 0.01])
def test_dg_dtau_is_derivative_of_estimator(tau):
    """The closed-form shifted dg/dtau recomposes (exp(m) * dg) to the
    autodiff derivative of the true estimator w.r.t. tau — including at
    tau = 0.01, where the old clamped path dropped the saturated entries.
    The comparison runs on log-derivatives (d log g/d tau = exp(m - lg) *
    dg) to stay in f32 range."""
    B = 48
    e1, e2, _, _ = _problem(B, seed=8)

    def log_g_sum(t):
        st = LS.row_stats(e1, e2, e1, e2, t, t)
        lg1, lg2 = LS.log_g(st)
        return jnp.sum(lg1) + jnp.sum(lg2)

    auto = jax.grad(log_g_sum)(jnp.asarray(tau))
    st = LS.row_stats(e1, e2, e1, e2, tau, tau)
    lg1, lg2 = LS.log_g(st)
    closed = (jnp.sum(jnp.exp(st.m1 - lg1) * st.dg1_dtau)
              + jnp.sum(jnp.exp(st.m2 - lg2) * st.dg2_dtau))
    np.testing.assert_allclose(closed, auto, rtol=1e-4)


def _count_primitives(jaxpr, name):
    """Count ``name`` eqns in a jaxpr, recursing into sub-jaxprs."""
    import jax.extend.core as jc
    n = 0
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == name:
            n += 1
        for v in eqn.params.values():
            subs = v if isinstance(v, (list, tuple)) else [v]
            for s in subs:
                if isinstance(s, jc.ClosedJaxpr):
                    n += _count_primitives(s.jaxpr, name)
                elif isinstance(s, jc.Jaxpr):
                    n += _count_primitives(s, name)
    return n


def test_fused_step_runs_one_stats_kernel():
    """Exactly one Pallas pass in the forward (stats) and one in the
    backward (grads): no duplicated stats pre-pass survives the
    custom-vjp boundary."""
    B = 64
    e1, e2, lu1, lu2 = _problem(B, seed=6)
    op = D.make_fcco_loss_op(None, EPS, True, loss_impl="fused",
                             interpret=True)

    def f(a, b):
        loss, (lu1n, lu2n, stats, sat) = op(a, b, lu1, lu2, 0.07, 0.07,
                                            GAMMA)
        # consume the aux like the train step does (stop-grad)
        sg = jax.lax.stop_gradient
        return loss + 0.0 * jnp.sum(sg(lu1n) + sg(lu2n) + sg(sat))

    jaxpr = jax.make_jaxpr(
        lambda a, b: jax.value_and_grad(f, argnums=(0, 1))(a, b))(e1, e2)
    n_pallas = _count_primitives(jaxpr.jaxpr, "pallas_call")
    assert n_pallas == 2, f"expected 2 pallas_call (fwd stats + bwd " \
                          f"grads), found {n_pallas}"


def test_fused_hlo_has_no_dense_pair_matrix():
    """Acceptance: the lowered fused HLO materializes no (B, B) f32 pair
    matrix; the dense lowering does (the positive control)."""
    B, d = 256, 128
    e1, e2, lu1, lu2 = _problem(B, d)
    marker = f"f32[{B},{B}]"

    def grad_of(impl):
        op = D.make_fcco_loss_op(None, EPS, True, loss_impl=impl,
                                 interpret=True)

        def f(a, b):
            loss, _ = op(a, b, lu1, lu2, 0.07, 0.07, GAMMA)
            return loss

        return jax.jit(jax.grad(f, argnums=(0, 1)))

    dense_hlo = grad_of("dense").lower(e1, e2).compile().as_text()
    fused_hlo = grad_of("fused").lower(e1, e2).compile().as_text()
    assert marker in dense_hlo          # positive control
    assert marker not in fused_hlo, \
        "fused path materialized the (B, B) pair matrix"


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_fcco_op_bf16_matches_f64_reference(dtype):
    """bf16 embeddings with f32 accumulation: dense and fused paths land
    within 1e-2 of the f64 linear-domain oracle (loss, grads, log-u)."""
    from repro.kernels.ref import fcco_step_f64
    B, d = 64, 256
    e1, e2, lu1, lu2 = _problem(B, d, seed=9)
    e1c = e1.astype(dtype)
    e2c = e2.astype(dtype)
    tau = 0.05
    ref = fcco_step_f64(np.asarray(e1c, np.float32),
                        np.asarray(e2c, np.float32), np.asarray(lu1),
                        np.asarray(lu2), tau, tau, GAMMA, EPS)
    tol = 1e-5 if dtype == jnp.float32 else 1e-2
    for impl in ("dense", "fused"):
        op = D.make_fcco_loss_op(None, EPS, True, loss_impl=impl,
                                 interpret=True)
        loss, grads = jax.value_and_grad(
            lambda a, b: op(a, b, lu1, lu2, tau, tau, GAMMA)[0],
            argnums=(0, 1))(e1c, e2c)
        _, (lu1n, lu2n, _, sat) = op(e1c, e2c, lu1, lu2, tau, tau, GAMMA)
        np.testing.assert_allclose(float(loss), ref["loss"], rtol=tol)
        np.testing.assert_allclose(lu1n, ref["lu1_new"], atol=tol)
        for g, r in zip(grads, (ref["de1"], ref["de2"])):
            assert g.dtype == dtype
            np.testing.assert_allclose(np.asarray(g, np.float64), r,
                                       atol=tol * np.abs(r).max(),
                                       err_msg=f"{impl} {dtype}")
        assert float(jnp.max(sat)) == 0.0


def test_train_step_loss_impl_knob():
    """One full train step with loss_impl="fused" matches "dense"."""
    from repro.configs import get_arch
    from repro.core import fastclip as FC
    from repro.core import train_step as TS
    from repro.core.schedules import lr_warmup_cosine
    from repro.optim import adamw

    cfg = get_arch("clip-vitb32-cc12m").reduced()
    n = 64
    rng = jax.random.PRNGKey(0)
    c = cfg.clip
    batch = {
        "images": jax.random.normal(rng, (32, c.image_size, c.image_size,
                                          3)),
        "texts": jax.random.randint(rng, (32, c.context_length), 0,
                                    cfg.vocab_size),
    }
    idx = jnp.arange(32)

    results = {}
    for impl in ("dense", "fused"):
        fc = FC.FastCLIPConfig(version="v3", n_samples=n,
                               steps_per_epoch=2, gamma_decay_epochs=2)
        tc = TS.TrainStepConfig(arch=cfg, fc=fc, optimizer=adamw(),
                                lr_fn=lr_warmup_cosine(1e-3, 2, 10),
                                wd=0.1, loss_impl=impl)
        state = TS.init_train_state(jax.random.PRNGKey(1), tc)
        state, m = jax.jit(TS.make_train_step(tc))(state, batch, idx)
        results[impl] = (state, m)

    sd, md = results["dense"]
    sf, mf = results["fused"]
    np.testing.assert_allclose(mf["loss"], md["loss"], rtol=1e-5)
    np.testing.assert_allclose(mf["sat_rate"], 0.0)
    for a, b in zip(jax.tree.leaves(sf["params"]),
                    jax.tree.leaves(sd["params"])):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-5)
    # u state is log-domain: compare only the rows this batch touched
    # (untouched rows are -inf on both sides)
    np.testing.assert_allclose(sf["fc"]["u1"][idx], sd["fc"]["u1"][idx],
                               rtol=1e-5, atol=1e-7)
