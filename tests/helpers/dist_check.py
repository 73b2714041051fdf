"""Subprocess helper: multi-device checks that need forced host devices.
Run: python tests/helpers/dist_check.py <check_name>
Prints PASS/FAIL lines; exit code 0 on success.
"""
import os
import sys

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))), "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P  # noqa: E402

from repro.core import distributed as D  # noqa: E402
from repro.core import losses as LS  # noqa: E402


def mesh1d():
    return Mesh(np.array(jax.devices()).reshape(8), ("data",))


def check_vjp_equivalence():
    """FastCLIP custom-vjp grads == single-device autodiff oracle.
    All FCCO quantities in the log-sum-exp-shifted / log-u form."""
    mesh = mesh1d()
    B, d = 32, 16
    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    e1 = jax.random.normal(ks[0], (B, d))
    e2 = jax.random.normal(ks[1], (B, d))
    lu1 = jnp.log(jax.random.uniform(ks[2], (B,)) + 0.1)
    lu2 = jnp.log(jax.random.uniform(ks[3], (B,)) + 0.1)
    tau, gamma, eps = 0.07, 0.5, 1e-14

    def ref(e1, e2):
        loss, _ = LS.fcco_reference_step(e1, e2, lu1, lu2, tau, tau,
                                         gamma, eps)
        return loss

    g_ref = jax.grad(ref, argnums=(0, 1))(e1, e2)

    def dist(e1, e2, lu1, lu2, reduction):
        def inner(e1l, e2l, lu1l, lu2l):
            e1n, e2n = LS.l2_normalize(e1l), LS.l2_normalize(e2l)
            off = jax.lax.axis_index("data") * e1l.shape[0]
            sg = jax.lax.stop_gradient
            e1a = jax.lax.all_gather(sg(e1n), "data", tiled=True)
            e2a = jax.lax.all_gather(sg(e2n), "data", tiled=True)
            st = LS.row_stats(sg(e1n), sg(e2n), e1a, e2a, tau, tau,
                              row_offset=off)
            lg1, lg2 = LS.log_g(st)
            lu1n = LS.update_log_u(lu1l, lg1, gamma)
            lu2n = LS.update_log_u(lu2l, lg2, gamma)
            lw1, lw2 = LS.fcco_log_weights(lu1n, lu2n, tau, tau, eps)
            f = (D.make_fastclip_pair_loss(("data",)) if
                 reduction == "fastclip"
                 else D.make_allgather_ad_pair_loss(("data",)))
            loss, _ = f(e1n, e2n, lw1, lw2, tau, tau)
            return loss
        fn = jax.shard_map(inner, mesh=mesh, in_specs=(P("data"),) * 4,
                           out_specs=P(), check_vma=False)
        return fn(e1, e2, lu1, lu2)

    ok = True
    for red in ("fastclip", "allgather_ad"):
        g = jax.grad(lambda a, b: dist(a, b, lu1, lu2, red),
                     argnums=(0, 1))(e1, e2)
        for gd, gr in zip(g, g_ref):
            err = float(jnp.max(jnp.abs(gd - gr)))
            ok &= err < 1e-5
            print(f"{red} grad err {err:.2e}")
    print("PASS" if ok else "FAIL")
    return ok


def check_fused_parity(K=4):
    """Fused (Pallas) shard_map grads == single-device fcco_reference_step
    autodiff for v1/v2/v3, incl. the per-row tau (v2) case, on K devices."""
    mesh = Mesh(np.array(jax.devices()[:K]), ("data",))
    B, d = 32, 16
    ks = jax.random.split(jax.random.PRNGKey(0), 5)
    e1 = jax.random.normal(ks[0], (B, d))
    e2 = jax.random.normal(ks[1], (B, d))
    lu1 = jnp.log(jax.random.uniform(ks[2], (B,)) + 0.1)
    lu2 = jnp.log(jax.random.uniform(ks[3], (B,)) + 0.1)
    gamma, eps = 0.5, 1e-14
    tau_row = jax.random.uniform(ks[4], (B,)) * 0.05 + 0.03

    # (version, tau, scale_by_tau): v1/v3 share the loss-gradient form
    cases = [("v1", 0.07, True), ("v2", tau_row, True),
             ("v3", 0.05, True)]
    ok = True
    for name, tau, sbt in cases:
        def ref(a, b):
            loss, _ = LS.fcco_reference_step(a, b, lu1, lu2, tau, tau,
                                             gamma, eps, scale_by_tau=sbt)
            return loss
        g_ref = jax.grad(ref, argnums=(0, 1))(e1, e2)

        for impl in ("dense", "fused"):
            op = D.make_fcco_loss_op(("data",), eps, sbt, loss_impl=impl,
                                     interpret=True)
            tau_is_arr = jnp.ndim(tau) > 0

            def dist(a, b):
                def inner(e1l, e2l, lu1l, lu2l, t1l, t2l):
                    e1n = LS.l2_normalize(e1l)
                    e2n = LS.l2_normalize(e2l)
                    t1 = t1l if tau_is_arr else tau
                    t2 = t2l if tau_is_arr else tau
                    loss, _ = op(e1n, e2n, lu1l, lu2l, t1, t2, gamma)
                    return loss
                tspec = (P("data"),) * 2 if tau_is_arr else (P(), P())
                targ = tau if tau_is_arr else jnp.zeros(())
                fn = jax.shard_map(inner, mesh=mesh,
                                   in_specs=(P("data"),) * 4 + tspec,
                                   out_specs=P(), check_vma=False)
                return fn(a, b, lu1, lu2, targ, targ)

            g = jax.grad(dist, argnums=(0, 1))(e1, e2)
            err = max(float(jnp.max(jnp.abs(gd - gr)))
                      for gd, gr in zip(g, g_ref))
            ok &= err < 1e-5
            print(f"K={K} {name} {impl} grad err {err:.2e}")
    print("PASS" if ok else "FAIL")
    return ok


def check_comm_reduction():
    """FastCLIP reduction emits no feature-grad reduce-scatter and fewer
    collective bytes than the OpenCLIP-style reduction.  The fastclip side
    is the production engine (make_fcco_loss_op): stats + u update + loss
    in one op, no stats pre-pass / duplicated feature gathers."""
    from repro.roofline.analysis import collective_stats
    mesh = mesh1d()
    b, dim = 64, 512
    B = b * 8

    fcco_op = D.make_fcco_loss_op(("data",), 1e-14, True,
                                  loss_impl="dense")

    def make(reduction):
        def inner(e1l, e2l, lu1l, lu2l):
            sg = jax.lax.stop_gradient
            e1n, e2n = LS.l2_normalize(e1l), LS.l2_normalize(e2l)
            if reduction == "fastclip":
                loss, _ = fcco_op(e1n, e2n, lu1l, lu2l, 0.07, 0.07, 0.5)
                return loss
            off = jax.lax.axis_index("data") * e1l.shape[0]
            e1a = jax.lax.all_gather(sg(e1n), "data", tiled=True)
            e2a = jax.lax.all_gather(sg(e2n), "data", tiled=True)
            st = LS.row_stats(sg(e1n), sg(e2n), e1a, e2a, 0.07, 0.07,
                              row_offset=off)
            lg1, lg2 = LS.log_g(st)
            lu1n = LS.update_log_u(lu1l, lg1, 0.5)
            lu2n = LS.update_log_u(lu2l, lg2, 0.5)
            lw1, lw2 = LS.fcco_log_weights(lu1n, lu2n, 0.07, 0.07, 1e-14)
            f = D.make_allgather_ad_pair_loss(("data",))
            loss, _ = f(e1n, e2n, lw1, lw2, 0.07, 0.07)
            return loss

        def outer(e1, e2, u1, u2):
            return jax.shard_map(inner, mesh=mesh,
                                 in_specs=(P("data"),) * 4, out_specs=P(),
                                 check_vma=False)(e1, e2, u1, u2)

        def grad_fn(e1, e2, u1, u2):
            return jax.grad(lambda a, c: outer(a, c, u1, u2),
                            argnums=(0, 1))(e1, e2)
        return grad_fn

    args = ((jax.ShapeDtypeStruct((B, dim), jnp.float32),) * 2
            + (jax.ShapeDtypeStruct((B,), jnp.float32),) * 2)
    stats = {}
    for red in ("fastclip", "allgather_ad"):
        comp = jax.jit(make(red)).lower(*args).compile()
        stats[red] = collective_stats(comp.as_text(), default_group=8)
        print(red, stats[red].total_bytes, stats[red].counts)
    ok = (stats["fastclip"].total_bytes < 0.6
          * stats["allgather_ad"].total_bytes)
    ok &= stats["fastclip"].counts["reduce-scatter"] == 0
    ok &= stats["allgather_ad"].counts["reduce-scatter"] > 0
    print("PASS" if ok else "FAIL")
    return ok


def check_train_step_equivalence():
    """Distributed contrastive train step == single-device step (same
    params, same batch) for v3 and openclip."""
    from repro.configs import get_arch
    from repro.core import fastclip as FC
    from repro.core import train_step as TS
    from repro.core.schedules import lr_warmup_cosine
    from repro.optim import adamw

    mesh = mesh1d()
    TS.set_mesh(mesh)
    cfg = get_arch("clip-vitb32-cc12m").reduced()
    n = 64
    rng = jax.random.PRNGKey(0)
    c = cfg.clip
    batch = {
        "images": jax.random.normal(rng, (32, c.image_size, c.image_size, 3)),
        "texts": jax.random.randint(rng, (32, c.context_length), 0,
                                    cfg.vocab_size),
    }
    idx = jnp.arange(32)

    ok = True
    for version in ("v3", "openclip"):
        fc = FC.FastCLIPConfig(version=version, n_samples=n,
                               steps_per_epoch=2, gamma_decay_epochs=2)
        common = dict(arch=cfg, fc=fc, optimizer=adamw(),
                      lr_fn=lr_warmup_cosine(1e-3, 2, 10), wd=0.1)
        tc_local = TS.TrainStepConfig(**common, mesh_axes=None)
        tc_dist = TS.TrainStepConfig(**common, mesh_axes=("data",))
        state_l = TS.init_train_state(jax.random.PRNGKey(1), tc_local)
        state_d = jax.device_get(state_l)
        step_l = jax.jit(TS.make_train_step(tc_local))
        step_d = jax.jit(TS.make_train_step(tc_dist))
        sl, ml = step_l(state_l, batch, idx)
        sd, md = step_d(state_d, batch, idx)
        dl = float(jnp.abs(ml["loss"] - md["loss"]))
        # compare a couple of param leaves after the update
        pa = jax.tree.leaves(sl["params"])[0]
        pb = jax.tree.leaves(sd["params"])[0]
        dp = float(jnp.max(jnp.abs(pa - pb)))
        print(f"{version}: dloss={dl:.2e} dparam={dp:.2e}")
        ok &= dl < 1e-5 and dp < 1e-5
    print("PASS" if ok else "FAIL")
    return ok


CHECKS = {
    "vjp": check_vjp_equivalence,
    "comm": check_comm_reduction,
    "train": check_train_step_equivalence,
    "fused2": lambda: check_fused_parity(K=2),
    "fused4": lambda: check_fused_parity(K=4),
}

if __name__ == "__main__":
    name = sys.argv[1]
    sys.exit(0 if CHECKS[name]() else 1)
