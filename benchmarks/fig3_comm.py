"""Paper Fig. 3 / §4 claim: the FastCLIP gradient reduction moves fewer
bytes than the OpenCLIP-style (DDP) reduction, and the gap grows with
worker count.  Dry-run analog: collective bytes from the lowered HLO at
K = 4, 8 workers (subprocess with forced host devices) plus the 256-chip
numbers from experiments/dryrun if present."""
import json
import os
import subprocess
import sys
import textwrap

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_SCRIPT = textwrap.dedent("""
    import os, sys, json
    K = int(sys.argv[1])
    os.environ["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={K}"
    sys.path.insert(0, os.path.join(sys.argv[2], "src"))
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import Mesh, PartitionSpec as P
    from repro.core import distributed as D, losses as LS
    from repro.roofline.analysis import collective_stats
    mesh = Mesh(np.array(jax.devices()).reshape(K), ("data",))
    b, dim = 128, 512
    B = b * K
    fcco_op = D.make_fcco_loss_op(("data",), 1e-14, True,
                                  loss_impl="dense")
    def make(red):
        def inner(e1l, e2l, u1l, u2l):
            sg = jax.lax.stop_gradient
            e1n, e2n = LS.l2_normalize(e1l), LS.l2_normalize(e2l)
            if red == "fastclip":   # production engine: no stats pre-pass
                loss, _ = fcco_op(e1n, e2n, u1l, u2l, 0.07, 0.07, 0.5)
                return loss
            off = jax.lax.axis_index("data") * e1l.shape[0]
            e1a = jax.lax.all_gather(sg(e1n), "data", tiled=True)
            e2a = jax.lax.all_gather(sg(e2n), "data", tiled=True)
            st = LS.row_stats(sg(e1n), sg(e2n), e1a, e2a, 0.07, 0.07,
                              row_offset=off)
            lg1, lg2 = LS.log_g(st)
            lw1, lw2 = LS.fcco_log_weights(
                LS.update_log_u(u1l, lg1, .5),
                LS.update_log_u(u2l, lg2, .5), 0.07, 0.07, 1e-14)
            f = D.make_allgather_ad_pair_loss(("data",))
            loss, _ = f(e1n, e2n, lw1, lw2, 0.07, 0.07)
            return loss
        def outer(e1, e2, u1, u2):
            return jax.shard_map(inner, mesh=mesh,
                                 in_specs=(P("data"),)*4, out_specs=P(),
                                 check_vma=False)(e1, e2, u1, u2)
        return lambda e1, e2, u1, u2: jax.grad(
            lambda a, c: outer(a, c, u1, u2), argnums=(0, 1))(e1, e2)
    args = ((jax.ShapeDtypeStruct((B, dim), jnp.float32),)*2
            + (jax.ShapeDtypeStruct((B,), jnp.float32),)*2)
    out = {}
    for red in ("fastclip", "allgather_ad"):
        comp = jax.jit(make(red)).lower(*args).compile()
        cs = collective_stats(comp.as_text(), default_group=K)
        out[red] = {"bytes": cs.total_bytes, "counts": cs.counts}
    print(json.dumps(out))
""")


def run(steps=None, seed=None):
    rows = []
    for K in (4, 8):
        # forced host devices: the child never reaches for a chip the
        # parent process may hold
        p = subprocess.run([sys.executable, "-c", _SCRIPT, str(K), ROOT],
                           capture_output=True, text=True, timeout=300,
                           env=dict(os.environ, JAX_PLATFORMS="cpu"))
        if p.returncode != 0:
            raise RuntimeError(f"fig3 K={K} worker failed "
                               f"(rc={p.returncode}): {p.stderr[-2000:]}")
        out = json.loads(p.stdout.strip().splitlines()[-1])
        fb = out["fastclip"]["bytes"]
        ob = out["allgather_ad"]["bytes"]
        rows.append((f"fig3/K={K}/fastclip", 0.0, f"coll_bytes={fb}"))
        rows.append((f"fig3/K={K}/openclip-style", 0.0,
                     f"coll_bytes={ob};reduction={100*(1-fb/ob):.1f}%"))
    # 256-chip numbers from the dry-run sweep, if available
    for red in ("fastclip", "allgather_ad"):
        fp = os.path.join(ROOT, "experiments", "dryrun",
                          f"qwen3-1.7b__train_4k__16x16__contrastive__{red}"
                          ".json")
        if os.path.exists(fp):
            d = json.load(open(fp))
            rows.append((f"fig3/256chips/{red}", 0.0,
                         f"coll_bytes_per_dev="
                         f"{d['collective_bytes_per_device']:.3e}"))
    return rows
