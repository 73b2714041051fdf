import os, sys
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))), "src"))
import jax, jax.numpy as jnp, numpy as np, dataclasses
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from repro.configs import get_arch
from repro.models import moe as M
from repro.models import layers as L

mesh = Mesh(np.array(jax.devices()).reshape(2, 4), ("data", "model"))
cfg = get_arch("qwen3-moe-30b-a3b").reduced()
cfg = cfg.replace(moe=dataclasses.replace(cfg.moe, capacity_factor=8.0))  # no drops
params = M.init_moe(jax.random.PRNGKey(0), cfg)
B, S, d = 8, 16, cfg.d_model
x = jax.random.normal(jax.random.PRNGKey(1), (B, S, d)) * 0.5

ref, aux_ref = M.apply_moe(params, cfg, x)

def inner(p, h):
    y, aux = M.apply_moe_a2a_local(p, cfg, h, axis="model")
    return y, jax.tree.map(lambda a: jax.lax.pmean(a, axis_name=("data","model")), aux)

wspec = {k: (P("model", None, None) if getattr(v, "ndim", 0) >= 3 else P())
         for k, v in params.items() if k in ("w_gate","w_up","w_down")}
pspec = {k: (wspec[k] if k in wspec else jax.tree.map(lambda _: P(), v))
         for k, v in params.items()}
xspec = P(("data","model"), None, None)
y, aux = jax.shard_map(inner, mesh=mesh, in_specs=(pspec, xspec),
                       out_specs=(xspec, P()), check_vma=False)(params, x)
err = float(jnp.max(jnp.abs(y - ref)))
print("max err", err, "aux_lb", float(aux["moe_lb"]), float(aux_ref["moe_lb"]))
# gradient flows
g = jax.grad(lambda p: jnp.sum(jax.shard_map(inner, mesh=mesh, in_specs=(pspec, xspec),
                 out_specs=(xspec, P()), check_vma=False)(p, x)[0]**2))(params)
gn = sum(float(jnp.sum(jnp.abs(l))) for l in jax.tree.leaves(g))
print("grad norm finite:", np.isfinite(gn), gn > 0)
assert err < 2e-4, err
print("A2A MOE OK")
