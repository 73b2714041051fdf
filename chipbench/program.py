"""What the benchmark's drivers share: the program's step and state built as
its launcher builds them from a configuration file, the readings that
``correct`` compares, and the plain reference's readings on the same
batches.  Each kind of traffic has its own driver in ``drivers/``.
"""
from __future__ import annotations

import dataclasses
import re
from typing import Callable, Optional, Tuple

from chipbench import harness


@dataclasses.dataclass(frozen=True)
class Variant:
    """A change to the timed path: extra launcher flags (the program's own
    lower-precision path, the control) or a wrapper around its step (a
    planted fault)."""
    argv: Tuple[str, ...] = ()
    wrap: Optional[Callable] = None


def timed(jitted, variant: Optional[Variant], batch_size: int):
    """The step the window drives: the program's jitted step, or the
    variant's wrapper around it."""
    if variant is not None and variant.wrap is not None:
        return variant.wrap(jitted, batch_size)
    return jitted


def program_arch(c: dict):
    """The program's architecture for configuration ``c``: its registered
    one with every width taken from the file."""
    from repro.configs import get_arch
    dc = dataclasses
    base = get_arch(c["program_arch"])
    if c["vision_mlp_ratio"] != 4 or c["vision_rope_theta"] != 10000.0:
        raise harness.Refused("the program's ViT fixes MLP ratio 4 and "
                              "rotary theta 1e4")
    clip = dc.replace(base.clip, image_size=c["image_size"],
                      patch_size=c["patch_size"],
                      vision_layers=c["vision_layers"],
                      vision_width=c["vision_width"],
                      vision_heads=c["vision_heads"],
                      embed_dim=c["embed_dim"],
                      context_length=c["context_length"])
    return dc.replace(base, n_layers=c["text_layers"],
                      d_model=c["text_width"], n_heads=c["text_heads"],
                      n_kv_heads=c["text_heads"], d_ff=c["text_mlp"],
                      vocab_size=c["vocab_size"],
                      rope_theta=c["text_rope_theta"], clip=clip)


def program_argv(c: dict, mix: dict, seed: int):
    h = c["hyper"]
    return ["--arch", c["program_arch"], "--version", h["version"],
            "--optimizer", h["optimizer"],
            "--global-batch", str(c["global_batch"]),
            "--n-samples", str(c["n_samples"]), "--steps", str(mix["steps"]),
            "--lr", repr(h["lr"]), "--wd", repr(h["wd"]),
            "--rho", repr(h["rho"]), "--eps", repr(h["eps"]),
            "--gamma-min", repr(h["gamma_min"]), "--seed", str(seed)]


def reference_hyper(c: dict, mix: dict) -> dict:
    spe = c["n_samples"] // c["global_batch"]
    return dict(c["hyper"], steps=mix["steps"], steps_per_epoch=spe,
                gamma_decay_epochs=max(1, mix["steps"] // (2 * spe)))


def check_stated(tc, c: dict, variant: Optional[Variant]) -> None:
    """Refuses a step config that departs from what the file states.  Only
    a variant's own flags may change the tower precision."""
    h, fc = c["hyper"], tc.fc
    stated = {k: h[k] for k in ("version", "tau_init", "lr_tau", "tau_min",
                                "tau_lr_decay_at", "rho", "eps")}
    got = {k: getattr(fc, k) for k in stated}
    precision = tc.resolved_precision.name
    departs = (got != stated or tc.wd != h["wd"] or tc.grad_clip or tc.guard
               or tc.optimizer.name != h["optimizer"])
    if variant is None or not variant.argv:
        departs = departs or precision != "f32"
    if departs:
        raise harness.Refused(f"the program's step departs from the "
                              f"configuration: {got}, precision {precision}")


def _paths(tree):
    import jax
    return [jax.tree_util.keystr(p)
            for p, _ in jax.tree_util.tree_flatten_with_path(tree)[0]]


def _norms(tree, minus=None):
    import jax
    import jax.numpy as jnp
    if minus is not None:
        tree = jax.tree.map(jnp.subtract, tree, minus)
    return [jnp.sqrt(jnp.sum(jnp.square(x))) for x in jax.tree.leaves(tree)]


def leaf_norms(tree, minus=None, scale: float = 1.0) -> dict:
    """leaf path -> f32 norm of the leaf (of ``tree - minus``), times
    ``scale``; one jitted call."""
    import jax
    global _jit_norms
    if _jit_norms is None:
        _jit_norms = jax.jit(_norms)
    vals = jax.device_get(_jit_norms(tree, minus))
    return {k: float(v) * scale for k, v in zip(_paths(tree), vals)}


def copy_tree(tree):
    """A copy of ``tree`` on the device in one jitted call (it outlives the
    donation of the original)."""
    import jax
    import jax.numpy as jnp
    global _jit_copy
    if _jit_copy is None:
        _jit_copy = jax.jit(lambda t: jax.tree.map(jnp.copy, t))
    return _jit_copy(tree)


_jit_norms = _jit_copy = None


def peak_bytes(dev) -> int:
    stats = dev.memory_stats() or {}
    return int(stats.get("peak_bytes_in_use", 0))


def build_step(c: dict, mix: dict, seed: int,
               variant: Optional[Variant] = None):
    """(step config, jitted step) as the launcher builds them from its own
    parser for configuration ``c``; ``variant``'s flags change them."""
    from repro.core import train_step as TS
    from repro.launch import train as LT
    from repro.launch.steps import donated_jit
    argv = program_argv(c, mix, seed) + list(variant.argv if variant else ())
    args = LT.build_parser().parse_args(argv)
    tc = LT.train_step_config(args, program_arch(c),
                              c["n_samples"] // c["global_batch"],
                              sharded=False)
    check_stated(tc, c, variant)
    return tc, donated_jit(TS.make_train_step(tc))


def init_state(tc, seed: int):
    """The program's train state from the seed, in one jitted call."""
    import jax
    from repro.core import train_step as TS
    return jax.jit(lambda k: TS.init_train_state(k, tc))(
        jax.random.PRNGKey(seed))


def program_readings(c: dict, step, state, pool):
    """The first ``check_steps`` steps through ``step`` on distinct pool
    batches: (state, {loss per step, first gradient's and the whole
    change's leaf norms})."""
    from jax.profiler import TraceAnnotation as span
    p0 = copy_tree(state["params"])
    got = {"loss": []}
    for k in range(c["check_steps"]):
        with span("chipbench.check_step"):
            state, m = step(state, *pool[k])
        got["loss"].append(float(m["loss_value"]))
        if k == 0:
            got["grad"] = leaf_norms(
                state["opt"]["m"], scale=1 / (1 - c["hyper"]["adam_beta1"]))
    got["update"] = leaf_norms(state["params"], p0)
    return state, got


def reference_readings(c: dict, mix: dict, seed: int, batches):
    """The same readings from the plain reference on the same (batch,
    indices) pairs."""
    from chipbench.reference import Reference
    ref = Reference(c, reference_hyper(c, mix), seed)
    p0 = ref.params
    want = {"loss": []}
    for k, (batch, idx) in enumerate(batches[:c["check_steps"]]):
        want["loss"].append(ref.step(batch, idx))
        if k == 0:
            want["grad"] = leaf_norms(
                ref.m, scale=1 / (1 - c["hyper"]["adam_beta1"]))
    want["update"] = leaf_norms(ref.params, p0)
    return want


def abstract(tree):
    """Shape, type and placement of every array of ``tree``: what lowering
    a step needs, kept before its arguments are donated."""
    import jax
    return jax.tree.map(lambda x: jax.ShapeDtypeStruct(
        x.shape, x.dtype, sharding=x.sharding), tree)


# element types narrower than the configuration's float32
NARROW = re.compile(r"^(bf16|f16|f8\w*|i8|ui8|i4|ui4)$")
_PRODUCT = re.compile(
    r"stablehlo\.(?:dot_general|dot|convolution|custom_call)\b.*:\s*"
    r"\(([^()]*)\)\s*->")
_ELEMENT = re.compile(r"tensor<(?:[0-9?]+x)*(\w+)>")


def narrow_products(text: str) -> int:
    """Matrix products, convolutions and custom calls of a lowered program
    (StableHLO text) with an operand of a type narrower than float32."""
    return sum(any(NARROW.match(t) for t in _ELEMENT.findall(m.group(1)))
               for m in _PRODUCT.finditer(text))


def narrow_dots(jitted, args) -> int:
    """``narrow_products`` of the program that ``jitted`` runs on ``args``
    (arrays or ``abstract`` ones): the window's own jit, lowered again
    (its trace is cached), so the count is of what the window ran."""
    return narrow_products(jitted.lower(*args).as_text())
