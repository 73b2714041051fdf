"""Compile-only checks of the main path's Pallas kernels for a TPU v5e.

The TPU compiler is installed even where no chip is attached, so each
kernel is lowered and compiled at ViT-B/32 widths for a described v5e
chip.  This catches what interpret mode cannot: a block layout or tiling
the chip's compiler refuses (the FCCO kernels' 1-D vector blocks were
refused this way), or too much VMEM.  Nothing runs, so these tests say
nothing about results or times.  One more check reads the device layouts
the compiler gives the launcher's batches: why they are copied flat.

The topology is described inside a fixture, never at import: only one
process may load the TPU library, and every test worker imports this file.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.experimental.compilation_cache import compilation_cache
from jax.sharding import SingleDeviceSharding

from repro.data.pipeline import unflatten
from repro.kernels.flash_attention import flash_attention
from repro.kernels.gcl_loss import gcl_pair_grads, gcl_pair_stats

b, d = 512, 512        # per-chip contrastive batch, ViT-B/32 embed dim
B_GLOBAL = 2048        # gathered columns of the sharded (rectangular) case


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without the chip; keep it out."""
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compile_text(fn, *shapes):
    return jax.jit(fn).lower(*shapes).compile().as_text()


def _gcl_shapes(one_chip, dtype, cols):
    def sds(shape, dt=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    rows = [sds((b, d), dtype)] * 2 + [sds((b,))] * 2       # e1 e2 t1 t2
    gathered = [sds((cols, d), dtype)] * 2                  # e1_all e2_all
    return sds, rows, gathered


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("cols", [b, B_GLOBAL], ids=["square", "rect"])
def test_gcl_pair_stats_compiles(one_chip, no_persistent_cache, dtype,
                                 cols):
    sds, rows, gathered = _gcl_shapes(one_chip, dtype, cols)
    if cols == b:
        text = _compile_text(
            lambda e1, e2, t1, t2: gcl_pair_stats(e1, e2, t1, t2), *rows)
    else:
        text = _compile_text(
            lambda e1, e2, t1, t2, e1a, e2a, off: gcl_pair_stats(
                e1, e2, t1, t2, e1_all=e1a, e2_all=e2a, row_offset=off),
            *rows, *gathered, sds((), jnp.int32))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("cols", [b, B_GLOBAL], ids=["square", "rect"])
def test_gcl_pair_grads_compiles(one_chip, no_persistent_cache, dtype,
                                 cols):
    sds, rows, gathered = _gcl_shapes(one_chip, dtype, cols)
    lwt = [sds((b,))] * 2
    if cols == b:
        text = _compile_text(
            lambda e1, e2, t1, t2, lw1, lw2: gcl_pair_grads(
                e1, e2, lw1, lw2, t1, t2), *rows, *lwt)
    else:
        # sd_all, lwt1_all, lwt2_all, tau1_all, tau2_all
        col_vecs = [sds((cols,))] * 5
        text = _compile_text(
            lambda e1, e2, t1, t2, lw1, lw2, e1a, e2a, sda, la1, la2, ta1,
            ta2, off: gcl_pair_grads(
                e1, e2, lw1, lw2, t1, t2, e1_all=e1a, e2_all=e2a,
                sd_all=sda, lwt1_all=la1, lwt2_all=la2, tau1_all=ta1,
                tau2_all=ta2, row_offset=off),
            *rows, *lwt, *gathered, *col_vecs, sds((), jnp.int32))
    assert "tpu_custom_call" in text


# the image tower (12 heads, 49 patches + cls, bidirectional) and the
# text tower (8 heads, 77-token context, causal) at per-chip batch 512
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("heads,seq,causal", [(12, 50, False),
                                              (8, 77, True)],
                         ids=["vit", "text"])
def test_flash_attention_compiles(one_chip, no_persistent_cache, dtype,
                                  heads, seq, causal):
    x = jax.ShapeDtypeStruct((b, heads, seq, 64), dtype, sharding=one_chip)
    text = _compile_text(
        lambda q, k, v: flash_attention(q, k, v, causal=causal), x, x, x)
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("kernel", ["stats", "grads", "flash"])
def test_bf16_kernels_compile_under_highest_precision(
        one_chip, no_persistent_cache, kernel):
    """A ``default_matmul_precision("highest")`` context does not reach the
    kernels' bf16 dots, which Mosaic refuses at HIGHEST precision."""
    def sds(shape, dt=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    e = [sds((b, d), jnp.bfloat16)] * 2
    vec = sds((b,))
    with jax.default_matmul_precision("highest"):
        if kernel == "stats":
            text = _compile_text(gcl_pair_stats, *e, vec, vec)
        elif kernel == "grads":
            text = _compile_text(gcl_pair_grads, *e, vec, vec, vec, vec)
        else:
            x = sds((b, 12, 50, 64), jnp.bfloat16)
            text = _compile_text(
                lambda q, k, v: flash_attention(q, k, v, causal=False),
                x, x, x)
    assert "tpu_custom_call" in text



def test_flat_batch_copy_keeps_host_order(one_chip, no_persistent_cache):
    """The launcher copies each batch flat (``repro.data.device_array``)
    and the device lays it out with ``unflatten``.  The image batch's own
    layout has the batch dimension minor, which a copy in that shape is
    transposed into on the host; the flat array keeps its host order
    (1-D tiles).  The relayout ends in the batch's own layout and holds
    at most about twice the batch beside it: a direct reshape laid out a
    (b, h, w, c) intermediate with c = 3 padded to 128 (42x)."""
    shape = (b, 224, 224, 3)
    flat = jax.ShapeDtypeStruct((b * 224 * 224 * 3,), jnp.float32,
                                sharding=one_chip)

    def layout(x):
        return jax.jit(lambda a: a + 1).lower(x).compile() \
            .input_formats[0][0].layout
    images = layout(jax.ShapeDtypeStruct(shape, jnp.float32,
                                         sharding=one_chip))
    assert images.major_to_minor[-1] == 0           # the batch is minor
    assert layout(flat).major_to_minor == (0,)
    assert all(len(tile) == 1 for tile in layout(flat).tiling)
    relayout = jax.jit(unflatten, static_argnums=1).lower(flat, shape) \
        .compile()
    assert relayout.output_formats.layout == images
    mem = relayout.memory_analysis()
    assert mem.temp_size_in_bytes <= 3 * mem.output_size_in_bytes
