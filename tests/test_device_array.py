"""``repro.data.device_array``: a host batch array copied flat and
reshaped on the device equals ``jnp.asarray`` of it, in shape, type and
bytes; the launcher's batches reach the step that way."""
import contextlib
import io

import jax.numpy as jnp
import numpy as np
import pytest

from repro.data import device_array
from repro.data.pipeline import unflatten
from repro.launch import train as LT


@pytest.mark.parametrize("x", [
    np.random.default_rng(0).standard_normal((4, 16, 16, 3)).astype(
        np.float32),                                      # images
    np.arange(4 * 77, dtype=np.int32).reshape(4, 77),     # token ids
    np.arange(6, dtype=np.int64).reshape(2, 3),           # narrowed to i32
    np.float32(2.5),                                      # a scalar
    np.zeros((0, 5), np.float32),                         # no rows
], ids=["images", "texts", "int64", "scalar", "empty"])
def test_device_array_equals_asarray(x):
    got, want = device_array(x), jnp.asarray(x)
    assert got.shape == want.shape and got.dtype == want.dtype
    assert np.asarray(got).tobytes() == np.asarray(want).tobytes()


def test_device_array_reads_a_strided_view():
    x = np.arange(2 * 6 * 4, dtype=np.float32).reshape(2, 6, 4)[:, ::2]
    np.testing.assert_array_equal(np.asarray(device_array(x)), x)


def test_launcher_feeds_the_step_device_arrays(monkeypatch):
    """Every array of every batch the launcher copies goes through
    ``device_array``."""
    seen = []

    def spy(x):
        seen.append(np.shape(x))
        return device_array(x)
    monkeypatch.setattr(LT, "device_array", spy)
    argv = ["--arch", "clip-vitb32-cc12m", "--reduced", "--global-batch",
            "8", "--n-samples", "32", "--steps", "3", "--log-every", "1"]
    with contextlib.redirect_stdout(io.StringIO()):
        LT.main(argv)
    assert len(seen) == 3 * 2                  # images and texts a step
    assert sorted(set(seen)) == sorted({(8, 32, 32, 3), (8, 16)})


@pytest.mark.parametrize("shape", [(3, 8, 8, 3), (2, 5, 7, 4), (1, 2, 2, 1),
                                   (4, 6), (7,)])
def test_unflatten_equals_reshape(shape):
    """The image-batch route through (h, w*c, b) lays out the same values
    as a reshape, for any four sizes; other ranks reshape."""
    x = np.arange(np.prod(shape), dtype=np.float32)
    np.testing.assert_array_equal(
        np.asarray(unflatten(jnp.asarray(x), shape)), x.reshape(shape))
