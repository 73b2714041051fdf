"""The per-sample noise fill of ``repro.data.rng``: batches whose rows
hold ``FAN_OUT_ROW`` normals or more split their rows over the shared pool
and still give the bytes of a plain per-sample loop, as smaller ones do
on the caller's thread; the fill counts; the launcher's ``noise:`` line;
streaming decode workers that fan out into the pool."""
import contextlib
import io
import os
import re
import sys
import threading

import numpy as np
import pytest

from repro.data import (ContrastiveDataset, ShardedLoader, StreamingDataset,
                        StreamingLoader, write_contrastive_shards)
from repro.data import rng as R
from repro.launch import train as LT

NOISE_LINE = r"noise: (\d+) of (\d+) fills on (\d+) threads"


@pytest.fixture()
def width4(monkeypatch):
    """A pool of four, so the fan-out path runs whatever the cores here."""
    monkeypatch.setattr(R, "pool_width", lambda: 4)


def _dataset(n=1000, image_size=64):
    return ContrastiveDataset(n=n, image_size=image_size, context_length=16,
                              vocab_size=512, n_classes=8, seed=5)


def _plain_noise(key, idx, shape):
    return np.stack([R.sample_generator(key, i).standard_normal(
        shape, dtype=np.float32) for i in idx])


def _fanned_out(call):
    before = R.fills()
    out = call()
    after = R.fills().since(before)
    assert (after.serial, after.parallel) == (0, 1)
    return out


@pytest.mark.parametrize("which", ["add_gaussian_noise", "contrastive"])
def test_fan_out_matches_plain_per_sample_reference(which, width4):
    """400 rows of 64x64x3 (12,288 normals a row, over the threshold)
    split over the pool equal ``base + scale * noise`` drawn one sample at
    a time."""
    ds = _dataset()
    idx = np.random.default_rng(1).permutation(ds.n)[:400]
    base = ds.clean_images(idx)
    assert base[0].size >= R.FAN_OUT_ROW
    want = base + np.float32(ds.noise) * _plain_noise(ds._img_key, idx,
                                                      base.shape[1:])
    if which == "contrastive":
        got = _fanned_out(lambda: ds.images(idx))
    else:
        got = _fanned_out(lambda: R.add_gaussian_noise(
            base, ds.noise, ds._img_key, idx))
    assert got.dtype == want.dtype and got.shape == want.shape
    assert got.tobytes() == want.tobytes()


def test_permutation_invariance_over_the_threshold(width4):
    """``batch([i])`` (serial) equals row i of ``batch(perm)`` (fanned
    out) for every sample of a 400-row permutation."""
    ds = _dataset()
    perm = np.random.default_rng(2).permutation(ds.n)[:400]
    full = _fanned_out(lambda: ds.batch(perm))
    for pos, i in enumerate(perm):
        single = ds.batch(np.asarray([i]))
        for k in full:
            np.testing.assert_array_equal(full[k][pos], single[k][0],
                                          err_msg=f"{k!r}, sample {i}")


def test_fill_counts_serial_parallel_and_width(monkeypatch):
    key = R.stream_key(0, "test")
    big = R.FAN_OUT_ROW                        # rows just at it
    for width, parallel in ((4, True), (1, False)):
        monkeypatch.setattr(R, "pool_width", lambda: width)
        start = R.fills()
        small = R.per_sample_normal(key, np.arange(8), (big - 1,))
        large = R.per_sample_normal(key, np.arange(100), (big,))
        got = R.fills().since(start)
        assert (got.serial, got.parallel) == ((1, 1) if parallel else (2, 0))
        assert got.width == width
        assert re.fullmatch(NOISE_LINE, str(got))
        np.testing.assert_array_equal(small, _plain_noise(
            key, np.arange(8), (big - 1,)))
        np.testing.assert_array_equal(large[[0, 57, 99]], _plain_noise(
            key, [0, 57, 99], (big,)))


def test_pool_width_leaves_two_cores():
    assert R.pool_width() == max(1, len(os.sched_getaffinity(0)) - 2)


@pytest.mark.parametrize("rows,shape", [(512, (512,)), (64, (32, 32, 3))])
def test_serial_fill_matches_plain_per_sample_reference(rows, shape):
    """Rows under the threshold (the (b, 512) embeddings, toy images) stay
    on the caller's thread, scale and add over the whole batch, and give
    the same bytes as ``base + scale * noise`` drawn one sample at a
    time."""
    key = R.stream_key(3, "test")
    idx = np.random.default_rng(3).permutation(4 * rows)[:rows]
    base = np.random.default_rng(4).standard_normal(
        (rows,) + shape).astype(np.float32)
    want = base + np.float32(0.3) * _plain_noise(key, idx, shape)
    before = R.fills()
    got = R.add_gaussian_noise(base, 0.3, key, idx)
    after = R.fills().since(before)
    assert (after.serial, after.parallel) == (1, 0)
    assert got.dtype == np.float32 and got.tobytes() == want.tobytes()


def test_noise_base_must_be_float32():
    key = R.stream_key(0, "test")
    with pytest.raises(TypeError, match="float32"):
        R.add_gaussian_noise(np.zeros((2, 4)), 0.3, key, [0, 1])


def test_clean_images_render_each_prototype_block():
    """The shard writer's clean images and the batch's base share one
    rendering: each prototype value repeated over an (up, up) block."""
    ds = _dataset(n=50, image_size=48)
    idx = np.asarray([7, 3, 49, 3])
    protos = ds.protos[ds.classes[idx]]
    want = np.repeat(np.repeat(protos, 6, axis=1), 6, axis=2)
    assert ds.clean_images(idx).tobytes() == want.tobytes()


def test_counts_are_exact_under_concurrent_callers():
    """More caller threads than cores, switching often: no fill is lost
    from the counts."""
    key = R.stream_key(1, "test")
    n_threads, calls = 16, 50
    start = R.fills()
    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        threads = [threading.Thread(target=lambda: [
            R.per_sample_normal(key, [3, 1], (4,)) for _ in range(calls)])
            for _ in range(n_threads)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
        assert not any(t.is_alive() for t in threads)
    finally:
        sys.setswitchinterval(old)
    assert R.fills().since(start).serial == n_threads * calls


def test_launcher_prints_its_noise_line():
    """After the ``input:`` line the launcher says how its batches' noise
    was made: at toy sizes every fill stays serial."""
    argv = ["--arch", "clip-vitb32-cc12m", "--reduced", "--global-batch",
            "16", "--n-samples", "64", "--steps", "6", "--log-every", "1"]
    out = io.StringIO()
    with contextlib.redirect_stdout(out):
        LT.main(argv)
    lines = out.getvalue().splitlines()
    at = [k for k, line in enumerate(lines) if re.fullmatch(NOISE_LINE, line)]
    assert len(at) == 1
    assert lines[at[0] - 1].startswith("input: queue empty at ")
    parallel, total, width = map(int, re.fullmatch(
        NOISE_LINE, lines[at[0]]).groups())
    assert (parallel, total, width) == (0, 6, R.pool_width())


def test_streaming_decode_workers_fan_out_into_the_shared_pool(tmp_path,
                                                               width4):
    """Two decode workers each hand a 96-row chunk of 128x128x3 images
    (4.7M normals) to the shared pool while the in-memory loader fans out
    its own batches: the streams stay equal bit for bit, and the run ends
    inside its time limit (a pool task that waited on another could hang
    it)."""
    ds = _dataset(n=384, image_size=128)
    root = str(tmp_path / "shards")
    write_contrastive_shards(ds, root, samples_per_shard=128)
    strm = StreamingLoader(StreamingDataset(root), global_batch=192,
                           seed=4, workers=2, decode_ahead=3)
    mem = ShardedLoader(ds, global_batch=192, seed=4)
    start, compared, errors = R.fills(), [], []

    def compare():
        try:
            for (ea, sa, ia, ba), (eb, sb, ib, bb) in zip(
                    mem.steps(4), strm.steps(4), strict=True):
                assert (ea, sa) == (eb, sb)
                np.testing.assert_array_equal(ia, ib)
                for k in ba:
                    assert ba[k].tobytes() == bb[k].tobytes(), k
                compared.append(sa)
        except BaseException as e:        # surfaced on the test's thread
            errors.append(e)

    t = threading.Thread(target=compare, daemon=True)
    t.start()
    t.join(timeout=120)
    strm.dataset.close()
    assert not t.is_alive(), "streaming through the shared pool hung"
    if errors:
        raise errors[0]
    assert compared == [0, 1, 2, 3]
    # 4 in-memory batches and 4 x 2 decode chunks, all fanned out
    assert R.fills().since(start).parallel == 12
