"""Tests of the benchmark's own code: the trace reduction, the FLOP count,
the manifest's names and units, resolution by name, the count of narrow
products, the launcher window's opening, and the refusals."""
from __future__ import annotations

import json
import os
import re
import shutil
import subprocess
import sys

import pytest

from chipbench import flops, harness, program as P, trace as TR
from chipbench.drivers import launcher

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
TEXT = re.compile(r"^[^\t\n]{1,200}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


# -- trace reduction ---------------------------------------------------------

def _iv(name, start, end):
    return TR.Interval(name, float(start), float(end))


def test_reduce_busy_ops_and_gaps():
    ops = {"/device:TPU:0": [_iv("a", 0, 10), _iv("b", 5, 20),
                             _iv("c", 30, 40), _iv("late", 60, 70)]}
    spans = [_iv(TR.WINDOW, 0, 50), _iv("chipbench.step_dispatch", 18, 32),
             _iv("chipbench.step_wait", 38, 50)]
    out = TR.reduce(ops, spans)
    assert out["window_s"] == pytest.approx(50e-9)
    assert out["busy_s"] == pytest.approx(30e-9)       # [0,20] and [30,40]
    assert out["device_ops"][0] == ["b", pytest.approx(15e-9)]
    assert {n for n, _ in out["device_ops"]} == {"a", "b", "c"}
    assert dict(out["idle_gaps"]) == {
        "chipbench.step_dispatch": pytest.approx(10e-9),
        "chipbench.step_wait": pytest.approx(10e-9)}


def test_reduce_averages_busy_over_chips_and_labels_other():
    ops = {"/device:TPU:0": [_iv("x", 0, 100)],
           "/device:TPU:1": [_iv("x", 0, 50)]}
    out = TR.reduce(ops, [_iv(TR.WINDOW, 0, 200)])
    assert out["busy_s"] == pytest.approx(75e-9)
    assert out["idle_gaps"] == [["host:other", pytest.approx(100e-9)]]


def test_reduce_without_window_or_ops_is_none():
    assert TR.reduce({"/device:TPU:0": [_iv("a", 0, 1)]}, []) is None
    assert TR.reduce({}, [_iv(TR.WINDOW, 0, 1)]) is None


def test_load_reads_host_spans_of_a_cpu_trace(tmp_path):
    import jax
    import jax.numpy as jnp
    f = jax.jit(lambda x: jnp.sin(x) @ x)
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    TR.start(str(tmp_path))
    with jax.profiler.TraceAnnotation(TR.WINDOW):
        for _ in range(3):
            with jax.profiler.TraceAnnotation("chipbench.step_dispatch"):
                y = f(x)
            y.block_until_ready()
    jax.profiler.stop_trace()
    ops, spans = TR.load(str(tmp_path))
    names = [s.name for s in spans]
    assert names.count(TR.WINDOW) == 1
    assert names.count("chipbench.step_dispatch") == 3
    win = next(s for s in spans if s.name == TR.WINDOW)
    assert all(win.start <= s.start <= s.end <= win.end for s in spans)
    assert ops == {}                    # the CPU has no device plane


# -- FLOP count --------------------------------------------------------------

def _config(name):
    return harness.load_json(os.path.join(harness.HERE, "configs",
                                          f"{name}.json"))


def _hand_count(patch, batch):
    """ViT-B/`patch` CLIP by hand: (2 FLOPs per multiply-add)."""
    n = (224 // patch) ** 2
    s = n + 1
    image = (2 * n * (3 * patch * patch) * 768
             + 12 * (2 * s * 768 * 768 * 4 + 2 * 2 * s * 768 * 3072
                     + 2 * 2 * s * s * 768)
             + 2 * 768 * 512)
    text = (12 * (2 * 77 * 512 * 512 * 4 + 2 * 2 * 77 * 512 * 2048
                  + 2 * 2 * (77 * 78 // 2) * 512)
            + 2 * 512 * 512)
    loss = 2 * batch * 512
    return 3 * (image + text + loss)


@pytest.mark.parametrize("name,patch,batch,about", [
    ("clip-vitb32-cc12m", 32, 512, 44.3e9),
    ("clip-vitb16-laion", 16, 640, 123.4e9)])
def test_flops_per_pair_matches_hand_count(name, patch, batch, about):
    got = flops.per_pair(_config(name), batch)
    assert got == pytest.approx(_hand_count(patch, batch), rel=1e-12)
    assert got == pytest.approx(about, rel=0.005)


# -- manifest ----------------------------------------------------------------

def test_manifest_names_units_and_references():
    man = harness.manifest()
    assert set(man) == {"command", "paths", "run_seconds", "configs",
                        "workloads", "end_to_end", "per_layer"}
    names = [x["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in man[k]]
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names)
    cfgs = {c["name"] for c in man["configs"]}
    for c in man["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert TEXT.match(c["why"]) and TEXT.match(c["source"])
        assert all(NAME.match(k) for k in c["reduced"])
        assert c["file"].startswith("chipbench/configs/")
    for w in man["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["config"] in cfgs and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4) and TEXT.match(w["why"])
    e2e = {m["name"] for m in man["end_to_end"]}
    assert "setup_s" in e2e
    cells = {w["name"] for w in man["workloads"]}
    for m in man["end_to_end"] + man["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES
        assert set(m.get("workloads", [])) <= cells
    for m in man["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in man["per_layer"]:
        assert m["moves"] in e2e and TEXT.match(m["layer"])
    for cell in cells:
        assert len(harness.metrics_of(man, cell, False)) >= 2
        assert harness.metrics_of(man, cell, True)
    assert len(json.dumps(man)) < 64 * 1024


# -- resolution by name ------------------------------------------------------

def test_resolution_by_name():
    man = harness.manifest()
    for w in man["workloads"]:
        assert harness.workload(man, w["name"]) is w
        c = harness.config(man, w["config"])
        assert c["name"] == w["config"]
        assert callable(harness.driver(harness.traffic(w["traffic"])[
            "driver"]))
        assert set(harness.limits(w["name"])) >= {"loss_gap", "grad_gap",
                                                  "update_gap"}
    for m in man["per_layer"]:
        assert callable(harness.reader(m["name"]))
    for bad in (lambda: harness.workload(man, "nope"),
                lambda: harness.config(man, "nope"),
                lambda: harness.traffic("nope"),
                lambda: harness.driver("nope"),
                lambda: harness.driver("../harness"),
                lambda: harness.reader("nope"),
                lambda: harness.limits("nope"),
                lambda: harness.peaks("no such chip")):
        with pytest.raises(harness.Refused):
            bad()
    assert harness.peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12


def test_metric_readers_find_nothing_without_their_inputs():
    man = harness.manifest()
    empty = {"e2e": {}, "trace": None, "ops": {}, "spans": [], "peak": None,
             "flops_per_pair": 1.0}
    for m in man["per_layer"]:
        assert harness.reader(m["name"])(empty) is None


# -- narrow products ---------------------------------------------------------

@pytest.mark.parametrize("dtype,narrow", [("float32", 0), ("bfloat16", 2)])
def test_narrow_dots_counts_products_below_float32(dtype, narrow):
    import jax
    import jax.numpy as jnp

    def f(x, w):
        y = x.astype(dtype) @ w.astype(dtype)
        return jnp.einsum("ij,kj->ik", y, y).astype(jnp.float32) @ w

    x = jnp.ones((8, 8))
    assert P.narrow_dots(jax.jit(f), P.abstract((x, x))) == narrow


def test_narrow_products_reads_operands_not_results():
    line = ('%2 = stablehlo.dot_general %0, %1, contracting_dims = [1] x '
            '[0] : (tensor<4x8xf32>, tensor<8x4xf32>) -> tensor<4x4xbf16>')
    assert P.narrow_products(line) == 0
    assert P.narrow_products(line.replace("8x4xf32", "8x4xf8E4M3FN")) == 1
    assert P.narrow_products('%3 = stablehlo.add %1, %2 : tensor<4xbf16>') \
        == 0


# -- the launcher window -----------------------------------------------------

@pytest.mark.parametrize("queued,logged,want", [
    ([0, 0, 0, 0, 0, 0], 6, 3),       # never ahead: right after the checks
    ([0, 1, 2, 2, 1, 0, 0, 0], 8, 6),  # queued during the compile, drained
    ([0, 1, 2, 2, 1, 0, 0], 6, None),  # not yet drained, not yet known
    ([0, 2, 2, 2, 2, 2, 2, 2, 2, 2, 2], 10, 8)])  # never drains: the latest
def test_launcher_window_opens_once_the_queue_is_drained(queued, logged,
                                                         want):
    gets = [(q, 0.0) for q in queued]
    assert launcher.opens_at(gets, logged, first=3, latest=8) == want


# -- refusals ----------------------------------------------------------------

def test_step_pool_refuses_more_than_one_chip():
    from chipbench.drivers import step_pool
    with pytest.raises(harness.Refused):
        step_pool.run({}, {}, seed=1, seconds=1.0, trace=False, t_start=0.0,
                      devs=[None, None])

def _run(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload", "vitb32.step",
         "--seed", "2147483700", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_refuses_without_a_tpu():
    p = _run(harness.ROOT)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def test_refuses_in_a_bare_directory(tmp_path):
    shutil.copy(os.path.join(harness.ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(harness.HERE, tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(str(tmp_path))
    assert p.returncode != 0 and p.stdout.strip() == ""
