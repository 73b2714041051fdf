"""The comparison that decides ``correct`` must fail the control and every
fault a one-chip training cell can have.  A tiny CLIP on the CPU drives the
whole run of each cell (the harness's look for a chip skipped), with the
cell's own limits: a sound run passes; a step that returns its state
unchanged and a step that leaves out half of the batch fail, and so does
the control (the program's own bf16 tower policy).  The launcher runs only
registered architectures, so its cell's runs see the tiny one under the
registered name."""
from __future__ import annotations

import json
import time

import pytest

import repro.configs
from chipbench import harness, probe, program as P
from repro.launch import train as LT

TINY = dict(image_size=32, patch_size=16, vision_width=64, vision_layers=2,
            vision_heads=2, text_width=64, text_layers=2, text_heads=2,
            text_mlp=256, context_length=8, vocab_size=128, embed_dim=32,
            global_batch=32, n_samples=512)


@pytest.fixture(scope="module")
def runs():
    man = harness.manifest()
    out = {}

    def run(cell, variant):
        w = harness.workload(man, cell)
        cfg = dict(harness.config(man, w["config"]), **TINY)
        # at TINY's sizes the cells of one mix differ only in their limits
        # and hyperparameters: cells that share those share their runs
        key = (w["traffic"], json.dumps([harness.limits(cell), cfg["hyper"]],
                                        sort_keys=True), variant)
        if key not in out:
            arch = P.program_arch(cfg)
            with pytest.MonkeyPatch.context() as mp:
                mp.setattr(repro.configs, "get_arch", lambda name: arch)
                mp.setattr(LT, "get_arch", lambda name: arch)
                out[key] = harness.run_cell(
                    cell, 2147483731, 0.5, False,
                    t_start=time.perf_counter(), man=man, cfg=cfg,
                    require_chip=False, variant=probe.VARIANTS[variant])
        return out[key]
    return run


CELLS = [w["name"] for w in harness.manifest()["workloads"]]


@pytest.mark.parametrize("cell", CELLS)
def test_sound_run_is_correct(runs, cell):
    r = runs(cell, "program")
    assert r["correct"], r["checks"]
    assert r["attempted"] > 0 and r["failed"] == 0


@pytest.mark.parametrize("variant", ["unchanged", "half_batch"])
@pytest.mark.parametrize("cell", CELLS)
def test_faults_are_not_correct(runs, cell, variant):
    r = runs(cell, variant)
    assert not r["correct"], r["checks"]


@pytest.mark.parametrize("cell", CELLS)
def test_control_is_not_correct(runs, cell):
    """The program's own bf16 tower policy through the cell's whole run."""
    r = runs(cell, "control")
    assert not r["correct"], r["checks"]


@pytest.mark.parametrize("cell", CELLS)
def test_control_fails_on_its_narrow_products(runs, cell):
    """The bf16 policy reads like the program in the gaps of norms on the
    chip; the element types of its products set it apart."""
    assert runs(cell, "program")["checks"]["narrow_dots"]["value"] == 0
    assert runs(cell, "control")["checks"]["narrow_dots"]["value"] > 0


def test_launcher_traces_the_windows_last_seconds(monkeypatch):
    """A traced launcher run records only the window's last
    ``trace_seconds``: the window span in the trace is that long."""
    from chipbench import trace as TR
    man = harness.manifest()
    w = harness.workload(man, "vitb32.fed")
    cfg = dict(harness.config(man, w["config"]), **TINY)
    arch = P.program_arch(cfg)
    monkeypatch.setattr(repro.configs, "get_arch", lambda name: arch)
    monkeypatch.setattr(LT, "get_arch", lambda name: arch)
    mix = dict(harness.traffic(w["traffic"]), trace_seconds=0.3)
    monkeypatch.setattr(harness, "traffic", lambda name: mix)
    seen = []
    reduce = TR.reduce

    def spy(ops, spans):
        seen.extend((s.end - s.start) / 1e9 for s in spans
                    if s.name == TR.WINDOW)
        return reduce(ops, spans)
    monkeypatch.setattr(TR, "reduce", spy)
    r = harness.run_cell("vitb32.fed", 2147483741, 1.0, True,
                         t_start=time.perf_counter(), man=man, cfg=cfg,
                         require_chip=False)
    assert r["correct"], r["checks"]
    assert len(seen) == 1 and 0.25 < seen[0] < 0.5, seen
