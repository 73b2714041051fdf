"""The trainer's profiler names (``repro.tracing``): the four device scopes
in the compiled train step's HLO metadata, the input path's host spans in
a profiler trace, and the prefetcher's wait counts with the launcher's
``input:`` line."""
import contextlib
import glob
import io
import os
import re
import subprocess
import sys
import threading
import time

import jax
import jax.numpy as jnp
import pytest

from repro import tracing as TR
from repro.configs import get_arch
from repro.core import fastclip as FC
from repro.core import train_step as TS
from repro.core.schedules import lr_warmup_cosine
from repro.data import DevicePrefetcher, InputWaits
from repro.launch import train as LT
from repro.launch.steps import donated_jit
from repro.optim import adamw

FSDP_HELPER = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "helpers", "fsdp_check.py")

# an instruction of compiled HLO text: its opcode and its metadata op_name
_INSTR = re.compile(r"^\s*(?:ROOT )?%?\S+ = (?:\([^=]*\)|\S+) ([a-z-]+)\(")
_OP_NAME = re.compile(r'op_name="([^"]*)"')


def scopes_of(hlo_text: str):
    """(opcode, innermost ``repro.tracing`` scope of its op_name path or
    "") of every instruction of an HLO module's text."""
    out = []
    for line in hlo_text.splitlines():
        m = _INSTR.match(line)
        if not m:
            continue
        path = _OP_NAME.search(line)
        parts = re.split(r"[/()]", path.group(1)) if path else []
        found = [p for p in parts if p in TR.SCOPES]
        out.append((m.group(1), found[-1] if found else ""))
    return out


def assert_step_is_scoped(hlo_text: str):
    ops = scopes_of(hlo_text)
    assert {s for _, s in ops} >= set(TR.SCOPES)
    products = [(op, s) for op, s in ops if op in ("dot", "convolution")]
    assert products
    assert all(s for _, s in products), [p for p in products if not p[1]]


def test_scopes_of_reads_forward_and_backward_paths():
    hlo = "\n".join([
        '  %a = f32[2]{0} dot(f32[2]{0} %x, f32[2]{0} %y), metadata='
        '{op_name="jit(s)/jvp(image_tower)/while/body/dot_general"}',
        '  %b = (f32[2]{0}, s32[]) while(%t), condition=%c, body=%w, '
        'metadata={op_name="jit(s)/transpose(jvp(text_tower))/while"}',
        '  ROOT %c.1 = f32[] add(f32[] %p, f32[] %q)',
        '  %d = f32[2]{0} fusion(%a), kind=kLoop, calls=%f, metadata='
        '{op_name="jit(s)/optimizer/mul"}'])
    assert scopes_of(hlo) == [("dot", "image_tower"), ("while", "text_tower"),
                              ("add", ""), ("fusion", "optimizer")]


def _tiny_step_config(version):
    cfg = get_arch("clip-vitb32-cc12m").reduced()
    fc = FC.FastCLIPConfig(version=version, n_samples=64, steps_per_epoch=2,
                           gamma_decay_epochs=2)
    return TS.TrainStepConfig(arch=cfg, fc=fc, optimizer=adamw(),
                              lr_fn=lr_warmup_cosine(1e-3, 2, 10),
                              grad_clip=1.0)


@pytest.mark.parametrize("version", ["v3", "v2", "openclip"])
def test_single_device_step_carries_the_four_scopes(version):
    """Every matrix product of the compiled step lies under a tower or
    the loss op, and the optimizer's update under ``optimizer``."""
    tc = _tiny_step_config(version)
    state = jax.eval_shape(
        lambda: TS.init_train_state(jax.random.PRNGKey(0), tc))
    c, b = tc.arch.clip, 8
    batch = {"images": jax.ShapeDtypeStruct(
                 (b, c.image_size, c.image_size, 3), jnp.float32),
             "texts": jax.ShapeDtypeStruct((b, c.context_length),
                                           jnp.int32)}
    idx = jax.ShapeDtypeStruct((b,), jnp.int32)
    hlo = donated_jit(TS.make_train_step(tc)).lower(
        state, batch, idx).compile().as_text()
    assert_step_is_scoped(hlo)


def test_fsdp_step_carries_the_four_scopes(tmp_path):
    """The same on the sharded-state step over a data:1,fsdp:2 mesh (4
    forced host devices, in a subprocess)."""
    out = tmp_path / "fsdp_step.hlo"
    p = subprocess.run([sys.executable, FSDP_HELPER, "scopes", str(out)],
                       capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, (p.stdout[-3000:], p.stderr[-3000:])
    assert_step_is_scoped(out.read_text())


# -- host spans and wait counts ---------------------------------------------

def test_input_spans_land_in_a_profiler_trace(tmp_path):
    from jax.profiler import ProfileData
    jax.profiler.start_trace(str(tmp_path))
    try:
        pf = DevicePrefetcher(iter(range(4)), depth=2,
                              transform=jnp.asarray)
        assert [int(x) for x in pf] == [0, 1, 2, 3]
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    names = [e.name for plane in ProfileData.from_file(path).planes
             if plane.name.startswith("/host:")
             for line in plane.lines for e in line.events]
    # one make per item plus the one that finds the stream ended; one
    # wait per ask, the last finding the end
    assert names.count("repro.input.make") == 5
    assert names.count("repro.input.copy") == 4
    assert names.count("repro.input.wait") == 5


def test_prefetcher_counts_empty_asks_and_waits():
    """Asks that find the queue empty and the seconds waited, for batches
    made slower than they are asked for and for batches queued ahead."""
    gate = threading.Semaphore(0)

    def gated():
        for i in range(4):
            gate.acquire()
            yield i

    waits = InputWaits()
    pf = DevicePrefetcher(gated(), depth=2, waits=waits)
    threading.Timer(0.2, gate.release).start()
    assert next(pf) == 0                      # waited ~0.2 s on an empty queue
    assert (waits.asks, waits.empty) == (1, 1)
    assert waits.waited_s >= 0.15
    gate.release()
    gate.release()
    while pf._q.qsize() < 2:                  # two batches queued ahead
        time.sleep(0.01)
    assert [next(pf), next(pf)] == [1, 2]
    assert (waits.asks, waits.empty) == (3, 1)
    gate.release()
    assert list(pf) == [3]
    assert waits.asks == 4
    assert str(waits).startswith(f"input: queue empty at {waits.empty} of "
                                 "4 asks, waited ")


def test_prefetchers_sharing_waits_count_together():
    waits = InputWaits()
    for n in (3, 2):
        pf = DevicePrefetcher(iter(range(n)), depth=2, waits=waits)
        assert list(pf) == list(range(n))
    assert waits.asks == 5


@pytest.mark.parametrize("chaos", [None, "sigterm@2"])
def test_launcher_prints_its_input_line(chaos):
    """At the end of a run, and after the SIGTERM drain, the launcher
    prints what its loop waited for."""
    argv = ["--arch", "clip-vitb32-cc12m", "--reduced", "--global-batch",
            "16", "--n-samples", "64", "--steps", "6", "--log-every", "1"]
    if chaos:
        argv += ["--chaos", chaos]
    out = io.StringIO()
    with contextlib.redirect_stdout(out):
        LT.main(argv)
    lines = out.getvalue().splitlines()
    m = [re.fullmatch(r"input: queue empty at (\d+) of (\d+) asks, "
                      r"waited \d+\.\d s", line) for line in lines]
    m = [x for x in m if x]
    assert len(m) == 1
    empty, asks = int(m[0].group(1)), int(m[0].group(2))
    # with sigterm@2 the loop runs step 2, takes step 3's batch and stops
    assert asks == (4 if chaos else 6) and 0 <= empty <= asks
    if chaos:
        assert any(line.startswith("preempted (signal") for line in lines)
