"""The trace reduction, on a slice of a trace recorded on a v5e (sat2b
fan-out, ``data/trace_v5e.json``: its device ops, named as ``trace.load``
names them, and its ``bench.*`` host spans over 0.6 s) and on a hand-made
one whose answers are known.

    JAX_PLATFORMS=cpu python -m pytest bench/tests -q
"""
from __future__ import annotations

import json
import pathlib

import numpy as np
import pytest

from harness import readers, roofline
from harness import trace as TR

DATA = pathlib.Path(__file__).resolve().parent / "data"


A2B = {"heads": 12, "kv_heads": 2, "regions": 1024}
PAGED = "pallas.paged[32,2,6,128]:closed_call.13"
FLASH = "pallas.dense[1,12,1024,128]:closed_call.4"


def hand_made():
    ops = [("fusion.1", 0.0, 1.0), (PAGED, 0.5, 2.0),
           ("all-reduce.2", 2.5, 3.0), ("fusion.9", 2.8, 2.9),
           (FLASH, 4.0, 5.0)]
    spans = [("bench.window", 0.0, 6.0), ("bench.step", 0.0, 2.2),
             ("bench.admit", 3.5, 4.5)]
    return TR.Trace({"/device:TPU:0": ops}, spans)


def test_busy_idle_and_gaps_by_span():
    tr = hand_made()
    red = TR.reduce(tr)
    # busy: [0, 2] + [2.5, 3] + [4, 5] = 3.5 of a 6 s window
    assert red["window_s"] == pytest.approx(6.0)
    assert red["busy_s"] == pytest.approx(3.5)
    idle = dict(red["idle_gaps"])
    # gaps: [2, 2.5] (0.2 under bench.step), [3, 4] (0.5 under admit),
    # [5, 6]
    assert idle["bench.step"] == pytest.approx(0.2)
    assert idle["bench.admit"] == pytest.approx(0.5)
    assert idle["host.other"] == pytest.approx(0.3 + 0.5 + 1.0)
    assert sum(idle.values()) == pytest.approx(6.0 - 3.5)


def test_kernel_labels_from_hlo_text():
    text = ("%closed_call.13 = bf16[32,2,6,128]{3,2,1,0:T(8,128)(2,1)S(1)} "
            "custom-call(s32[32,257]{1,0:T(8,128)S(1)} %copy-done, "
            "s32[32]{0:T(128)} %x, bf16[32,2,6,128]{3,2,1,0} %q), "
            'custom_call_target="tpu_custom_call", metadata={}')
    assert TR.op_name(text) == PAGED
    flash = ("%closed_call.4 = bf16[1,12,1024,128]{3,2,1,0} custom-call("
             "bf16[1,12,1024,128]{3,2,1,0} %q, bf16[1,2,1024,128]{3} %k, "
             'bf16[1,2,1024,128]{3} %v), custom_call_target="tpu_custom_call"')
    assert TR.op_name(flash) == FLASH
    assert TR.op_name("%copy.55 = bf16[1,1536]{1,0} copy(%a)") == "copy.55"
    assert readers.PAGED_DECODE(A2B)(PAGED)
    assert not readers.PAGED_DECODE(A2B)(FLASH)
    assert readers.FLASH(A2B)(FLASH) and not readers.FLASH(A2B)(PAGED)


def test_kernel_time_and_families():
    tr = hand_made()
    ops = tr.devices["/device:TPU:0"]
    paged = readers.PAGED_DECODE(A2B)
    assert TR.kernel_s(ops, paged, 0, 6) == pytest.approx(1.5)
    assert TR.kernel_s(ops, paged, 1.0, 6) == pytest.approx(1.0)
    fam = dict(TR.top_ops(ops, 0, 6))
    assert fam["fusion"] == pytest.approx(1.1)
    assert fam["all-reduce"] == pytest.approx(0.5)


def test_roofline_share_from_work_and_time():
    a = {"layers": 2, "heads": 4, "kv_heads": 2, "hd": 8, "regions": 16}
    fl, by = roofline.paged_decode(a, rows=3, ctx_sum=30)
    assert fl == 2 * 4 * 30 * 4 * 8
    assert by == 2 * (2 * 30 * 2 * 8 + 2 * 3 * 4 * 8) * 2
    pk = {"flops_bf16": 1e6, "hbm_bytes_s": 1e5}
    # bytes bound: 4608 B at 1e5 B/s = 46.08 ms of a 100 ms kernel
    assert roofline.share(fl, by, 0.1, pk) == pytest.approx(46.08)
    assert roofline.share(0, 0, 0.1, pk) is None
    with pytest.raises(KeyError):
        roofline.peaks("no such chip")


@pytest.fixture(scope="module")
def recorded():
    d = json.loads((DATA / "trace_v5e.json").read_text())
    devs = {k: [tuple(o) for o in v] for k, v in d["devices"].items()}
    spans = [tuple(s) for s in d["spans"]]
    lo, hi = d["window"]
    return TR.Trace(devs, spans + [("bench.window", lo, hi)]), lo, hi


def test_recorded_trace(recorded):
    tr, lo, hi = recorded
    red = TR.reduce(tr)
    ops = tr.devices[sorted(tr.devices)[0]]
    # busy against a brute-force count on a 10 us grid
    t = np.arange(lo, hi, 1e-5) + 5e-6
    on = np.zeros_like(t, bool)
    for _, s, e in ops:
        on |= (t >= s) & (t < e)
    assert red["busy_s"] == pytest.approx(on.mean() * (hi - lo), rel=0.02)
    idle = dict(red["idle_gaps"])
    assert sum(idle.values()) == pytest.approx(red["window_s"]
                                               - red["busy_s"], rel=1e-6)
    assert set(idle) <= {"bench.step", "bench.admit", "bench.collect",
                         "bench.wait_arrival", "bench.generate",
                         "host.other"}
    paged = TR.kernel_s(ops, readers.PAGED_DECODE(A2B), lo, hi)
    flash = TR.kernel_s(ops, readers.FLASH(A2B), lo, hi)
    assert 0 < paged and 0 < flash and paged + flash <= red["busy_s"]
    # the paged kernel is the largest family, as in every fan-out trace
    fam = red["device_ops"]
    top = [k for k, _ in fam if not k.startswith("while")][0]
    assert readers.PAGED_DECODE(A2B)(top + ".0")
    # a roofline share of the slice's paged decode time cannot pass 100%
    # for the work of 32 rows at the longest context, once per layer
    a = dict(A2B, layers=28, hd=128)
    fl, by = roofline.paged_decode(a, 32, 32 * 2049)
    pk = roofline.peaks("TPU v5 lite")
    assert 0 < roofline.share(fl, by, paged, pk) < 100
