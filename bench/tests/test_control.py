"""The control must come out not correct: the reference computed with its
matmuls in fp8 (the precision below the configuration's bf16), read at the
prompts and tokens a run served, lies past the limit on every seed, while
the program itself stays within it.  At a size a test run holds on the CPU;
``control.py`` reads the same at the cells' own sizes on the chip."""
from __future__ import annotations

import json
import pathlib

import pytest

import common
from control import readings

ROOT = pathlib.Path(__file__).resolve().parents[2]


@pytest.mark.parametrize("workload,loop", [("sat2b-det-bulk", "closed"),
                                           ("sat2b-vqa-fanout", "open")])
def test_control_fails_program_passes(workload, loop):
    lim = json.loads((ROOT / "bench" / "limits" / f"{workload}.json")
                     .read_text())
    err = lim["answer_logit_err"]["limit"]
    got = list(readings(common.tiny_cell(loop, lim), [1, 2, 3], 1.5))
    for r in got:
        assert r["correct"], r
    assert all(r["fp8"]["answer_logit_err"] > err for r in got), got
