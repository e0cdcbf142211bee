"""The harness, driven on the CPU past its look for a chip, with the timed
path broken underneath: each fault a serving cell can have must come out
``correct: false``, and the unbroken run ``correct: true``.

    JAX_PLATFORMS=cpu python -m pytest bench/tests -q
"""
from __future__ import annotations

import json
import os
import pathlib
import subprocess
import sys
import time

import pytest

import common
from faults import alter_tokens
from harness.cell import run_cell

ROOT = pathlib.Path(__file__).resolve().parents[2]
CELLS = {"open": "sat2b-vqa-fanout", "closed": "sat2b-det-bulk"}


def limits(loop):
    return json.loads((ROOT / "bench" / "limits" / f"{CELLS[loop]}.json")
                      .read_text())


@pytest.mark.parametrize("loop", ["open", "closed"])
def test_sound_run_is_correct(loop):
    out = run_cell(common.tiny_cell(loop, limits(loop)), 11, 1.5, False,
                   time.perf_counter())
    assert out["line"]["correct"], out["line"]["check"]


@pytest.mark.parametrize("loop", ["open", "closed"])
def test_altered_token_is_not_correct(loop):
    lim = limits(loop)
    out = run_cell(common.tiny_cell(loop, lim), 11, 1.5, False,
                   time.perf_counter(), mutate=alter_tokens)
    line = out["line"]
    assert not line["correct"]
    assert (line["check"]["widest_logit_gap"]["value"]
            > lim["widest_logit_gap"]["limit"])


MESH_SCRIPT = r"""
import copy, json, sys, time
sys.path.insert(0, {tests!r}); sys.path.insert(0, {bench!r})
import common, faults
from harness.cell import run_cell
conf = copy.deepcopy(common.TINY)
conf["mesh"] = {{"data": 1, "model": 2}}
cell = common.tiny_cell("closed", {limits!r}, config=conf)
cell.chips = 2
if {drop!r}:
    faults.drop_exchange()
out = run_cell(cell, 11, 1.5, False, time.perf_counter())
print(json.dumps(out["line"]))
"""


@pytest.mark.parametrize("drop", [False, True])
def test_exchange_between_chips(drop):
    """Tensor-parallel over two virtual CPU devices: leaving out the
    all-reduces after the attention and MLP projections must fail."""
    here = pathlib.Path(__file__).resolve().parent
    script = MESH_SCRIPT.format(tests=str(here), bench=str(here.parent),
                                limits=limits("closed"), drop=drop)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=2")
    res = subprocess.run([sys.executable, "-c", script], env=env,
                         capture_output=True, text=True, timeout=600)
    assert res.returncode == 0, res.stderr[-3000:]
    line = json.loads(res.stdout.strip().splitlines()[-1])
    assert line["correct"] is (not drop), line["check"]


def test_refuses_the_cpu(tmp_path):
    """No accelerator: exit 2 and no result line."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    res = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "sat2b-det-bulk",
         "--seed", "1", "--seconds", "1", "--trace", "0"], cwd=ROOT, env=env,
        capture_output=True, text=True, timeout=300)
    assert res.returncode == 2
    assert res.stdout.strip() == ""

