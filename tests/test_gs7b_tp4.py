"""Qwen2-VL-7B's serving path, tensor-parallel over four devices, against
the plain float32 reference, at a tiny width on the CPU.

The configuration keeps the 7B's ratios: 28 query and 4 KV heads (so a
(1, 4) mesh leaves one KV head and a group of 7 query heads on each
device), M-RoPE sections in the published 2 : 3 : 3 proportion, an untied
head, an MLP that splits four ways.  Weights come from the benchmark's
seeded generator (``bench/harness/weights.py``) and the engine is built as
the benchmark builds it, through ``make_engine_core``; admission and every
decode step's answer logits (``_slot_logits``) are compared with
``bench/harness/reference.answer_logits`` for the same seed, on the mesh
and on one device.  Leaving out the all-reduces after the attention and
MLP projections must fail the comparison.
"""
import copy
import json
import pathlib
import sys

import numpy as np
import pytest

BENCH = pathlib.Path(__file__).resolve().parents[1] / "bench"
sys.path.insert(0, str(BENCH))

from harness import reference, system, traffic  # noqa: E402
from harness import weights as W  # noqa: E402
from harness.cell import prompt_token  # noqa: E402

pytestmark = pytest.mark.mesh

SEED = 20241016
STEPS = 6
#: (scene, det class) of each admitted request: four slots, three scenes,
#: so one scene prefix is shared by two rows
REQUESTS = [(0, 1), (1, 5), (0, 7), (2, 3)]

#: Largest max |program - reference| / max |reference| over every row's
#: answer logits.  The program computes in bf16 (8-bit mantissa, a relative
#: step of 2^-8 = 0.0039 per rounding) and the reference in float32; the
#: error carried through two layers, the paged cache and the head reads
#: 0.008-0.011 over three seeds, on one device and on four alike (the
#: all-reduces add a bf16 rounding of their own), so the limit leaves
#: twice that.  A mistake in the mathematics is O(1): with the all-reduces
#: left out, each device keeps a quarter of every attention and MLP output
#: and the error reads 1.3-1.6.
LOGIT_REL_TOL = 0.02


def tiny_gs(model: int):
    conf = json.loads((BENCH / "configs" / "qwen2-vl-7b-gs.json").read_text())
    conf = copy.deepcopy(conf)
    conf.update(hidden_size=64, intermediate_size=128, num_hidden_layers=2,
                head_dim=16, vocab_size=256,
                rope_scaling={"type": "mrope", "mrope_section": [2, 3, 3]})
    conf["eo_adapter"] = {"grid": 4, "image_size": 32, "channels": 3,
                          "num_classes": 8}
    conf["engine"] = {"slots": 4, "page_size": 4, "prefix_cache_scenes": 4,
                      "answer_vocab": 16}
    conf["mesh"] = {"data": 1, "model": model}
    return conf


def served_logits(conf):
    """Admit ``REQUESTS`` and step ``STEPS`` times; per request, its
    prompt id, the tokens it was served and the answer logits the engine
    held before each of them and after the last, (STEPS + 1, vocab)."""
    a = W.arch(conf)
    core, ac = system.build(conf, SEED)
    reqs = [system.request(traffic.Query(0.0, s, "det", c),
                           traffic.scene_image(SEED, s, a), ac)
            for s, c in REQUESTS]
    slots = core.admit_many(reqs)
    # each device holds its own KV head of every page (pool leaves are
    # (layers, pages, page, KV heads, head_dim))
    (pool,) = core._slot_cache
    assert {sh.data.shape[3] for sh in pool["k"].addressable_shards} == {
        conf["num_key_value_heads"] // conf["mesh"]["model"]}
    rows = [np.asarray(core._slot_logits)[slots, :a["answer_vocab"]]]
    for _ in range(STEPS):
        assert core.step() == []               # det answers run on
        rows.append(np.asarray(core._slot_logits)[slots, :a["answer_vocab"]])
    got = np.stack(rows, axis=1)               # (requests, STEPS + 1, V)
    toks = [list(core._slots[s].tokens) for s in slots]
    assert all(len(t) == STEPS for t in toks)
    pids = [prompt_token("det", c, a["num_classes"]) for _, c in REQUESTS]
    return a, pids, toks, got


def logit_err(conf) -> float:
    a, pids, toks, got = served_logits(conf)
    scenes = sorted({s for s, _ in REQUESTS})
    mine = {s: [i for i, (t, _) in enumerate(REQUESTS) if t == s]
            for s in scenes}
    ref = reference.answer_logits(
        SEED, a, np.stack([traffic.scene_image(SEED, s, a) for s in scenes]),
        [[np.asarray([pids[i]] + toks[i], np.int32) for i in mine[s]]
         for s in scenes])
    err = 0.0
    for j, s in enumerate(scenes):
        for r, i in zip(ref[j], mine[s]):
            assert r.shape == got[i].shape
            err = max(err, float(np.abs(got[i] - r).max() / np.abs(r).max()))
    return err


@pytest.mark.parametrize("model", [1, 4], ids=["one-device", "tp4"])
def test_answer_logits_match_the_reference(make_mesh, model):
    make_mesh(1, model)                        # skips without the devices
    assert logit_err(tiny_gs(model)) <= LOGIT_REL_TOL


def test_leaving_out_the_all_reduces_fails(make_mesh, monkeypatch):
    """The exchange between devices is what makes four quarters one
    model: with both hooks identity the comparison must fail."""
    from repro.distributed import collectives
    make_mesh(1, 4)
    monkeypatch.setattr(collectives, "tp_attn_all_reduce", lambda x: x)
    monkeypatch.setattr(collectives, "tp_mlp_all_reduce", lambda x: x)
    assert logit_err(tiny_gs(4)) > LOGIT_REL_TOL
