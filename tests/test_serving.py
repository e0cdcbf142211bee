"""Serving runtime: engine batching + cascade server behaviour."""
import json
import os
import time

import numpy as np
import pytest

from repro.network.orbit import ContactPlan
from repro.serving import (CascadeServer, EngineConfig, InferenceEngine,
                           Request, obs)

GOLDEN = os.path.join(os.path.dirname(__file__), "golden_cascade_server.json")


def _requests(bundle, task, n):
    data = bundle.datasets[task]
    return [Request(task=task, image=data["images"][i],
                    prompt=int(data["prompts"][i]), t_arrival=float(i))
            for i in range(n)]


def test_engine_serves_mixed_queue(tiny_bundle):
    eng = InferenceEngine(tiny_bundle.sat.params, tiny_bundle.sat.cfg,
                          tiny_bundle.adapter_cfg,
                          EngineConfig(slots=4, answer_vocab=9))
    reqs = _requests(tiny_bundle, "vqa", 5) + _requests(tiny_bundle, "cls", 4)
    resps = eng.serve(reqs)
    assert len(resps) == 9
    assert {r.request_id for r in resps} == {q.request_id for q in reqs}


def test_cascade_server_roundtrip(tiny_bundle):
    server = CascadeServer(
        tiny_bundle.sat, tiny_bundle.gs, tiny_bundle.adapter_cfg,
        tiny_bundle.conf_params, tiny_bundle.cascade_cfg,
        tiny_bundle.latency,
        plan=ContactPlan(contact_fraction_override=1.0))
    for req in _requests(tiny_bundle, "cls", 4):
        resp = server.handle(req, now=req.t_arrival)
        assert resp.tier in ("satellite", "ground")
        assert resp.latency_s > 0
        if resp.tier == "ground":
            assert resp.tx_bytes > 0
            assert "tx" in resp.timings
        else:
            assert resp.tx_bytes == 0


def test_cascade_server_link_down_degrades_to_satellite(tiny_bundle):
    server = CascadeServer(
        tiny_bundle.sat, tiny_bundle.gs, tiny_bundle.adapter_cfg,
        tiny_bundle.conf_params, tiny_bundle.cascade_cfg,
        tiny_bundle.latency, link_up=False)
    for req in _requests(tiny_bundle, "cls", 6):
        resp = server.handle(req)
        assert resp.tier == "satellite"
        assert resp.tx_bytes == 0


def test_continuous_batching_refills_slots_mid_stream(tiny_bundle):
    """A finished slot must be refilled from the queue while other slots are
    still mid-answer — the batch never drains to admit the next request."""
    eng = InferenceEngine(tiny_bundle.sat.params, tiny_bundle.sat.cfg,
                          tiny_bundle.adapter_cfg,
                          EngineConfig(slots=2, answer_vocab=9))
    data = tiny_bundle.datasets["cls"]
    # det answers take N_r = 16 tokens, vqa/cls answers take 1: the det
    # request pins one slot while 1-token requests stream through the other
    reqs = [Request(task="det", image=data["images"][0], prompt=0)]
    reqs += _requests(tiny_bundle, "vqa", 5)
    t0 = time.perf_counter()
    resps = eng.serve(reqs)
    assert len(resps) == 6
    assert {r.request_id for r in resps} == {q.request_id for q in reqs}
    det = next(r for r in resps if r.request_id == reqs[0].request_id)
    assert det.tokens.shape == (tiny_bundle.adapter_cfg.n_regions,)
    # ≥4 admissions happened after step 0 with the det slot still active
    assert eng.core.stats["mid_stream_refills"] >= 4
    # the slot table stayed full whenever work was pending: every admission
    # left both slots occupied
    admits = obs.TRACER.spans("engine.admit", since=t0)
    assert sum(s.attrs["requests"] for s in admits) == len(reqs)
    assert all(s.attrs["active_after"] == 2 for s in admits)


def test_engine_emits_unified_tier_vocabulary(tiny_bundle):
    from repro.serving import TIERS
    eng = InferenceEngine(tiny_bundle.sat.params, tiny_bundle.sat.cfg,
                          tiny_bundle.adapter_cfg,
                          EngineConfig(slots=4, answer_vocab=9))
    resps = eng.serve(_requests(tiny_bundle, "cls", 3))
    assert all(r.tier in TIERS for r in resps)
    assert all(r.tier == "satellite" for r in resps)


def test_cascade_server_matches_prerefactor_golden(tiny_bundle):
    """Fixed-seed equivalence with the PRE-refactor per-request server: the
    golden file was captured from the seed implementation on this exact
    bundle; the unified executor path must reproduce its decisions (exit
    stage, tier, prediction) and transmitted bytes."""
    with open(GOLDEN) as f:
        golden = json.load(f)
    server = CascadeServer(
        tiny_bundle.sat, tiny_bundle.gs, tiny_bundle.adapter_cfg,
        tiny_bundle.conf_params, tiny_bundle.cascade_cfg,
        tiny_bundle.latency,
        plan=ContactPlan(contact_fraction_override=1.0))
    for rec in golden["records"]:
        data = tiny_bundle.datasets[rec["task"]]
        i = rec["index"]
        req = Request(task=rec["task"], image=data["images"][i],
                      prompt=int(data["prompts"][i]), t_arrival=float(i))
        resp = server.handle(req, now=req.t_arrival)
        assert resp.exit_stage == rec["exit_stage"], rec
        assert resp.tier == rec["tier"], rec
        assert int(np.asarray(resp.pred).reshape(-1)[0]) == rec["pred"], rec
        np.testing.assert_array_equal(
            np.asarray(resp.tokens).reshape(-1), rec["tokens"], err_msg=str(rec))
        assert resp.tx_bytes == pytest.approx(rec["tx_bytes"], rel=1e-6), rec


def test_server_decisions_match_batch_evaluator(tiny_bundle):
    """The request server and the batch evaluator are adapters over ONE
    executor: per-request decisions must agree with the vectorised
    counterfactual run on the same inputs."""
    import jax.numpy as jnp
    sv = tiny_bundle.spaceverse()
    server = CascadeServer(
        tiny_bundle.sat, tiny_bundle.gs, tiny_bundle.adapter_cfg,
        tiny_bundle.conf_params, tiny_bundle.cascade_cfg,
        tiny_bundle.latency,
        plan=ContactPlan(contact_fraction_override=1.0))
    data = tiny_bundle.datasets["cls"]
    out = sv.run_batch("cls", jnp.asarray(data["images"][:8]),
                       jnp.asarray(data["prompts"][:8]))
    exit_b = np.asarray(out["exit_stage"])
    off_b = np.asarray(out["offload"])
    pred_b = np.asarray(out["pred"])
    for i in range(8):
        req = Request(task="cls", image=data["images"][i],
                      prompt=int(data["prompts"][i]))
        resp = server.handle(req, now=float(i))
        assert resp.exit_stage == exit_b[i]
        assert (resp.tier == "ground") == bool(off_b[i])
        assert int(np.asarray(resp.pred)) == pred_b[i]


def test_cascade_server_contact_window_wait(tiny_bundle):
    # a realistic contact plan: requests in the dead zone pay window wait
    import dataclasses
    server = CascadeServer(
        tiny_bundle.sat, tiny_bundle.gs, tiny_bundle.adapter_cfg,
        tiny_bundle.conf_params, tiny_bundle.cascade_cfg,
        tiny_bundle.latency, plan=ContactPlan(alt_km=570.0, num_gs=1))
    server.cc = dataclasses.replace(server.cc, taus=(1.1, 1.1))  # force offload
    plan = server.plan
    req = _requests(tiny_bundle, "cls", 1)[0]
    t_dead = plan.window_s + 5.0
    resp = server.handle(req, now=t_dead)
    assert resp.tier == "ground"
    assert resp.timings["tx"] > plan.next_window(t_dead)[0] - t_dead - 1.0
