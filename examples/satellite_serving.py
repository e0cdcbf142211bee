"""End-to-end serving driver: batched Earth-observation requests through the
two-tier SpaceVerse server with orbital contact windows.

    PYTHONPATH=src python examples/satellite_serving.py [--requests 48]

This is the paper's deployment story: a request stream arrives at the
satellite; the progressive confidence network triages each request; offloads
pass Eq. 2/Eq. 3 preprocessing and a Starlink-calibrated link whose contact
windows are simulated by the orbit model; the GS tier answers the rest.  The
demo also drops the link mid-stream to show graceful degradation to
satellite-only service, then fans several prompts out over ONE captured
scene to show the paged KV cache sharing the image-region prefix across
queries (the region tokens prefill once; every further query only runs its
prompt suffix).  A final section turns on chunked prefill
(``EngineCoreConfig(prefill_chunk=C)``): admission stops running the scene
prefill as one synchronous call and instead streams it into the paged
cache a few region tokens per fused token-budget step, printing the
per-step decode/prompt/chunk token mix and the measured TTFT with
chunking on vs off.  The closing section saturates a tiny engine with
bulk mapping work and injects urgent queries mid-burst, with overload
control (``EngineCoreConfig(overload=OverloadConfig(...))``) on vs off:
on, the bounded priority queue defers/rejects bulk explicitly and the
urgent arrivals preempt their way into slots; off, they wait FIFO behind
the whole backlog.
"""
import sys, os
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import argparse

import numpy as np

from repro.core import pipeline as P
from repro.launch.compile_cache import enable_compile_cache
from repro.network.orbit import ContactPlan
from repro.serving import (CascadeServer, EngineConfig, EngineCore,
                           EngineCoreConfig, InferenceEngine, Request)
from repro.serving.engine_core import shared_core


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--requests", type=int, default=48)
    ap.add_argument("--contact-fraction", type=float, default=1.0,
                    help="1.0 = always in contact; 0.0433 = paper's average")
    ap.add_argument("--fanout", type=int, default=8,
                    help="queries fanned out over one scene in the paged-"
                         "KV prefix-sharing demo")
    args = ap.parse_args()

    print("== training tiers + confidence network ==")
    bundle = P.build_system(scale="small", n_train=192, n_test=64,
                            proxy_steps=150, conf_steps=150, seed=0)
    server = CascadeServer(
        bundle.sat, bundle.gs, bundle.adapter_cfg, bundle.conf_params,
        bundle.cascade_cfg, bundle.latency,
        plan=ContactPlan(contact_fraction_override=args.contact_fraction))

    # request stream mixing the three tasks
    reqs = []
    rng = np.random.default_rng(0)
    for i in range(args.requests):
        task = ("vqa", "cls", "det")[i % 3]
        data = bundle.datasets[task]
        j = int(rng.integers(0, data["images"].shape[0]))
        reqs.append(Request(task=task, image=data["images"][j],
                            prompt=int(data["prompts"][j]), t_arrival=i * 0.5))

    print(f"== serving {len(reqs)} requests ==")
    tiers = {"satellite": 0, "ground": 0}
    lat, tx = [], []
    for q, req in enumerate(reqs):
        if q == 2 * len(reqs) // 3:
            print("-- link DOWN: degrading to satellite-only --")
            server.link_up = False
        resp = server.handle(req, now=req.t_arrival)
        tiers[resp.tier] += 1
        lat.append(resp.latency_s)
        tx.append(resp.tx_bytes)
        if q < 8 or q == 2 * len(reqs) // 3:
            print(f"req {resp.request_id:3d} [{req.task}] → {resp.tier:9s} "
                  f"exit={resp.exit_stage} lat={resp.latency_s:6.3f}s "
                  f"tx={resp.tx_bytes/1e6:6.2f}MB")

    med, n_strag = server.scheduler.straggler_report()
    print(f"\nserved: {tiers}; mean latency {np.mean(lat):.3f}s; "
          f"downlinked {np.sum(tx)/1e6:.1f}MB; "
          f"median transfer {med:.3f}s; stragglers {n_strag}; "
          f"re-replicated {server.scheduler.n_replicated}")
    # the server and the batch evaluator share one executor (DESIGN.md
    # §serving): the same bundle evaluated in counterfactual mode
    res = bundle.spaceverse().evaluate("cls", bundle.datasets["cls"],
                                       batch_size=16)
    print(f"batch evaluator (same executor): performance "
          f"{res['performance']:.3f}, offload rate {res['offload_rate']:.2f}")

    # -- scene fan-out: many prompts over ONE captured scene ---------------
    # the dominant on-satellite traffic shape: cls + det + a batch of VQA
    # questions about the same tile.  The paged engine prefills the 16
    # region tokens once and maps their KV pages read-only into every
    # query's block table — watch the prefix hit rate.
    print(f"\n== scene fan-out over one image ({args.fanout} queries, "
          "paged KV prefix sharing) ==")
    eng = InferenceEngine(bundle.sat.params, bundle.sat.cfg,
                          bundle.adapter_cfg,
                          EngineConfig(slots=4, answer_vocab=9))
    eng.warmup()
    scene_img = bundle.datasets["cls"]["images"][0]
    fan = [Request(task="det", image=scene_img, prompt=0, scene_id="tile-0"),
           Request(task="cls", image=scene_img, prompt=0, scene_id="tile-0")]
    fan += [Request(task="vqa", image=scene_img, prompt=q % 2,
                    scene_id="tile-0")
            for q in range(max(args.fanout - 2, 0))]
    resps = eng.serve(fan)
    st = eng.core.stats
    kv = eng.core.kv_stats()
    n_regions = bundle.adapter_cfg.n_regions
    print(f"answered {len(resps)} queries over one scene: "
          f"prefix hit rate {kv['prefix_hit_rate']:.2f} "
          f"({st['prefix_hits']} hits / {st['prefix_misses']} miss)")
    print(f"prefilled {st['prefill_tokens']} tokens total "
          f"(dense would prefill {len(fan) * (n_regions + 1)}); "
          f"amortised KV {kv['kv_bytes_per_slot']} B/slot "
          f"across {kv['pages_in_use']} live pages")

    # -- cascade-speculative decoding on the ground tier -------------------
    # the cascade pair IS a speculative pair: the compact satellite model
    # drafts γ tokens per slot and W^g verifies them in one multi-token
    # scoring step (token-for-token identical to greedy decode).  Offloaded
    # requests arrive with the satellite's answer already computed — those
    # tokens piggyback on the downlink payload as free drafts; the rest
    # draft with the local compact model.
    gamma = 4
    print(f"\n== cascade-speculative decoding on the ground tier "
          f"(γ={gamma}) ==")
    spec_core = EngineCore(
        bundle.gs, bundle.adapter_cfg,
        EngineCoreConfig(slots=4, answer_vocab=9, spec_gamma=gamma),
        draft=bundle.sat)
    spec_core.warmup()
    sat_core = shared_core(bundle.sat, bundle.adapter_cfg)
    det = bundle.datasets["det"]
    spec_reqs = []
    for i in range(8):
        img = det["images"][i]
        req = Request(task="det", image=img, prompt=int(det["prompts"][i]))
        if i % 2 == 0:      # offloaded half: satellite answer rides along
            toks, _ = sat_core.generate(
                "det", np.asarray(img)[None],
                np.asarray([int(det["prompts"][i])], np.int32), 9)
            req.draft_tokens = np.asarray(toks)[0].astype(np.int32)
        spec_reqs.append(req)
    queue = list(reversed(spec_reqs))
    while queue or spec_core.active_count():
        n = min(len(queue), len(spec_core.free_slots()))
        if n:
            spec_core.admit_many([queue.pop() for _ in range(n)])
        spec_core.step()
    sp = spec_core.spec_stats()
    local = sp["drafted"] - sp["piggybacked"]
    print(f"answered {spec_core.stats['finished']} det queries "
          f"speculatively: accept rate {sp['accept_rate']:.2f}, "
          f"{sp['tokens_per_slot_step']:.2f} tokens/slot-step "
          f"(greedy commits 1.0)")
    print(f"draft sources: {sp['piggybacked']} piggybacked from the "
          f"satellite's downlinked answer, {local} drafted locally by the "
          f"compact model; {sp['verify_only_steps']}/{sp['steps']} steps "
          f"skipped the drafter entirely")

    _chunked_demo(bundle, args.fanout)
    _overload_demo(bundle)


def _chunked_demo(bundle, fanout: int) -> None:
    """Continuous-arrival chunked prefill: admission streams each new
    scene's region tokens into the paged cache a few per fused step, next
    to everyone else's decode tokens — the engine never stops decoding to
    admit.  Prints the per-step token mix and the measured TTFT with
    chunking on vs off (same requests, token-for-token equal answers)."""
    import time

    import numpy as np

    from repro.serving import EngineCore, EngineCoreConfig, Request
    from repro.core.cascade import TierModel

    print("\n== chunked prefill: admission fused into the decode step ==")
    scenes = bundle.datasets["cls"]["images"]
    n_regions = bundle.adapter_cfg.n_regions

    def stream(tag):
        reqs = []
        for s in range(4):
            img = scenes[s % len(scenes)]
            reqs.append(Request(task="det", image=img, prompt=0,
                                scene_id=f"{tag}-{s}"))
            reqs += [Request(task="vqa", image=img, prompt=q % 2,
                             scene_id=f"{tag}-{s}")
                     for q in range(max(fanout // 2 - 1, 1))]
        return reqs

    results = {}
    for chunk in (0, 8):
        core = EngineCore(TierModel(bundle.sat.params, bundle.sat.cfg),
                          bundle.adapter_cfg,
                          EngineCoreConfig(slots=4, answer_vocab=9,
                                           prefill_chunk=chunk))
        core.warmup()
        queue = list(reversed(stream(f"c{chunk}")))
        outs = {}
        while queue or core.active_count():
            n = min(len(queue), len(core.free_slots()))
            if n:
                core.admit_many([queue.pop() for _ in range(n)])
            for r, t in core.step():
                outs[r.request_id] = t.tolist()
        log = core.stats["request_log"]
        ttft = sorted(r["t_first"] - r["t_admit"] for r in log)
        results[chunk] = {"outs": [outs[k] for k in sorted(outs)],
                          "ttft_ms": ttft[len(ttft) // 2] * 1e3,
                          "core": core}
    chunked = results[8]["core"]
    mix = chunked.stats["sched"]["step_log"]
    print(f"per-step token mix of the first fused steps "
          f"(decode/prompt/chunk), budget "
          f"{chunked.scheduler_stats()['budget']}:")
    for i, (d, p, c) in enumerate(mix[:8]):
        bar = "D" * d + "P" * p + "c" * c
        print(f"  step {i:2d}: {d:2d} decode + {p} prompt + {c:2d} chunk  "
              f"|{bar}|")
    st = chunked.scheduler_stats()
    print(f"{st['fused_steps']} fused steps, budget utilisation "
          f"{st['budget_utilization']:.2f}, prefill by kind "
          f"{st['prefill_by_kind']} "
          f"(the {n_regions}-token scene prefix streams as "
          f"'chunk' tokens instead of one synchronous admission call)")
    same = results[0]["outs"] == results[8]["outs"]
    print(f"TTFT p50: {results[8]['ttft_ms']:.2f}ms chunked vs "
          f"{results[0]['ttft_ms']:.2f}ms stall admission; outputs "
          f"token-for-token equal: {same}  (at this demo's toy 16-token "
          f"scenes the stall is tiny — benchmarks/serving_bench.py "
          f"measures production-shaped 256-token scenes, where the "
          f"urgent-query TTFT halves)")


def _overload_demo(bundle) -> None:
    """Graceful degradation under saturation, overload control on vs off:
    the same bulk det burst floods a 2-slot engine, then two urgent vqa
    queries arrive mid-burst.  Controlled, the queue stays bounded (excess
    bulk is rejected with an explicit reason) and the urgent pair preempts
    straight into slots; uncontrolled, everything queues FIFO and the
    urgent queries wait behind the entire backlog."""
    import time

    from collections import Counter

    from repro.core.cascade import TierModel
    from repro.serving import (EngineCore, EngineCoreConfig, OverloadConfig,
                               PRIORITY_BULK, PRIORITY_URGENT, Request)

    print("\n== overload control: urgent queries under bulk saturation ==")
    scenes = bundle.datasets["cls"]["images"]
    tier = TierModel(bundle.sat.params, bundle.sat.cfg)

    def burst(tag):
        bulk = [Request(task="det", image=scenes[i % len(scenes)], prompt=0,
                        scene_id=f"{tag}-b{i}", priority=PRIORITY_BULK)
                for i in range(8)]
        urgent = [Request(task="vqa", image=scenes[(8 + i) % len(scenes)],
                          prompt=i % 2, scene_id=f"{tag}-u{i}",
                          priority=PRIORITY_URGENT) for i in range(2)]
        return bulk, urgent

    # -- control ON: bounded queue, priority admission, preemption ---------
    core = EngineCore(tier, bundle.adapter_cfg,
                      EngineCoreConfig(slots=2, answer_vocab=9,
                                       overload=OverloadConfig(queue_cap=4)))
    core.warmup()
    bulk, urgent = burst("on")
    out = core.submit_many(bulk)
    print(f"bulk burst of {len(bulk)} on 2 slots (queue cap 4): "
          f"{dict(Counter(out[r.request_id] for r in bulk))}")
    for _ in range(3):
        core.step()
    out_u = core.submit_many(urgent)
    ol = core.scheduler_stats()["overload"]
    print(f"2 urgent vqa arrive mid-burst: "
          f"{dict(Counter(out_u[r.request_id] for r in urgent))} "
          f"(preempted {ol['preemptions']} bulk slots to take them)")
    n_done = 0
    while core.active_count() or core.queue_depth():
        n_done += len(core.step())
    ol = core.scheduler_stats()["overload"]
    print(f"drained: {n_done} completed, queue peak {ol['queue_peak']}, "
          f"deferred {ol['admissions_deferred']}, "
          f"rejections {ol['rejections']}, re-admission wait p50 "
          f"{ol['readmit_wait_ms']['p50']:.1f}ms")
    names = {PRIORITY_BULK: "bulk", PRIORITY_URGENT: "URGENT"}
    ttft_on = {}
    for p, v in ol["ttft_by_priority"].items():
        ttft_on[p] = v["p99_ms"]
        print(f"  {names.get(p, p):6s} TTFT-from-submit p50 "
              f"{v['p50_ms']:6.1f}ms  p99 {v['p99_ms']:6.1f}ms  "
              f"({v['n']} completed)")

    # -- control OFF: the pre-overload deployment (unbounded host FIFO) ----
    base = EngineCore(tier, bundle.adapter_cfg,
                      EngineCoreConfig(slots=2, answer_vocab=9))
    base.warmup()
    bulk, urgent = burst("off")
    base.stats["request_log"].clear()
    arrive = {}
    fifo = list(bulk)
    for r in bulk:
        arrive[r.request_id] = time.perf_counter()
    steps = 0
    while fifo or base.active_count():
        n = min(len(fifo), len(base.free_slots()))
        if n:
            base.admit_many(fifo[:n])
            del fifo[:n]
        base.step()
        steps += 1
        if steps == 3:                    # urgent joins the back of the line
            for r in urgent:
                arrive[r.request_id] = time.perf_counter()
            fifo += urgent
    prio_of = {r.request_id: r.priority for r in bulk + urgent}
    ttft_off = {}
    for p in (PRIORITY_BULK, PRIORITY_URGENT):
        ts = sorted((r["t_first"] - arrive[r["request_id"]]) * 1e3
                    for r in base.stats["request_log"]
                    if prio_of[r["request_id"]] == p)
        ttft_off[p] = ts[-1]
        print(f"  {names[p]:6s} TTFT without control: p50 "
              f"{ts[len(ts) // 2]:6.1f}ms  worst {ts[-1]:6.1f}ms  "
              f"(all {len(ts)} served FIFO)")
    if ttft_on.get(PRIORITY_URGENT):
        print(f"urgent tail with control on: {ttft_on[PRIORITY_URGENT]:.1f}ms"
              f" vs {ttft_off[PRIORITY_URGENT]:.1f}ms off "
              f"({ttft_off[PRIORITY_URGENT] / ttft_on[PRIORITY_URGENT]:.1f}×"
              " better) — bulk pays with deferrals/rejections instead of "
              "the urgent class paying with latency")


if __name__ == "__main__":
    main()
