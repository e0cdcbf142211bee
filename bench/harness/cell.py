"""One run of one cell: set-up, the measured window, the reference check.

Order matters for the numbers: the end-to-end metrics come from a window
with the profiler off (``trace=False``); a traced run gives the per-layer
metrics.  ``memory_peak_bytes`` is read before the program's state is
freed, and the reference runs after that, so it never sets the peak.
"""
from __future__ import annotations

import gc
import shutil
import sys
import tempfile
import time
from typing import Any, Callable, Dict, List, Optional

import jax
import numpy as np

from harness import check, drive, roofline, system, traffic
from harness import trace as TR
from harness import weights as W
from harness.spec import Cell

#: seconds of arrivals scheduled past the window, so the drain of the
#: window's last requests meets the same load
DRAIN_S = 30.0


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def prompt_token(task: str, prompt: int, num_classes: int) -> int:
    """The task protocol's prompt id (configuration file, ``eo_adapter``):
    vqa -> [0, C), cls -> C, det -> C + 1 + prompt."""
    if task == "vqa":
        return prompt
    if task == "cls":
        return num_classes
    if task == "det":
        return num_classes + 1 + prompt
    raise ValueError(task)


def device_info() -> Dict[str, Any]:
    d = jax.devices()
    return {"platform": d[0].platform, "kind": d[0].device_kind,
            "count": len(d)}


def memory_peak() -> Optional[int]:
    peaks = [(dv.memory_stats() or {}).get("peak_bytes_in_use")
             for dv in jax.local_devices()]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def _sample(rng, items: List, n: int) -> List:
    idx = rng.choice(len(items), size=min(n, len(items)), replace=False)
    return [items[i] for i in sorted(idx)]


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool,
             t_start: float, mutate: Optional[Callable] = None,
             overrides: Optional[Dict[str, Any]] = None,
             controls: tuple = ()) -> Dict[str, Any]:
    """Run ``cell`` once; returns the result line's fields plus what the
    control script reads (``readings``).  ``mutate(core)`` plants a fault
    in the system under test (tests only); ``overrides`` replace engine
    settings; ``controls`` names extra reference precisions to read."""
    a = W.arch(cell.config)
    mix = cell.traffic
    guard = drive.CompileCounter()
    core, ac = system.build(cell.config, seed, overrides)
    if mutate is not None:
        mutate(core)
    log(f"built {cell.config['name']} on {device_info()}: kernels "
        f"{system.kernel_impl()}, {time.perf_counter() - t_start:.1f}s")

    images: Dict[int, np.ndarray] = {}

    def image(i):
        if i not in images:
            images[i] = traffic.scene_image(seed, i, a)
        return images[i]

    queries: Dict[int, traffic.Query] = {}
    answers: Dict[int, np.ndarray] = {}
    read: Dict[int, np.ndarray] = {}          # request id -> answer logits

    def make(q):
        r = system.request(q, image(q.scene), ac)
        queries[r.request_id] = q
        return r

    tdir = tempfile.mkdtemp(prefix="bench_trace_") if trace else None
    win = []

    def start_window():
        if trace:
            # device ops and the harness's own spans (level 1); the Python
            # function tracer and the runtime's verbose host events would
            # slow the host loop the window measures
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 1
            jax.profiler.start_trace(tdir, profiler_options=opts)
        win.append(jax.profiler.TraceAnnotation("bench.window"))
        win[0].__enter__()

    def end_window():
        win[0].__exit__(None, None, None)
        if trace:
            jax.profiler.stop_trace()

    if mix["loop"] == "open":
        sched = traffic.open_schedule(mix, seed, seconds + DRAIN_S, a)
        reqs = [(q.t, make(q)) for q in sched]
        core.warmup()
        drive.serve_until_idle(core, [r for t, r in reqs if t < 0],
                               lambda r, tk: None)
        setup_s = time.perf_counter() - t_start
        log(f"set-up {setup_s:.2f}s: warm-up, {sum(t < 0 for t, _ in reqs)}"
            f" history queries served, {len(images)} scenes made")
        start_window()
        rec = drive.open_loop(core, [(t, r) for t, r in reqs if t >= 0],
                              seconds, answers, guard, end_window,
                              read_logits=int(mix["logit_sample"]),
                              logits=read, vocab=a["answer_vocab"])
    else:
        slots = core.cfg.slots
        conc = slots if mix["concurrency"] == "slots" else \
            int(mix["concurrency"])
        qs = iter(traffic.closed_queries(mix, seed, 10 ** 6, a))
        core.admit_many([make(next(qs)) for _ in range(conc)])
        for _ in range(int(mix.get("warm_steps", 2))):
            for r, tk in core.step():
                answers[r.request_id] = tk
        setup_s = time.perf_counter() - t_start
        log(f"set-up {setup_s:.2f}s: {conc} {mix['task']} requests admitted")
        start_window()
        rec = drive.closed_loop(core, lambda: make(next(qs)), seconds,
                                answers, guard)
        end_window()
    served = dict(answers)
    flight = drive.in_flight(core)
    served.update({k: np.asarray(v, np.int32) for k, (_, v) in flight.items()})
    if flight:                  # next-token logits of the rows still decoding
        ids = sorted(flight)
        lg = drive.answer_logits(core, [flight[r][0] for r in ids],
                                 a["answer_vocab"])
        read.update(zip(ids, lg))
    mem = memory_peak()

    # -- end-to-end numbers (host clock) ------------------------------------
    e2e = {"setup_s": setup_s}
    if rec.ttft_s:
        e2e["ttft_p95_ms"] = drive.pct(rec.ttft_s, 95) * 1e3
    if mix["loop"] == "closed":
        e2e["output_tokens_per_s"] = rec.tokens / rec.window_s
    late = rec.lateness_s
    log(f"window {rec.window_s:.3f}s: {rec.attempted} attempted, "
        f"{rec.failed} unanswered, {rec.tokens} tokens, "
        f"{len(rec.steps)} steps, {len(rec.admits)} admissions, "
        f"{rec.compiles} programs lowered in the window")
    if late:
        log(f"generator lateness: p50 {drive.pct(late, 50) * 1e3:.3f} ms, "
            f"p95 {drive.pct(late, 95) * 1e3:.3f} ms, max "
            f"{max(late) * 1e3:.3f} ms over {len(late)} requests")
    if rec.ttft_s:
        log(f"ttft: p50 {drive.pct(rec.ttft_s, 50) * 1e3:.3f} ms, p95 "
            f"{e2e['ttft_p95_ms']:.3f} ms over {len(rec.ttft_s)} requests")

    # -- free the program's state before the reference runs -----------------
    del core
    gc.collect()

    # -- per-layer numbers (traced run) --------------------------------------
    red = None
    if trace:
        red = TR.reduce(TR.load(tdir))
        shutil.rmtree(tdir, ignore_errors=True)
    per_layer = {}
    if trace:
        run = {"a": a, "rec": rec, "trace": red, "chips": cell.chips,
               "peaks": roofline.peaks(jax.devices()[0].device_kind)}
        for m in cell.per_layer:
            v = m.read(run)
            if v is not None:
                per_layer[m.name] = v

    # -- the reference check --------------------------------------------------
    bad = 0
    for rid, toks in served.items():
        q = queries[rid]
        if np.any((toks < 0) | (toks >= a["answer_vocab"])):
            bad += 1
        elif rid in answers and len(toks) != traffic.answer_len(q.task, a):
            bad += 1
    items = _items(seed, mix, a, rec, queries, served, read, answers)
    t_ref = time.perf_counter()
    got = check.compare(seed, a, items,
                        lambda s: traffic.scene_image(seed, s, a), controls)
    prog = got["program"]
    log(f"reference: {prog['gap_tokens']} served tokens and "
        f"{prog['err_positions']} logit rows of {len(items)} requests in "
        f"{time.perf_counter() - t_ref:.1f}s")
    lim = {k: float(v["limit"]) for k, v in cell.limits.items()}
    checked = {k: {"value": prog[k], "limit": lim[k]} for k in lim}
    checked["bad_answers"] = {"value": bad + rec.failed, "limit": 0}
    correct = bool(prog["gap_tokens"] > 0 and prog["err_positions"] > 0
                   and all(c["value"] <= c["limit"]
                           for c in checked.values()))

    metrics = {}
    src = cell.end_to_end if not trace else cell.per_layer
    for m in src:
        v = e2e.get(m.name) if not trace else per_layer.get(m.name)
        if v is not None:
            metrics[m.name] = {"value": v, "unit": m.unit}
        elif not trace:
            raise RuntimeError(f"end-to-end metric {m.name} not measured")
    device = dict(device_info(), memory_peak_bytes=mem)
    if trace:
        device.update(busy_s=red["busy_s"], window_s=red["window_s"])
    out = {"correct": correct, "attempted": rec.attempted,
           "failed": rec.failed + bad, "metrics": metrics, "device": device}
    if trace:
        out["breakdown"] = {"device_ops": red["device_ops"],
                            "idle_gaps": red["idle_gaps"]}
    out["check"] = checked
    return {"line": out, "readings": got, "rec": rec}


def _items(seed, mix, a, rec, queries, served, read, finished
           ) -> List[check.Item]:
    """What the reference checks, drawn from the seed, with the served
    tokens: every counted request on ``check_sample`` of the scenes (open
    loop), or ``check_sample`` of the rows still decoding and as many of the
    requests finished in the window (closed loop); and the logits read."""
    rng = np.random.default_rng([seed, 4])

    def pid(rid):
        q = queries[rid]
        return prompt_token(q.task, q.prompt, a["num_classes"])

    items = []
    if mix["loop"] == "open":
        by_scene: Dict[int, List[int]] = {}
        for rid in sorted(rec.counted):
            if len(served.get(rid, ())):
                by_scene.setdefault(queries[rid].scene, []).append(rid)
        for s in _sample(rng, sorted(by_scene), int(mix["check_sample"])):
            items += [check.Item(s, pid(r), served[r]) for r in by_scene[s]]
        items += [check.Item(queries[r].scene, pid(r), [], gap=False,
                             logits=lg) for r, lg in sorted(read.items())]
    else:
        rows = sorted(r for r in rec.counted if r in read)
        for r in _sample(rng, rows, int(mix["check_sample"])):
            items.append(check.Item(queries[r].scene, pid(r), served[r],
                                    logits=read[r]))
        done = sorted(r for r in rec.counted if r in finished)
        for r in _sample(rng, done, int(mix["check_sample"])):
            items.append(check.Item(queries[r].scene, pid(r), served[r]))
    return items
