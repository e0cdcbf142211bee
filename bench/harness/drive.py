"""The measured window: the loop ``InferenceEngine.serve`` runs,
``EngineCore.admit_many`` then ``EngineCore.step``, driven by the traffic.

Each call into the engine sits inside a host span of the profiler's own
trace (``jax.profiler.TraceAnnotation``), so a traced run can say what the
host was doing in each of the device's idle gaps:

    bench.wait_arrival  nothing is due and no slot is busy: open-loop idle
    bench.admit         EngineCore.admit_many (prefix prefill, prompt row)
    bench.step          EngineCore.step (one decode step, token fetch)
    bench.collect       recording the step's tokens and finished answers
    bench.generate      making the request that refills a closed loop

The loop records, per step and per admission, what the engine was given
(rows, cache lengths, prefix misses), which the per-layer readers turn
into operations and bytes; it never reads the clock of the device.
"""
from __future__ import annotations

import collections
import dataclasses
import time
from typing import Any, Callable, Dict, List, Optional

import jax
import numpy as np

SPAN = jax.profiler.TraceAnnotation


@dataclasses.dataclass
class Record:
    """What happened in the window."""
    t0: float = 0.0                 # perf_counter at the window's start
    t_end: float = 0.0              # ... and at its end
    steps: List[tuple] = dataclasses.field(default_factory=list)
    # (t_start, t_end, rows active, sum of rows' attended KV lengths)
    admits: List[tuple] = dataclasses.field(default_factory=list)
    # (t_start, t_end, requests, scenes prefilled)
    tokens: int = 0                 # answer tokens committed in the window
    ttft_s: List[float] = dataclasses.field(default_factory=list)
    lateness_s: List[float] = dataclasses.field(default_factory=list)
    attempted: int = 0
    failed: int = 0
    counted: set = dataclasses.field(default_factory=set)  # request ids
    counters0: Dict[str, int] = dataclasses.field(default_factory=dict)
    counters1: Dict[str, int] = dataclasses.field(default_factory=dict)
    compiles: int = 0               # programs lowered inside the window

    @property
    def window_s(self) -> float:
        return self.t_end - self.t0


class CompileCounter:
    """Counts programs lowered (traced and compiled, or loaded from the
    persistent cache) while armed: a warm window lowers none."""

    EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"

    def __init__(self):
        self.armed = False
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, name, _secs, **_kw):
        if self.armed and name == self.EVENT:
            self.count += 1


def counters(core) -> Dict[str, int]:
    st = core.stats
    return {"prefix_hits": int(st["prefix_hits"]),
            "prefix_misses": int(st["prefix_misses"])}


def _ctx_sum(core) -> int:
    """Attended KV length summed over the active rows of the next step:
    a decode row at cache index i reads i + 1 positions."""
    return sum(core._slot_pos(i) + 1
               for i, s in enumerate(core._slots) if s.active)


def _admit(core, reqs, rec: Optional[Record]):
    m0 = core.stats["prefix_misses"]
    t = time.perf_counter()
    with SPAN("bench.admit"):
        slots = core.admit_many(reqs)
    if rec is not None:
        rec.admits.append((t, time.perf_counter(), len(reqs),
                           core.stats["prefix_misses"] - m0))
    return slots


def answer_logits(core, slots, vocab: int) -> np.ndarray:
    """The next-token logits the engine holds for ``slots``, over the
    answer vocabulary (one fetch of the whole table)."""
    return np.asarray(core._slot_logits)[np.asarray(slots), :vocab]


def _step(core, rec: Optional[Record]):
    n, ctx = core.active_count(), _ctx_sum(core)
    t = time.perf_counter()
    with SPAN("bench.step"):
        fin = core.step()
    t1 = time.perf_counter()
    if rec is not None:
        rec.steps.append((t, t1, n, ctx))
    return fin, n, t1


def serve_until_idle(core, reqs: List[Any], done: Callable) -> None:
    """Set-up: serve ``reqs`` in arrival order, a slot table at a time."""
    pending = collections.deque(reqs)
    while pending or core.active_count():
        k = min(len(pending), len(core.free_slots()))
        if k:
            core.admit_many([pending.popleft() for _ in range(k)])
        for req, toks in core.step():
            done(req, toks)


def open_loop(core, schedule: List[tuple], seconds: float,
              answers: Dict[int, Any], guard: CompileCounter,
              on_window_end: Callable[[], None], read_logits: int = 0,
              logits: Optional[Dict[int, np.ndarray]] = None,
              vocab: int = 0) -> Record:
    """Open loop.  ``schedule``: (t, request) sorted by t >= 0, seconds
    from the window's start.  Every request due before ``seconds`` is
    counted and waited for; the load goes on while they drain, so the last
    of them meet the same traffic as the rest.  Once they are all
    answered, the loop goes on under the same load until the answer logits
    of ``read_logits`` more requests have been read into ``logits`` right
    after their admission (a fetch per admission, outside every timed
    request)."""
    rec = Record()
    due: collections.deque = collections.deque()
    sched_abs: Dict[int, float] = {}
    counted = {r.request_id for t, r in schedule if t < seconds}
    rec.counted = counted
    rec.attempted = len(counted)
    left = set(counted)
    i, n = 0, len(schedule)
    rec.counters0 = counters(core)
    guard.armed = True
    rec.t0 = t0 = time.perf_counter()
    in_window = True
    def more_logits():
        return (logits is not None and len(logits) < read_logits
                and (i < n or bool(due) or core.active_count() > 0))

    while left or more_logits():
        now = time.perf_counter()
        if in_window and now - t0 >= seconds:
            rec.t_end = now
            rec.counters1 = counters(core)
            guard.armed = False
            rec.compiles = guard.count
            on_window_end()
            in_window = False
        while i < n and schedule[i][0] <= now - t0:
            t, r = schedule[i]
            sched_abs[r.request_id] = t0 + t
            if r.request_id in counted:
                rec.lateness_s.append(now - t0 - t)
            due.append(r)
            i += 1
        k = min(len(due), len(core.free_slots()))
        if k:
            batch = [due.popleft() for _ in range(k)]
            slots = _admit(core, batch, rec if in_window else None)
            if not left and logits is not None:
                lg = answer_logits(core, slots, vocab)
                for r, row in zip(batch, lg):
                    logits[r.request_id] = row
        if core.active_count():
            fin, _, t_ans = _step(core, rec if in_window else None)
            with SPAN("bench.collect"):
                for req, toks in fin:
                    answers[req.request_id] = toks
                    if req.request_id in left:
                        left.discard(req.request_id)
                        rec.ttft_s.append(t_ans - sched_abs[req.request_id])
                        rec.tokens += len(toks)
        elif i < n:
            with SPAN("bench.wait_arrival"):
                time.sleep(max(t0 + schedule[i][0] - time.perf_counter(),
                               0.0))
        elif due:
            continue
        else:
            break
    if in_window:                       # everything drained before the end
        rec.t_end = time.perf_counter()
        rec.counters1 = counters(core)
        guard.armed = False
        rec.compiles = guard.count
        on_window_end()
    rec.failed = len(left)
    return rec


def closed_loop(core, next_request: Callable[[], Any], seconds: float,
                answers: Dict[int, Any], guard: CompileCounter) -> Record:
    """Closed loop: every slot busy; a finished request is replaced at
    once.  Tokens/s is every token committed over the whole window, which
    ends with the first step to end past ``seconds``."""
    rec = Record()
    rec.counters0 = counters(core)
    rec.attempted = core.active_count()
    rec.counted = {s.request.request_id for s in core._slots if s.active}
    guard.armed = True
    rec.t0 = t0 = time.perf_counter()
    while time.perf_counter() - t0 < seconds:
        fin, n, _ = _step(core, rec)
        rec.tokens += n
        with SPAN("bench.collect"):
            for req, toks in fin:
                answers[req.request_id] = toks
        if fin:
            with SPAN("bench.generate"):
                new = [next_request() for _ in fin]
            rec.attempted += len(new)
            rec.counted.update(r.request_id for r in new)
            _admit(core, new, rec)
    rec.t_end = time.perf_counter()
    rec.counters1 = counters(core)
    guard.armed = False
    rec.compiles = guard.count
    return rec


def in_flight(core) -> Dict[int, tuple]:
    """(slot, tokens served so far) of each unfinished request."""
    return {s.request.request_id: (i, list(s.tokens))
            for i, s in enumerate(core._slots) if s.active}


def pct(xs, q: float) -> float:
    return float(np.percentile(np.asarray(xs, np.float64), q))
