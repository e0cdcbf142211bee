"""The program's own spans inside the measured window, placed on the
trace's clock.

The program records ``engine.*`` spans around its host work in
``EngineCore.admit_many`` and ``EngineCore.step`` (``repro.serving.obs``:
one ring per process, on ``time.perf_counter()``, the clock of the window's
``Record``), in traced and untraced runs alike.  A traced run's window is
also the ``bench.window`` span on the trace's clock, so one offset carries
a ring span onto the trace: ``red["hi"] - rec.t_end`` (the span closes
microseconds after ``t_end`` is read, in both loops).  The start anchors,
``red["lo"] - rec.t0``, must agree with it within ``SKEW_S``, or nothing
that needs the trace is read.

Besides ``harness/system.py`` this is the one module that reads the
program, and only its span ring: where the program records no spans (a
checkout without ``repro.serving.obs``) every reader here returns None.
"""
from __future__ import annotations

from typing import Any, Callable, Dict, List, Optional

from harness import system  # noqa: F401  (puts the program on sys.path)
from harness import trace as TR

SKEW_S = 2e-3
FETCH = "engine.step.fetch"
ENGINE = "engine"               # the label of idle under any engine span


def window_spans(run) -> Optional[List[Any]]:
    """The ``engine.*`` spans that lie inside the window, in start order,
    or None where there are none."""
    try:
        from repro.serving.obs import TRACER
    except ImportError:
        return None
    rec = run["rec"]
    got = [s for s in TRACER.spans(since=rec.t0, until=rec.t_end)
           if s.name.startswith("engine.")]
    return sorted(got, key=lambda s: s.id) or None


def top_steps(spans) -> List[Any]:
    """The outermost ``engine.step`` spans: one per engine step."""
    return [s for s in spans if s.name == "engine.step" and s.parent is None]


def leaves(spans) -> List[Any]:
    parents = {s.parent for s in spans}
    return [s for s in spans if s.id not in parents]


def offset(run) -> Optional[float]:
    """Seconds to add to a ring time to place it on the trace's clock, or
    None where the window's two anchors disagree by more than ``SKEW_S``."""
    red, rec = run["trace"], run["rec"]
    if red is None:
        return None
    off = red["hi"] - rec.t_end
    if abs(red["lo"] - rec.t0 - off) > SKEW_S:
        return None
    return off


def step_host_ms(run) -> Optional[float]:
    """Mean host milliseconds of a window step outside its token fetch:
    upload, dispatch, commit and the step's own time."""
    spans = window_spans(run)
    steps = top_steps(spans or [])
    if not steps:
        return None
    by_id = {s.id: s for s in spans}

    def top(s):
        while s.parent in by_id:
            s = by_id[s.parent]
        return s.id
    fetch = {s.id: 0.0 for s in steps}
    for s in spans:
        if s.name == FETCH and top(s) in fetch:
            fetch[top(s)] += s.seconds
    return 1e3 * sum(s.seconds - fetch[s.id] for s in steps) / len(steps)


def idle_by_label(run, label: Callable[[Any], Optional[str]]
                  ) -> Optional[Dict[str, float]]:
    """Device idle seconds in the window (averaged over chips) under the
    window's spans, charged to ``label(span)`` (spans labelled None are
    left out); idle under none of them is ``host.other``."""
    spans, off = window_spans(run), offset(run)
    if not spans or off is None:
        return None
    red = run["trace"]
    iv = [(label(s), s.t0 + off, s.t1 + off) for s in spans]
    iv = [x for x in iv if x[0] is not None]
    out: Dict[str, float] = {}
    for ops in red["ops"].values():
        got = TR.idle_by_span(TR.gaps(ops, red["lo"], red["hi"]), iv,
                              skip=())
        for k, v in got.items():
            out[k] = out.get(k, 0.0) + v / len(red["ops"])
    return out


def idle_host_ms(run) -> Optional[float]:
    """Device idle per window step under the engine's host phases: every
    leaf ``engine.*`` span but the token fetch, where the host waits on the
    device."""
    spans = window_spans(run)
    if not spans:
        return None
    host = {s.id for s in leaves(spans) if s.name != FETCH}
    idle = idle_by_label(run, lambda s: "host" if s.id in host else None)
    if idle is None or not top_steps(spans):
        return None
    return 1e3 * idle.get("host", 0.0) / len(top_steps(spans))


def idle_split(run) -> Optional[Dict[str, float]]:
    """Device idle seconds by leaf span name, ``engine`` for the idle under
    an engine span but none of its leaves (a step's or an admission's own
    time), and ``outside the engine``."""
    spans = window_spans(run)
    if not spans:
        return None
    leaf = {s.id for s in leaves(spans)}
    by_leaf = idle_by_label(run, lambda s: s.name if s.id in leaf else None)
    whole = idle_by_label(run, lambda s: ENGINE if s.parent is None else None)
    if by_leaf is None or whole is None:
        return None
    out = {k: v for k, v in by_leaf.items() if k != "host.other"}
    out[ENGINE] = whole.get(ENGINE, 0.0) - sum(out.values())
    out["outside the engine"] = whole.get("host.other", 0.0)
    return out
