"""The program's spans against the device trace (``harness/program_spans``):
the clock offset, idle under chosen spans, the anchor check, and the
harness's own idle attribution left to its ``bench.*`` spans.

    JAX_PLATFORMS=cpu python -m pytest bench/tests -q
"""
from __future__ import annotations

import types

import jax
import pytest

from harness import program_spans as PS
from harness import trace as TR
from repro.serving import obs

OFF = 100.0                     # ring clock = trace clock + OFF


def _span(i, parent, name, t0, t1):
    return obs.Span(i, parent, name, t0 + OFF, t1 + OFF, {})


# trace clock: window [0, 6]; device busy [0, 2], [2.5, 3], [4, 5]; idle
# [2, 2.5], [3, 4], [5, 6]
OPS = [("fusion.1", 0.0, 1.0), ("pallas.paged[32,2,6,128]:c.1", 0.5, 2.0),
       ("fusion.2", 2.5, 3.0), ("fusion.3", 4.0, 5.0)]
SPANS = [
    _span(1, None, "engine.step", 0.0, 2.2),
    _span(2, 1, "engine.step.dispatch", 0.0, 0.1),
    _span(3, 1, "engine.step.fetch", 0.1, 2.05),
    _span(4, 1, "engine.step.commit", 2.05, 2.2),
    _span(5, None, "engine.admit", 3.5, 4.5),
    _span(6, 5, "engine.admit.lookup", 3.5, 3.6),
    _span(7, 5, "engine.admit.dispatch", 3.7, 4.5),
    _span(8, None, "engine.step", 5.2, 5.8),
    _span(9, 8, "engine.step.upload", 5.2, 5.3),
    _span(10, 8, "engine.step.fetch", 5.3, 5.7),
    _span(11, 8, "engine.step.commit", 5.7, 5.8),
    _span(12, None, "engine.step", 6.5, 7.0),        # after the window
]


class _Ring:
    def __init__(self, spans):
        self._spans = spans

    def spans(self, name=None, since=None, until=None):
        return [s for s in self._spans
                if s.t0 >= since and s.t1 <= until]


@pytest.fixture
def run(monkeypatch):
    monkeypatch.setattr(obs, "TRACER", _Ring(SPANS))
    red = {"lo": 0.0, "hi": 6.0, "ops": {"/device:TPU:0": OPS}}
    rec = types.SimpleNamespace(t0=OFF, t_end=6.0 + OFF)
    return {"trace": red, "rec": rec}


def test_offset_and_idle_under_spans(run):
    assert PS.offset(run) == pytest.approx(-OFF)
    assert [s.id for s in PS.window_spans(run)] == list(range(1, 12))
    split = PS.idle_split(run)
    want = {"engine.step.commit": 0.15 + 0.1, "engine.step.fetch": 0.05 + 0.4,
            "engine.admit.lookup": 0.1, "engine.admit.dispatch": 0.3,
            "engine.step.upload": 0.1,
            "engine": 0.1,                       # the admission's own time
            "outside the engine": 2.5 - (0.2 + 0.5 + 0.6)}
    assert split.keys() == want.keys()
    for k, v in want.items():
        assert split[k] == pytest.approx(v), k
    assert sum(split.values()) == pytest.approx(2.5)
    # host phases: every leaf but the fetch, over two window steps
    assert PS.idle_host_ms(run) == pytest.approx(1e3 * 0.75 / 2)
    # host time of a step outside its fetch: 2.2 - 1.95 and 0.6 - 0.4
    assert PS.step_host_ms(run) == pytest.approx(1e3 * (0.25 + 0.2) / 2)


def test_anchors_that_disagree_read_nothing(run):
    run["rec"].t0 = OFF + 1.5e-3
    assert PS.offset(run) is not None
    run["rec"].t0 = OFF + 2.5e-3
    assert PS.offset(run) is None
    assert PS.idle_host_ms(run) is None
    assert PS.idle_split(run) is None
    # the host time needs no trace, so it still reads
    assert PS.step_host_ms(run) is not None


def test_no_spans_reads_nothing(run, monkeypatch):
    monkeypatch.setattr(obs, "TRACER", _Ring([]))
    assert PS.step_host_ms(run) is None
    assert PS.idle_host_ms(run) is None
    run["trace"] = None
    assert PS.offset(run) is None


def test_harness_attribution_ignores_program_spans(tmp_path):
    """A profile with ``engine.*`` spans nested in ``bench.*`` ones loads
    only the harness's spans, so ``idle_by_span`` charges idle to them and
    ``host.other`` alone."""
    jax.profiler.start_trace(str(tmp_path))
    try:
        with jax.profiler.TraceAnnotation("bench.window"):
            for _ in range(2):
                with jax.profiler.TraceAnnotation("bench.step"):
                    with obs.span("engine.step"):
                        with obs.span("engine.step.fetch"):
                            pass
                with jax.profiler.TraceAnnotation("bench.collect"):
                    pass
    finally:
        jax.profiler.stop_trace()
    tr = TR.load(str(tmp_path))
    names = [n for n, _, _ in tr.spans]
    assert sorted(set(names)) == ["bench.collect", "bench.step",
                                  "bench.window"]
    lo, hi = TR.window(tr)
    gap = [(lo, hi)]                     # a window with no device work
    idle = TR.idle_by_span(gap, tr.spans)
    assert set(idle) <= {"bench.step", "bench.collect", "host.other"}
    assert sum(idle.values()) == pytest.approx(hi - lo)
