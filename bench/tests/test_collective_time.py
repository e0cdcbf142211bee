"""``collective_share.tp4`` and ``allreduce_gbs.tp4`` on a synthetic trace:
exposed collective time is the collectives' union less what other ops
overlap, where the layer loop's ``while`` counts as no compute."""
from __future__ import annotations

import types

import pytest

from harness import collective_time as CT
from harness.spec import load_reader

LO, HI = 0.0, 10.0


def chip(ops):
    return [(n, float(s), float(e)) for n, s, e in ops]


#: one chip's window: a ``while`` over the layer loop (0-8), inside it a
#: fusion (1-3), an all-reduce under nothing but the ``while`` (3-4), one
#: that a fusion overlaps (5-6, fusion 4.5-7) and one half overlapped
#: (7-8, fusion 7.5-9 partly outside the loop)
CHIP = chip([("while.1", 0, 8), ("fusion.1", 1, 3), ("psum.30", 3, 4),
             ("fusion.2", 4.5, 7), ("psum.31", 5, 6), ("all-reduce.2", 7, 8),
             ("fusion.3", 7.5, 9)])


def test_names():
    assert CT.is_collective("psum.30") and CT.is_collective("all-reduce.2")
    assert CT.is_collective("all-reduce-start.4")
    assert CT.is_collective("all-gather-done")
    assert not CT.is_collective("fusion.7")
    assert not CT.is_collective("pallas.paged[64,1,7,128]:closed_call.3")
    assert CT.is_container("while.12") and not CT.is_container("fusion.1")


def test_exposed_under_the_while_counts_overlapped_does_not():
    # 3-4 under the while alone: exposed; 5-6 under a fusion: hidden;
    # 7-8 with a fusion from 7.5: half exposed
    assert CT.exposed_s(CHIP, LO, HI) == pytest.approx(1.5)
    assert CT.collective_s(CHIP, LO, HI) == pytest.approx(3.0)
    # clipped to the window
    assert CT.exposed_s(CHIP, 3.5, 10.0) == pytest.approx(1.0)


def test_share_is_over_busy_time_averaged_over_chips():
    other = chip([("fusion.1", 0, 2), ("psum.3", 2, 4)])     # 2 of 4 busy
    red = {"lo": LO, "hi": HI, "ops": {"a": CHIP, "b": other}}
    read = load_reader("collective_share.tp4")
    # CHIP: 1.5 exposed of 9 busy; other: 2 of 4
    want = (100 * 1.5 / 9 + 100 * 2 / 4) / 2
    assert read({"trace": red}) == pytest.approx(want)
    assert read({"trace": {"lo": LO, "hi": HI,
                           "ops": {"a": chip([("fusion", 0, 1)])}}}) is None
    assert read({"trace": None}) is None


def test_allreduce_gbs_reads_the_span_attribute(monkeypatch):
    from harness import program_spans
    red = {"lo": LO, "hi": HI, "ops": {"a": CHIP, "b": CHIP}}
    span = types.SimpleNamespace
    spans = [span(parent=None, attrs={"allreduce_bytes": 6e9}),
             span(parent=1, attrs={}),
             span(parent=None, attrs={"allreduce_bytes": 3e9})]
    monkeypatch.setattr(program_spans, "window_spans", lambda run: spans)
    read = load_reader("allreduce_gbs.tp4")
    assert read({"trace": red}) == pytest.approx(9e9 / 3.0 / 1e9)
    # a program whose spans carry no such attribute reads nothing
    monkeypatch.setattr(program_spans, "window_spans",
                        lambda run: [span(parent=None, attrs={})])
    assert read({"trace": red}) is None
    monkeypatch.setattr(program_spans, "window_spans", lambda run: None)
    assert read({"trace": red}) is None
