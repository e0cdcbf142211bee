"""Collective time on each chip, from the reduced trace's device ops.

A tensor-parallel step all-reduces after every layer's attention and MLP
output projections.  In the trace those ops carry the HLO instruction's
name, which for an all-reduce that ``jax.lax.psum`` staged is ``psum.<n>``
(the opcode, ``all-reduce``, is not kept in the name): a v5e trace of the
7B's decode step on a (1, 4) mesh shows ``psum.30`` and ``psum.31``, 56 a
step on each chip, and no other collective.  XLA's own collectives are
``all-reduce.<n>``, ``all-gather.<n>``, ``reduce-scatter``,
``collective-permute`` and ``all-to-all``, with ``-start`` / ``-done``
halves where they run asynchronously.  A family (``trace.op_family``)
that begins with any of ``COLLECTIVE`` is a collective.

Exposed collective time is the union of a chip's collective intervals
less the part that any other op overlaps.  Control-flow containers
(``CONTAINER``) are no compute: the layer loop's ``while`` spans its whole
body, collectives included.
"""
from __future__ import annotations

from typing import List, Optional, Sequence, Tuple

from harness import trace as TR

COLLECTIVE = ("psum", "all-reduce", "all-gather", "reduce-scatter",
              "collective-permute", "all-to-all")
CONTAINER = ("while", "conditional", "call")


def is_collective(name: str) -> bool:
    return TR.op_family(name).startswith(COLLECTIVE)


def is_container(name: str) -> bool:
    return TR.op_family(name) in CONTAINER


def _minus(a: Sequence[Tuple[float, float]],
           b: Sequence[Tuple[float, float]]) -> List[Tuple[float, float]]:
    """The parts of ``a`` that ``b`` does not cover (both sorted and
    disjoint)."""
    out, j = [], 0
    for s, e in a:
        while j < len(b) and b[j][1] <= s:
            j += 1
        k, t = j, s
        while k < len(b) and b[k][0] < e:
            if b[k][0] > t:
                out.append((t, b[k][0]))
            t = max(t, b[k][1])
            k += 1
        if t < e:
            out.append((t, e))
    return out


def exposed_s(ops: Sequence[TR.Interval], lo: float, hi: float) -> float:
    """Seconds in [lo, hi] in which a collective runs and no other op but
    a container does."""
    ops = TR._clip(ops, lo, hi)
    coll = TR.union(o for o in ops if is_collective(o[0]))
    work = TR.union(o for o in ops
                    if not is_collective(o[0]) and not is_container(o[0]))
    return sum(e - s for s, e in _minus(coll, work))


def collective_s(ops: Sequence[TR.Interval], lo: float, hi: float) -> float:
    """Summed device seconds of the collective ops in [lo, hi]."""
    return TR.kernel_s(ops, is_collective, lo, hi)


def exposed_share(red) -> Optional[float]:
    """Percent of each chip's busy time that is exposed collective time,
    averaged over chips; None where no collective ran."""
    if red is None:
        return None
    lo, hi, ops = red["lo"], red["hi"], red["ops"]
    if not any(is_collective(n) for o in ops.values() for n, _, _ in o):
        return None
    shares = [100.0 * exposed_s(o, lo, hi) / TR.busy_s(o, lo, hi)
              for o in ops.values() if TR.busy_s(o, lo, hi) > 0]
    return sum(shares) / len(shares) if shares else None


def seconds_per_chip(red) -> Optional[float]:
    """Collective device seconds in the window, averaged over chips."""
    if red is None:
        return None
    ops = red["ops"]
    return sum(collective_s(o, red["lo"], red["hi"])
               for o in ops.values()) / len(ops)
