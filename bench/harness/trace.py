"""Reduce a profiler trace to the numbers the per-layer readers use.

``load`` reads the ``.xplane.pb`` that ``jax.profiler`` writes: device ops
from each ``/device:<kind>:<n>`` plane (its ``XLA Ops`` line), and the
harness's ``bench.*`` host spans from the host plane, on the same clock.
The rest is plain arithmetic on (name, start, end) intervals in seconds,
kept apart from the loader so a test can check it on a recorded trace:

- busy: the union of a device's op intervals inside the window;
- kernel time: the summed durations of the ops whose name holds a key;
- idle gaps: the window minus busy, each piece charged to the host spans
  that overlap it (``host.other`` where none does).
"""
from __future__ import annotations

import dataclasses
import glob
import re
from typing import Dict, Iterable, List, Sequence, Tuple

Interval = Tuple[str, float, float]          # (name, start s, end s)

OP_LINE = "XLA Ops"


@dataclasses.dataclass
class Trace:
    devices: Dict[str, List[Interval]]       # plane name -> ops
    spans: List[Interval]                    # bench.* host spans


_SHAPE = re.compile(r"(\w+)\[([\d,]*)\]")


def op_name(text: str) -> str:
    """An op event's name: the HLO instruction's (``%fusion.3 = ...`` is
    ``fusion.3``), or the event's own where it is not HLO text.

    A Pallas kernel is a ``tpu_custom_call`` named after its enclosing
    scope (``closed_call.13``), so it is labelled by what it is instead:
    ``pallas.paged[<out dims>]:<name>`` where its first two operands are
    the scalar-prefetched int32 block table and lengths of a paged
    attention kernel, ``pallas.dense[<out dims>]:<name>`` otherwise."""
    if " = " not in text:
        return text
    head, rest = text.split(" = ", 1)
    name = head.lstrip("%")
    if "custom-call(" not in rest or "tpu_custom_call" not in rest:
        return name
    out = _SHAPE.match(rest)
    args = _SHAPE.findall(rest.split("custom-call(", 1)[1])
    kind = ("paged" if len(args) >= 2 and args[0][0] == "s32"
            and args[1][0] == "s32" else "dense")
    return f"pallas.{kind}[{out.group(2) if out else ''}]:{name}"


def kernel_dims(name: str):
    """Output dims of a labelled Pallas op, or None for any other op."""
    m = re.match(r"pallas\.\w+\[([\d,]*)\]", name)
    return [int(x) for x in m.group(1).split(",") if x] if m else None


def load(trace_dir: str) -> Trace:
    from jax.profiler import ProfileData
    paths = sorted(glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    pd = ProfileData.from_file(paths[-1])
    devices: Dict[str, List[Interval]] = {}
    spans: List[Interval] = []
    for plane in pd.planes:
        if plane.name.startswith("/device:") and \
                not plane.name.startswith("/device:CPU"):
            ops = [(op_name(e.name), e.start_ns * 1e-9,
                    (e.start_ns + e.duration_ns) * 1e-9)
                   for line in plane.lines if line.name == OP_LINE
                   for e in line.events]
            if ops:                 # planes without XLA ops are no chip
                devices[plane.name] = ops
        elif plane.name.startswith("/host:"):
            spans += [(e.name, e.start_ns * 1e-9,
                       (e.start_ns + e.duration_ns) * 1e-9)
                      for line in plane.lines for e in line.events
                      if e.name.startswith("bench.")]
    return Trace(devices, spans)


def window(tr: Trace, name: str = "bench.window") -> Tuple[float, float]:
    w = [s for s in tr.spans if s[0] == name]
    if not w:
        raise ValueError(f"no {name} span in the trace")
    return w[-1][1], w[-1][2]


def _clip(iv: Iterable[Interval], lo: float, hi: float) -> List[Interval]:
    return [(n, max(s, lo), min(e, hi)) for n, s, e in iv
            if e > lo and s < hi]


def union(iv: Iterable[Interval]) -> List[Tuple[float, float]]:
    out: List[List[float]] = []
    for _, s, e in sorted(iv, key=lambda x: x[1]):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def busy_s(ops: Sequence[Interval], lo: float, hi: float) -> float:
    return sum(e - s for s, e in union(_clip(ops, lo, hi)))


def gaps(ops: Sequence[Interval], lo: float, hi: float
         ) -> List[Tuple[float, float]]:
    out, t = [], lo
    for s, e in union(_clip(ops, lo, hi)):
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if t < hi:
        out.append((t, hi))
    return out


def _overlaps(pieces: Sequence[Tuple[float, float]],
              over: Sequence[Tuple[float, float]]) -> List[float]:
    """For each of ``pieces`` (sorted, disjoint) the time ``over`` (sorted,
    disjoint) covers of it, in one pass over both."""
    out, j = [], 0
    for s, e in pieces:
        while j < len(over) and over[j][1] <= s:
            j += 1
        k, cov = j, 0.0
        while k < len(over) and over[k][0] < e:
            cov += min(e, over[k][1]) - max(s, over[k][0])
            k += 1
        out.append(cov)
    return out


def idle_by_span(gap_list: Sequence[Tuple[float, float]],
                 spans: Sequence[Interval],
                 skip: Tuple[str, ...] = ("bench.window",)
                 ) -> Dict[str, float]:
    """Idle seconds charged to the host span that overlaps them; a gap's
    piece under no span is ``host.other``.  The harness's spans follow one
    another and do not nest (``bench.window`` aside, which is skipped)."""
    out: Dict[str, float] = {}
    covered = [0.0] * len(gap_list)
    for name in sorted({s[0] for s in spans} - set(skip)):
        mine = sorted((s, e) for n, s, e in spans if n == name)
        cov = _overlaps(gap_list, union((name, s, e) for s, e in mine))
        covered = [a + b for a, b in zip(covered, cov)]
        if sum(cov) > 0:
            out[name] = sum(cov)
    rest = sum(g1 - g0 for g0, g1 in gap_list) - sum(covered)
    if rest > 1e-12:
        out["host.other"] = rest
    return out


def kernel_s(ops: Sequence[Interval], match, lo: float, hi: float
             ) -> float:
    """Device seconds of the ops whose name holds ``match`` (a string) or
    satisfies it (a predicate on the name)."""
    hit = match if callable(match) else (lambda n: match in n)
    return sum(e - s for n, s, e in _clip(ops, lo, hi) if hit(n))


def op_family(name: str) -> str:
    """``fusion.123`` and ``fusion.7`` are one family: ``fusion``."""
    return re.sub(r"(\.\d+)+$", "", name)


def top_ops(ops: Sequence[Interval], lo: float, hi: float, n: int = 10
            ) -> List[List]:
    tot: Dict[str, float] = {}
    for name, s, e in _clip(ops, lo, hi):
        f = op_family(name)
        tot[f] = tot.get(f, 0.0) + (e - s)
    return [[k, v] for k, v in sorted(tot.items(), key=lambda x: -x[1])[:n]]


def reduce(tr: Trace) -> Dict:
    """Everything the readers and the result line take from one trace."""
    lo, hi = window(tr)
    devs = sorted(tr.devices)
    if not devs:
        raise ValueError("the trace holds no device plane")
    busy = [busy_s(tr.devices[d], lo, hi) for d in devs]
    idle: Dict[str, float] = {}
    for d in devs:
        for k, v in idle_by_span(gaps(tr.devices[d], lo, hi),
                                 tr.spans).items():
            idle[k] = idle.get(k, 0.0) + v / len(devs)
    all_ops = [o for d in devs for o in tr.devices[d]]
    return {
        "window_s": hi - lo, "lo": lo, "hi": hi, "n_devices": len(devs),
        "busy_s": sum(busy) / len(devs),
        "idle_gaps": [[k, v] for k, v in
                      sorted(idle.items(), key=lambda x: -x[1])[:10]],
        "device_ops": [[k, v / len(devs)]
                       for k, v in top_ops(all_ops, lo, hi)],
        "ops": {d: tr.devices[d] for d in devs},
    }
