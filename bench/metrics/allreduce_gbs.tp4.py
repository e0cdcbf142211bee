"""Bytes each chip all-reduced in the window, over the collective ops'
device seconds per chip (GB/s).  The bytes are the ``allreduce_bytes``
the program puts on its ``engine.step`` and ``engine.admit`` spans (hooks
that fire × layers × tokens × d_model × activation bytes); a program that
records no such attribute gives None.  Moves ``output_tokens_per_s``."""
from harness import collective_time, program_spans


def read(run):
    spans = program_spans.window_spans(run) or []
    counted = [s.attrs["allreduce_bytes"] for s in spans
               if s.parent is None and "allreduce_bytes" in s.attrs]
    sec = collective_time.seconds_per_chip(run["trace"])
    if not counted or not sec:
        return None
    return sum(counted) / sec / 1e9
