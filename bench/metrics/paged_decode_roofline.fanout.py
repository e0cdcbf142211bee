"""Roofline share of the paged decode attention kernel in the window: the
least time the chip needs to read each live row's KV once (decode steps
and admitted prompt rows, ``roofline.paged_decode``) over the kernel's
device time in the trace.  Moves ``ttft_p95_ms``."""
from harness import readers


def read(run):
    return readers.paged_decode_share(run)
