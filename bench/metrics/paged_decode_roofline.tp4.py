"""Roofline share of the paged decode attention kernel in the window, at
one chip's share of the heads (64 rows, 1 KV head, group 7): the least
time the four chips need to read each live row's KV once (decode steps and
admitted prompt rows, ``roofline.paged_decode``) over the kernel's device
time per chip.  Moves ``output_tokens_per_s``."""
from harness import readers


def read(run):
    return readers.paged_decode_share(run)
