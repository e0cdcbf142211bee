"""Exposed collective time over busy time, per chip, averaged over the
four chips: the part of the window's device time in which a chip runs an
all-reduce (or another collective) and nothing else.  The names matched,
and why the layer loop's ``while`` is no compute, are in
``harness/collective_time.py``.  Moves ``output_tokens_per_s``."""
from harness import collective_time


def read(run):
    return collective_time.exposed_share(run["trace"])
