"""Model FLOPs of every prefill and decode token done in the window (scene
prefixes, prompt rows, decode rows) over the window times the four chips'
bf16 peak: the share of the whole step.  Moves ``output_tokens_per_s``."""
from harness import readers


def read(run):
    return readers.mfu(run)
