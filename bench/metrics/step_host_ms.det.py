"""Mean host time of one window step outside its token fetch: the
``engine.step`` span less its ``engine.step.fetch`` (upload, dispatch,
commit and the step's own time), from the program's span ring.  Moves
``output_tokens_per_s``."""
from harness import program_spans


def read(run):
    return program_spans.step_host_ms(run)
