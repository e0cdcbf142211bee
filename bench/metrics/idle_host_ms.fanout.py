"""Device idle per window step under the engine's host phases: every leaf
``engine.*`` span but ``engine.step.fetch``, placed on the trace's clock
(``harness/program_spans.py``).  Moves ``ttft_p95_ms``."""
from harness import program_spans


def read(run):
    return program_spans.idle_host_ms(run)
