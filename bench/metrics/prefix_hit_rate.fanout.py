"""Share of admitted requests whose scene prefix was already resident,
counted over the window only (``EngineCore.stats`` prefix_hits and
prefix_misses).  Moves ``ttft_p95_ms``."""


def read(run):
    c0, c1 = run["rec"].counters0, run["rec"].counters1
    hits = c1["prefix_hits"] - c0["prefix_hits"]
    misses = c1["prefix_misses"] - c0["prefix_misses"]
    if hits + misses == 0:
        return None
    return 100.0 * hits / (hits + misses)
