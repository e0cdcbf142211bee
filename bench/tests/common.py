"""A tiny cell of each loop, built without BENCHMARK.json, for tests that
drive the harness on the CPU."""
from __future__ import annotations

import copy
import json
import pathlib
import sys

BENCH = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

from harness.spec import Cell, Metric, load_reader  # noqa: E402

TINY = json.loads((BENCH / "tests" / "data" / "tiny.json").read_text())

OPEN = {"loop": "open", "scene_period_s": 0.25, "history_s": 1.0,
        "queries": [{"task": "vqa", "count": 3, "offset_s": 0.0},
                    {"task": "cls", "count": 1, "offset_s": 0.0},
                    {"task": "vqa", "count": 4, "spread_s": 1.0}],
        "check_sample": 8, "logit_sample": 8}
CLOSED = {"loop": "closed", "task": "det", "concurrency": "slots",
          "warm_steps": 2, "check_sample": 8}


def tiny_cell(loop: str, limits, config=None) -> Cell:
    """``limits``: a cell's limits file, as ``bench/limits`` holds them."""
    e2e = [Metric("setup_s", "s", "lower", "host_clock")]
    if loop == "open":
        e2e.append(Metric("ttft_p95_ms", "ms", "lower", "host_clock"))
    else:
        e2e.append(Metric("output_tokens_per_s", "tokens/s", "higher",
                          "host_clock"))
    per_layer = [Metric(n, "%", "higher", "host_clock", load_reader(n))
                 for n in ("prefix_hit_rate.fanout",)]
    return Cell(f"tiny-{loop}", 1, copy.deepcopy(config or TINY),
                OPEN if loop == "open" else CLOSED, copy.deepcopy(limits),
                e2e, per_layer)
