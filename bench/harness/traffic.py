"""The one traffic generator: it reads a mix's parameters from
``bench/traffic/<mix>.json`` and makes the requests from the seed.

Two loops exist.

``"loop": "open"`` — scenes are captured on a fixed period
(``scene_period_s``) and each scene gets the queries of ``queries``:
``{"task", "count", "offset_s"}`` arrive ``offset_s`` after the capture;
``{"task", "count", "spread_s"}`` spread over the ``spread_s`` seconds that
follow it, query k of scene i at ``spread_s · (k + φ_i) / count`` with
φ_i = frac(i · 0.618...), a low-discrepancy sequence.  The arrival times do
not depend on the seed: every seed gets the same arrivals and the same
work, and the seed draws what is asked (prompts, pixels).
Scenes captured in the ``history_s`` seconds before the window feed it the
late queries a steady stream would; set-up serves their earlier queries,
which leaves the prefix cache as the stream would have left it.

``"loop": "closed"`` — ``concurrency`` requests of ``task`` (``"slots"``:
one per engine slot), each on a scene of its own; a request that finishes
is replaced at once by one on a new scene.

Images are ``scene_image(seed, i)``: scene i is the rows ``i mod 2048`` to
``i mod 2048 + size`` of one float32 field of pixels in [0, 1) made from
the seed, so every scene of a run is distinct, a scene costs no memory of
its own, and the reference can make any scene again.  Prompts are drawn
from the seed.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, List

import numpy as np


@dataclasses.dataclass
class Query:
    t: float            # scheduled arrival, seconds from the window's start
    scene: int
    task: str
    prompt: int


#: step of the arrival phases' low-discrepancy sequence, (sqrt(5) - 1) / 2
GOLDEN = 0.6180339887498949
#: distinct scene offsets into the field
FIELD_ROWS = 2048


@functools.lru_cache(maxsize=2)
def _field(seed: int, side: int, channels: int) -> np.ndarray:
    rng = np.random.default_rng([seed, 1])
    f = rng.random((side + FIELD_ROWS, side, channels), dtype=np.float32)
    f.flags.writeable = False
    return f


def scene_image(seed: int, scene: int, a: Dict[str, Any]) -> np.ndarray:
    side = a["image_size"]
    off = scene % FIELD_ROWS
    return _field(seed, side, a["channels"])[off:off + side]


def _prompt(rng, task: str, a) -> int:
    return int(rng.integers(a["num_classes"])) if task in ("vqa", "det") \
        else 0


def open_schedule(mix: Dict[str, Any], seed: int, horizon_s: float,
                  a: Dict[str, Any]) -> List[Query]:
    """Every query of the scenes captured in [-history_s, horizon_s),
    sorted by arrival (scene ids count from the first of them)."""
    rng = np.random.default_rng([seed, 2])
    period = float(mix["scene_period_s"])
    first = -int(np.ceil(mix.get("history_s", 0.0) / period))
    last = int(np.ceil(horizon_s / period))
    n = last - first
    phases = (np.arange(n) * GOLDEN) % 1.0
    out: List[Query] = []
    for j in range(n):
        t0 = (first + j) * period
        for q in mix["queries"]:
            for k in range(int(q["count"])):
                if "spread_s" in q:
                    t = t0 + q["spread_s"] * (k + phases[j]) / q["count"]
                else:
                    t = t0 + float(q.get("offset_s", 0.0))
                out.append(Query(t, j, q["task"], _prompt(rng, q["task"], a)))
    out.sort(key=lambda q: (q.t, q.scene))
    return out


def closed_queries(mix: Dict[str, Any], seed: int, n: int,
                   a: Dict[str, Any]) -> List[Query]:
    """``n`` queries of the closed loop, scene i for the i-th (the first
    ``concurrency`` fill the engine, the rest refill it in order)."""
    rng = np.random.default_rng([seed, 3])
    return [Query(0.0, i, mix["task"], _prompt(rng, mix["task"], a))
            for i in range(n)]


def answer_len(task: str, a: Dict[str, Any]) -> int:
    return a["regions"] if task == "det" else 1
