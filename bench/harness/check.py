"""What decides ``correct``: the served answers and logits set against the
plain reference, run once the program's state is freed.

Two numbers are compared, each with a limit from ``bench/limits``:

- ``widest_logit_gap``: over a sample (drawn from the seed) of what the
  window served, how far below the reference's best logit the reference
  puts each served token, at its worst.  Zero where every served token is
  the reference's argmax; a token altered where it is produced lies far
  below.
- ``answer_logit_err``: where the timed path's own answer-vocabulary
  logits were read (the next token's, for rows still decoding when the
  window closed; the answer's, for queries admitted after the window's
  requests were all answered), the largest ``max |program - reference| /
  max |reference|`` over those positions.  It reads how far the
  arithmetic lies from float32, also where no served token happens to sit
  near a tie.

``controls`` names reference precisions (``fp8``) read at the same
positions in the program's place: the gap of the token the control puts
first, and the control's logit error.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Sequence

import numpy as np

from harness import reference


@dataclasses.dataclass
class Item:
    """One request to compare: its scene, prompt id, served tokens, and the
    program's logits for the position after them, where read."""
    scene: int
    prompt_id: int
    tokens: Sequence[int]
    gap: bool = True                     # compare the served tokens
    logits: Optional[np.ndarray] = None  # (answer_vocab,) after ``tokens``


def _rel_err(got: np.ndarray, ref: np.ndarray) -> float:
    ref = np.asarray(ref, np.float64)
    return float(np.abs(np.asarray(got, np.float64) - ref).max()
                 / max(np.abs(ref).max(), 1e-30))


def compare(seed: int, a: Dict, items: List[Item], images,
            controls: Sequence[str] = ()) -> Dict[str, Dict[str, float]]:
    """Readings of the program (``"program"``) and of each control:
    ``{"widest_logit_gap", "answer_logit_err", "gap_tokens",
    "err_positions"}``."""
    by_scene: Dict[int, List[Item]] = {}
    for it in items:
        by_scene.setdefault(it.scene, []).append(it)
    scenes = sorted(by_scene)
    pix = np.stack([images(s) for s in scenes])
    # a request whose next-token logits were read runs one position more
    suffixes = [[np.asarray([it.prompt_id] + list(
        it.tokens if it.logits is not None else it.tokens[:-1]), np.int32)
        for it in by_scene[s]] for s in scenes]
    ref = reference.answer_logits(seed, a, pix, suffixes)
    ctl = {p: reference.answer_logits(seed, a, pix, suffixes, p)
           for p in controls}
    out = {k: {"widest_logit_gap": 0.0, "answer_logit_err": 0.0,
               "gap_tokens": 0, "err_positions": 0}
           for k in ("program",) + tuple(controls)}

    def note(who, key, v):
        out[who][key] = max(out[who][key], v)

    for i, s in enumerate(scenes):
        for j, it in enumerate(by_scene[s]):
            r = ref[i][j]
            n = len(it.tokens)
            if it.gap and n:
                chosen = np.asarray(it.tokens, np.int64)
                note("program", "widest_logit_gap",
                     float(reference.gaps(r[:n], chosen).max()))
                for p in controls:
                    note(p, "widest_logit_gap", float(reference.gaps(
                        r[:n], ctl[p][i][j][:n].argmax(-1)).max()))
                for k in out:
                    out[k]["gap_tokens"] += n
            if it.logits is not None:
                note("program", "answer_logit_err",
                     _rel_err(it.logits, r[n]))
                for p in controls:
                    note(p, "answer_logit_err", _rel_err(ctl[p][i][j][n],
                                                         r[n]))
                for k in out:
                    out[k]["err_positions"] += 1
    return out
