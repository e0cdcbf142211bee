"""Per-(backend, kernel, dtype) tile autotuning for the serving kernels.

The paged Pallas kernels expose tunable tile knobs — ``fan`` (for paged
decode and verify, the pages one in-kernel chunk copies and reduces, with
``None`` picking about 256 tokens' worth from the shapes; for prefill, the
pages one grid step fetches), ``q_blk`` (prefill query sub-block rows per
program) and the dense decode kernel's ``kv_blk`` (KV rows per program) —
whose best values depend on the executing backend: the interpret oracle
pays per-step Python overhead, a real MXU wants tiles near its native
shape, and the CPU ``ref`` path ignores them entirely.  Hand-picked
defaults therefore leave speed on the table on every backend but the one
they were picked on.

``sweep()`` times each candidate config on representative serving shapes
(median of repeats, executed on the live backend) and records the winners
in ``kernels/tuned/{backend}.json`` — one checked-in file per backend key
(``cpu``, ``cpu-interpret``, ``gpu``, ``tpu``) so results travel with the
repo.  ``ops.py`` consults ``lookup()`` at dispatch time: the resolution is
a pure-Python dict read at trace time, so a tuned config is exactly as
static as the old hard-coded default (CompileGuard-clean steady state).

Overrides, strongest first:

- explicit kernel kwargs (``ops.paged_prefill_attention(..., q_blk=4)``)
  always win — the escape hatch for tests and callers that know better;
- ``REPRO_KERNEL_TUNED=off`` ignores the tuned files process-wide and
  falls back to the hand-picked defaults (bisecting a suspect config);
- otherwise the backend's tuned file, then ``DEFAULTS``.

Regenerate with::

    PYTHONPATH=src python -m repro.kernels.autotune                 # this backend
    PYTHONPATH=src python -m repro.kernels.autotune --interpret     # interpret leg
"""
from __future__ import annotations

import argparse
import functools
import json
import os
import time
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.kernels import kv_quant
from repro.kernels.decode_attention import (decode_attention_pallas,
                                            paged_decode_attention_pallas,
                                            paged_prefill_attention_pallas)

TUNED_DIR = os.path.join(os.path.dirname(os.path.abspath(__file__)), "tuned")

DTYPE_KEYS = ("fp32", "int8", "fp8")

# candidate values per kernel — every candidate is legal on every shape
# (the kernels clamp to the table width, prefill to its divisors), so a
# tuned file can never break a call
SPACE: Dict[str, Dict[str, Tuple[Any, ...]]] = {
    "decode_dense": {"kv_blk": (128, 256, 512)},
    "paged_decode": {"fan": (None, 8, 16, 32, 64)},
    "paged_verify": {"fan": (None, 8, 16, 32, 64)},
    "paged_prefill": {"q_blk": (4, 8, 16), "fan": (1, 2, 4)},
}

# the hand-picked pre-autotune values (also the REPRO_KERNEL_TUNED=off
# fallback and the baseline `sweep` reports its speedup against); a None
# ``fan`` is the paged decode kernel's own pick from the shapes
DEFAULTS: Dict[str, Dict[str, Any]] = {
    "decode_dense": {"kv_blk": 256},
    "paged_decode": {"fan": None},
    "paged_verify": {"fan": None},
    "paged_prefill": {"q_blk": 8, "fan": 1},
}

# the dense decode kernel has no quantized path (scales ride the paged
# pools only), so its dtype axis collapses to fp32
KERNEL_DTYPES: Dict[str, Tuple[str, ...]] = {"decode_dense": ("fp32",)}


def dtype_key(pool_dtype) -> str:
    """Map a KV-pool leaf dtype onto the tuned-config dtype axis."""
    d = jnp.dtype(pool_dtype)
    if d == jnp.int8:
        return "int8"
    if d == jnp.dtype(jnp.float8_e4m3fn):
        return "fp8"
    return "fp32"


def backend_key(interpret: bool = False) -> str:
    """The tuned-file key for the currently executing backend.  Interpret
    mode is its own backend for tuning purposes: the kernel bodies run in
    Python, with a completely different cost model from compiled code."""
    base = jax.default_backend()
    return f"{base}-interpret" if interpret else base


def _tuned_path(backend: str) -> str:
    return os.path.join(TUNED_DIR, f"{backend}.json")


@functools.lru_cache(maxsize=None)
def _load_tuned(backend: str) -> Dict[str, Any]:
    try:
        with open(_tuned_path(backend)) as f:
            return json.load(f).get("configs", {})
    except (OSError, ValueError):
        return {}


def reload_tuned() -> None:
    """Drop the tuned-file cache (after a fresh ``sweep`` run)."""
    _load_tuned.cache_clear()


def lookup(kernel: str, dtype: str, *, interpret: bool = False
           ) -> Dict[str, Any]:
    """The knob dict ``ops.py`` dispatches with: defaults overlaid with the
    backend's tuned entry for (kernel, dtype) unless tuning is disabled."""
    cfg = dict(DEFAULTS[kernel])
    if os.environ.get("REPRO_KERNEL_TUNED", "").lower() in ("off", "0"):
        return cfg
    tuned = _load_tuned(backend_key(interpret)).get(kernel, {})
    cfg.update(tuned.get(dtype, {}))
    return cfg


# ---------------------------------------------------------------------------
# the sweep: representative serving shapes, timed on the live backend
# ---------------------------------------------------------------------------

def _bench_operands(dtype: str, seed: int = 0, s: int = 64):
    """One representative paged serving shape (mirrors the proxy engine:
    GQA 4:2 heads, hd 32, 8-slot pages, ``s``-token caches over ``s / 8``
    logical blocks, ragged lengths)."""
    h, kh, hd, page, b = 4, 2, 32, 8, 8
    n_logical = s // page
    n_pages = 1 + b * n_logical
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(seed), 3)
    kp = jax.random.normal(k1, (n_pages, page, kh, hd), jnp.float32)
    vp = jax.random.normal(k2, (n_pages, page, kh, hd), jnp.float32)
    strategy = {"fp32": "exact", "int8": "int8", "fp8": "fp8"}[dtype]
    pools = kv_quant.get_strategy(strategy).make_pools(kp, vp)
    bt = jnp.arange(1 + b * n_logical, dtype=jnp.int32)[1:] \
            .reshape(b, n_logical)
    clen = jnp.asarray([s, s // 2, s, page, s, s - 3, s, s // 2], jnp.int32)
    return pools, bt, clen, (h, kh, hd, page), k3


PAGED_DECODE_ROW_TOKENS = 136


def _kernel_call(kernel: str, dtype: str, cfg: Dict[str, Any]):
    """Build a zero-arg thunk running one kernel invocation with ``cfg``.
    Paged decode and verify get 136-token rows (17 pages, which no chunk
    size divides), so the chunk candidates walk different schedules."""
    s = PAGED_DECODE_ROW_TOKENS if kernel in ("paged_decode", "paged_verify") \
        else 64
    pools, bt, clen, (h, kh, hd, page), kq = _bench_operands(dtype, s=s)
    kp = pools["k"].transpose(0, 2, 1, 3)
    vp = pools["v"].transpose(0, 2, 1, 3)
    scales = {}
    if "k_scale" in pools:
        scales = {"k_scale": pools["k_scale"].transpose(0, 2, 1)[..., None],
                  "v_scale": pools["v_scale"].transpose(0, 2, 1)[..., None]}
    b = bt.shape[0]
    group = h // kh
    interp = jax.default_backend() != "tpu"

    def jitted(fn, *args, paged=True, **static):
        # compiled once in the warm-up call, so the timed calls run the
        # kernel, not its tracing; scales ride the paged kernels only
        f = jax.jit(functools.partial(fn, interpret=interp, **static))
        kw = scales if paged else {}
        return lambda: f(*args, **kw)

    if kernel == "decode_dense":
        s = 512
        clen_d = jnp.minimum(clen * 8, s)
        kd = jax.random.normal(kq, (b, kh, s, hd), jnp.float32)
        q = jax.random.normal(kq, (b, kh, group, hd), jnp.float32)
        return jitted(decode_attention_pallas, q, kd, kd, clen_d,
                      paged=False, kv_blk=cfg["kv_blk"])
    if kernel == "paged_decode":
        q = jax.random.normal(kq, (b, kh, group, hd), jnp.float32)
        return jitted(paged_decode_attention_pallas, q, kp, vp, bt, clen,
                      fan=cfg["fan"])
    if kernel == "paged_verify":
        t = 3                                   # γ+1 verify chunk
        q = jax.random.normal(kq, (b, kh, t * group, hd), jnp.float32)
        return jitted(paged_decode_attention_pallas, q, kp, vp, bt, clen,
                      q_len=t, fan=cfg["fan"])
    if kernel == "paged_prefill":
        c = 16                                  # prefill chunk
        q = jax.random.normal(kq, (b, kh, c * group, hd), jnp.float32)
        return jitted(paged_prefill_attention_pallas, q, kp, vp, bt, clen,
                      q_len=c, q_blk=cfg["q_blk"], fan=cfg["fan"])
    raise ValueError(f"unknown kernel {kernel!r}")


def _time_ms(thunk, repeats: int) -> float:
    jax.block_until_ready(thunk())            # warmup / trace
    best = float("inf")
    for _ in range(repeats):
        t0 = time.perf_counter()
        jax.block_until_ready(thunk())
        best = min(best, (time.perf_counter() - t0) * 1e3)
    return best


def _configs(kernel: str):
    """Cartesian product over the kernel's knob space."""
    items = sorted(SPACE[kernel].items())
    out = [{}]
    for name, values in items:
        out = [{**c, name: v} for c in out for v in values]
    return out


def sweep(kernels=None, dtypes=DTYPE_KEYS, repeats: int = 3,
          interpret: Optional[bool] = None) -> Dict[str, Any]:
    """Time every candidate config per (kernel, dtype) on the live backend
    and return the tuned-file record (winners + the full timing table).
    ``interpret`` only labels the backend key — off-TPU the kernels always
    execute interpreted."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    kernels = kernels or sorted(SPACE)
    configs: Dict[str, Any] = {}
    timings: Dict[str, Any] = {}
    for kernel in kernels:
        configs[kernel] = {}
        timings[kernel] = {}
        for dtype in dtypes:
            if dtype not in KERNEL_DTYPES.get(kernel, DTYPE_KEYS):
                continue
            rows = []
            for cfg in _configs(kernel):
                ms = _time_ms(_kernel_call(kernel, dtype, cfg), repeats)
                rows.append({"config": cfg, "ms": round(ms, 4)})
            best = min(rows, key=lambda r: r["ms"])
            default_ms = next(r["ms"] for r in rows
                              if r["config"] == DEFAULTS[kernel])
            configs[kernel][dtype] = best["config"]
            timings[kernel][dtype] = {
                "sweep": rows,
                "default_ms": default_ms,
                "best_ms": best["ms"],
                "speedup_vs_default": round(default_ms / best["ms"], 3),
            }
    return {
        "backend": backend_key(interpret),
        "tool": "repro.kernels.autotune",
        "shapes": "proxy serving: GQA 4:2, hd 32, page 8, 8x64-token rows "
                  "(paged decode and verify: 8x136)",
        "repeats": repeats,
        "configs": configs,
        "timings_ms": timings,
    }


def write_tuned(record: Dict[str, Any], path: Optional[str] = None) -> str:
    path = path or _tuned_path(record["backend"])
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        json.dump(record, f, indent=1, sort_keys=True)
        f.write("\n")
    reload_tuned()
    return path


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--kernels", default=None,
                    help="comma list (default: all)")
    ap.add_argument("--dtypes", default=",".join(DTYPE_KEYS))
    ap.add_argument("--repeats", type=int, default=3)
    ap.add_argument("--interpret", action="store_true",
                    help="label the record as the <backend>-interpret leg")
    ap.add_argument("--out", default=None,
                    help="output path (default kernels/tuned/{backend}.json)")
    args = ap.parse_args(argv)
    kernels = args.kernels.split(",") if args.kernels else None
    rec = sweep(kernels=kernels, dtypes=tuple(args.dtypes.split(",")),
                repeats=args.repeats,
                interpret=args.interpret or None)
    path = write_tuned(rec, args.out)
    for kernel, per_dtype in rec["timings_ms"].items():
        for dtype, t in per_dtype.items():
            print(f"{rec['backend']:>16} {kernel:>14} {dtype:>5}: "
                  f"{t['default_ms']:8.3f} ms -> {t['best_ms']:8.3f} ms "
                  f"({t['speedup_vs_default']:.2f}x) "
                  f"{rec['configs'][kernel][dtype]}")
    print(f"wrote {path}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
