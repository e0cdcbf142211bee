"""Peaks of the chip and the operations and bytes each kernel and the whole
model need, computed from shapes.

The counts are of the work the algorithm needs: a decode row reads the KV
of its live tokens once, plus its q and writes its out; a prefill reads
q, k and v and writes out once, and does the causal half of the score and
value products.  Padded capacity, skipped blocks the grid visits and the
program's own recomputation are not counted, so the count is the same
whatever implements the kernel and a share of the roofline cannot pass
100% unless the time is short of the work.
"""
from __future__ import annotations

from typing import Dict

#: Published peaks per chip, keyed by ``jax.Device.device_kind``.
#: Source: Google Cloud documentation, "TPU v5e" (system architecture):
#: 197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM at 819 GB/s.
PEAKS = {
    "TPU v5 lite": {"flops_bf16": 197e12, "hbm_bytes_s": 819e9},
}


def peaks(device_kind: str) -> Dict[str, float]:
    if device_kind not in PEAKS:
        raise KeyError(f"no peaks for device kind {device_kind!r}; add its "
                       "published numbers to bench/harness/roofline.py")
    return PEAKS[device_kind]


def paged_decode(a, rows: int, ctx_sum: int, bytes_el: int = 2):
    """One paged decode attention call over ``rows`` rows whose attended
    lengths sum to ``ctx_sum``, for the whole stack.  Returns (flops,
    bytes)."""
    per_layer_flops = 4 * ctx_sum * a["heads"] * a["hd"]
    per_layer_bytes = (2 * ctx_sum * a["kv_heads"] * a["hd"]
                       + 2 * rows * a["heads"] * a["hd"]) * bytes_el
    return a["layers"] * per_layer_flops, a["layers"] * per_layer_bytes


def flash_prefill(a, seqs: int, s: int, bytes_el: int = 2):
    """Causal flash attention over ``seqs`` sequences of ``s`` tokens, for
    the whole stack."""
    pairs = s * (s + 1) // 2
    per_layer_flops = 4 * pairs * a["heads"] * a["hd"] * seqs
    per_layer_bytes = (2 * s * a["heads"] * a["hd"]
                       + 2 * s * a["kv_heads"] * a["hd"]) * bytes_el * seqs
    return a["layers"] * per_layer_flops, a["layers"] * per_layer_bytes


def matmul_flops_per_token(a) -> int:
    d, hd = a["d"], a["hd"]
    per_layer = 2 * (d * a["heads"] * hd + 2 * d * a["kv_heads"] * hd
                     + a["heads"] * hd * d + 3 * d * a["ff"])
    return a["layers"] * per_layer


def decode_flops(a, rows: int, ctx_sum: int) -> int:
    """Model FLOPs of ``rows`` decode tokens: projections, MLP, attention
    over ``ctx_sum`` live positions, and the unembedding."""
    return (rows * (matmul_flops_per_token(a) + 2 * a["d"] * a["vocab"])
            + paged_decode(a, rows, ctx_sum)[0])


def prefix_flops(a, scenes: int) -> int:
    """Model FLOPs of ``scenes`` region-prefix prefills: the patch
    projection, projections and MLP of every region token, and causal
    attention; the prefix's own logits are not needed."""
    r = a["regions"]
    return (scenes * r * (matmul_flops_per_token(a)
                          + 2 * a["patch_dim"] * a["d"])
            + flash_prefill(a, scenes, r)[0])


def share(flops: float, nbytes: float, seconds: float, pk: Dict[str, float],
          chips: int = 1):
    """Percent of the roofline: the least time the chips could take for
    this work over the time it took; None where nothing ran."""
    if seconds <= 0 or (flops <= 0 and nbytes <= 0):
        return None
    least = max(flops / (pk["flops_bf16"] * chips),
                nbytes / (pk["hbm_bytes_s"] * chips))
    return 100.0 * least / seconds
