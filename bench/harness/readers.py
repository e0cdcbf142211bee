"""Arithmetic the per-layer readers share: work counted from the window's
record, kernel time taken from the trace.  A reader that finds nothing to
read returns None, and the metric is left out of the result line."""
from __future__ import annotations

from harness import roofline
from harness import trace as TR


def PAGED_DECODE(a):
    """Paged decode attention: a paged Pallas kernel whose output rows per
    KV head are one token's query heads, (B, KV heads, group, hd)."""
    group = a["heads"] // a["kv_heads"]

    def match(name):
        d = TR.kernel_dims(name)
        return (name.startswith("pallas.paged[") and d is not None
                and len(d) == 4 and d[2] == group)
    return match


def FLASH(a):
    """Flash attention over a scene prefix: a dense Pallas kernel whose
    output is (B, heads, regions, hd)."""
    def match(name):
        d = TR.kernel_dims(name)
        return (name.startswith("pallas.dense[") and d is not None
                and len(d) == 4 and d[2] == a["regions"])
    return match


def kernel_seconds(run, match):
    """Device seconds of the kernel ``match`` picks, averaged over chips."""
    red = run["trace"]
    if red is None:
        return None
    ops = red["ops"]
    return sum(TR.kernel_s(o, match, red["lo"], red["hi"])
               for o in ops.values()) / len(ops)


def kernel_share(run, match, flops: float, nbytes: float):
    sec = kernel_seconds(run, match)
    if not sec:
        return None
    return roofline.share(flops, nbytes, sec, run["peaks"], run["chips"])


def paged_decode_work(run):
    """(flops, bytes) of every paged decode attention call in the window:
    each step over its active rows, each admission over its admitted rows
    (their prompt token reads the whole region prefix)."""
    a, rec = run["a"], run["rec"]
    fl = by = 0
    for _, _, rows, ctx in rec.steps:
        f, b = roofline.paged_decode(a, rows, ctx)
        fl, by = fl + f, by + b
    for _, _, k, _ in rec.admits:
        f, b = roofline.paged_decode(a, k, k * (a["regions"] + 1))
        fl, by = fl + f, by + b
    return fl, by


def paged_decode_share(run):
    return kernel_share(run, PAGED_DECODE(run["a"]), *paged_decode_work(run))


def mfu(run):
    a, rec = run["a"], run["rec"]
    flops = sum(roofline.decode_flops(a, rows, ctx)
                for _, _, rows, ctx in rec.steps)
    flops += sum(roofline.decode_flops(a, k, k * (a["regions"] + 1))
                 + roofline.prefix_flops(a, m)
                 for _, _, k, m in rec.admits)
    if rec.window_s <= 0 or flops <= 0:
        return None
    return 100.0 * flops / (rec.window_s * run["peaks"]["flops_bf16"]
                            * run["chips"])
