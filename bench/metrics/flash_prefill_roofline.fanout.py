"""Roofline share of the flash attention kernel in the window's scene
prefix prefills: the least time the chip needs for the causal attention of
every prefilled scene (``roofline.flash_prefill``) over the kernel's device
time in the trace.  Moves ``ttft_p95_ms``."""
from harness import readers


def read(run):
    scenes = sum(m for _, _, _, m in run["rec"].admits)
    a = run["a"]
    return readers.kernel_share(
        run, readers.FLASH(a), *readers.roofline.flash_prefill(
            a, scenes, a["regions"]))
