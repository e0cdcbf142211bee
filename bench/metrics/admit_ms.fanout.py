"""Mean host time of one ``EngineCore.admit_many`` call in the window (the
``bench.admit`` span): prefix lookup, any scene prefill it dispatches, the
prompt row, block-table upload.  Moves ``ttft_p95_ms``."""


def read(run):
    adm = run["rec"].admits
    if not adm:
        return None
    return 1e3 * sum(t1 - t0 for t0, t1, _, _ in adm) / len(adm)
