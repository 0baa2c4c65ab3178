"""Candidate tier on the device (core/algorithms.py): device time of the
jitted ``block_query`` programs in the profiler trace, per Boolean query
answered in the traced window (ms/query)."""

PROGRAM = "jit_block_query"


def read(ctx):
    dev = ctx["device"]
    if dev is None or not ctx["n_boolean"]:
        return None
    secs = sum(v for k, v in dev["modules_s"].items() if k.startswith(PROGRAM))
    return secs * 1e3 / ctx["n_boolean"] if secs > 0 else None
