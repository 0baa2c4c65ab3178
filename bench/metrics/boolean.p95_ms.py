"""Open-loop client, Boolean cells whose tail is not an end-to-end metric:
95th percentile of the latency of every request due in the window, from its
due time, on the client side of ``Session.submit_async`` (ms)."""


def read(ctx):
    return ctx["p95_ms"] if ctx["mode"] == "boolean" else None
