"""Admission and batching (serve/sched), Boolean cells whose tail is not an
end-to-end metric: mean wait from submit to dispatch, from the session's
``sched.queue_us`` histogram over the window (ms)."""


def read(ctx):
    h = (ctx["snapshot"].get("sched") or {}).get("queue_us")
    if ctx["mode"] != "boolean" or not h or not h["count"]:
        return None
    return h["sum"] / h["count"] / 1e3
