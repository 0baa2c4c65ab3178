"""Shard executor (serve/shard.py): host time in ``shard.verify`` spans per
Boolean query answered in the window (ms/query)."""


def read(ctx):
    spans = [s for s in ctx["spans"] if s.name == "shard.verify"]
    if not spans or not ctx["n_boolean"]:
        return None
    return sum(s.dur_us for s in spans) / ctx["n_boolean"] / 1e3
