"""Planner (serve/planner.py): host time in ``serve.plan`` spans per Boolean
query answered in the window (ms/query).  Every shard plans the whole batch,
so a batch over 4 shards counts 4 plans."""


def read(ctx):
    spans = [s for s in ctx["spans"] if s.name == "serve.plan"]
    if not spans or not ctx["n_boolean"]:
        return None
    return sum(s.dur_us for s in spans) / ctx["n_boolean"] / 1e3
