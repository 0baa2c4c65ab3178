"""Candidate tier (core/algorithms.py): verified results over learned-Bloom
candidates, from the ``shard.verify`` span attributes (%).  A count that
repeats exactly for a seed: the model's false-positive cost."""


def read(ctx):
    spans = [s for s in ctx["spans"] if s.name == "shard.verify"]
    cands = sum(int(s.attrs.get("candidates", 0)) for s in spans)
    if not cands:
        return None
    return 100.0 * sum(int(s.attrs.get("results", 0)) for s in spans) / cands
