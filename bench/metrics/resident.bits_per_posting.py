"""Resident index state: everything the served engine holds besides the
tier-2 stores -- candidate tables, the membership model with thresholds and
backup keys, every uncompressed copy of the postings, whatever else the walk
from the engine reaches, device twins of store streams -- from the harness's
walk after the window (``memory.py``), in bits per posting."""


def read(ctx):
    b = ctx["index_bytes"]
    return 8.0 * (sum(b.values()) - b["tier2"]) / ctx["n_postings"]
