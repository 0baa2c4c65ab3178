"""Postings store (postings/hybrid.py): the bytes of every shard's tier-2
store -- codec streams, per-term metadata, payload streams, segment bounds --
from the harness's walk of the served engine after the window, in bits per
posting of the collection."""


def read(ctx):
    return 8.0 * ctx["index_bytes"]["tier2"] / ctx["n_postings"]
