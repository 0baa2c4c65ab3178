"""Bits the served index holds between requests, per posting of the collection.

Counted by walking the served engine's state here, not by the program's own
``memory_report()`` (whose ``model_bits`` already holds the backup keys that
its ``backup_bits`` adds again).  The walk starts at the engine and follows
every attribute, container and array it reaches, each buffer once; only the
decoded-postings caches (``DECODE_CACHES``, by attribute name) are left out,
since they are not the index.  Buffers go to the first component whose roots
reach them, in this order:

* ``tier2``: each shard's tier-2 store (codec streams and per-term
  metadata, payload streams, segment bounds);
* ``candidate_tables``: each shard's candidate-tier tables, tier-1 lists and
  block bitmaps;
* ``model``: the learned membership model (term and doc embeddings, bias),
  its per-term thresholds and its exact backup keys;
* ``uncompressed``: the global inverted index and each shard's slice of it,
  and the BM25 impact models with their quantized per-posting impacts;
* ``other``: everything else the engine reaches (each shard's slice of the
  membership model, df tables, device arenas, and any copy a later change
  keeps under a name of its own);
* ``device_streams``: device twins of store streams, which the program keeps
  in a table of its own module, as its counters report their uploads.
"""
from __future__ import annotations

import types

import numpy as np

_CANDIDATE_TABLES = ("tier1", "tier1_len", "dfs", "block_bitmaps")
DECODE_CACHES = frozenset({"_decode_cache"})
_SKIP = (str, bytes, int, float, bool, complex, types.ModuleType, type, types.FunctionType,
         types.BuiltinFunctionType, types.MethodType)


def _root(a):
    while isinstance(getattr(a, "base", None), np.ndarray):
        a = a.base
    return a


def _fields(obj) -> dict:
    fields = getattr(obj, "__dict__", None)
    if fields is None:
        slots = [s for c in type(obj).__mro__ for s in getattr(c, "__slots__", ())]
        fields = {s: getattr(obj, s, None) for s in slots}
    return {k: v for k, v in fields.items() if k not in DECODE_CACHES}


def walk_bytes(obj, seen: dict) -> int:
    """Bytes of every array reachable from ``obj`` and not yet in ``seen``,
    each buffer counted once.  ``seen`` maps id to object: holding the
    object keeps its id from being reused by a later one."""
    total, stack = 0, [obj]
    while stack:
        o = stack.pop()
        if o is None or isinstance(o, _SKIP):
            continue
        if isinstance(o, np.ndarray):
            o = _root(o)
        if id(o) in seen:
            continue
        seen[id(o)] = o
        if isinstance(o, np.ndarray) or (hasattr(o, "nbytes") and hasattr(o, "dtype")):
            total += int(o.nbytes)  # a host or a device array
        elif isinstance(o, dict):
            stack.extend(o.values())
        elif isinstance(o, (list, tuple, set, frozenset)):
            stack.extend(o)
        else:
            stack.extend(_fields(o).values())
    return total


def index_bytes(engine) -> dict[str, int]:
    """Held bytes by component."""
    from repro.kernels.arena import stream_residency_counters

    seen: dict = {}
    out = {"tier2": 0, "candidate_tables": 0}
    for sh in engine.shards:
        out["tier2"] += walk_bytes(sh.tier2, seen)
        out["candidate_tables"] += sum(
            walk_bytes(getattr(sh.state, name), seen) for name in _CANDIDATE_TABLES)
    lb = engine.lb
    out["model"] = walk_bytes([lb.params, lb.tau, lb.backup_keys], seen)
    out["uncompressed"] = walk_bytes(
        [engine.inv, engine._impact_model]
        + [[sh.inv, sh._impact_model] for sh in engine.shards], seen)
    out["other"] = walk_bytes(engine, seen)
    out["device_streams"] = int(stream_residency_counters()["upload_bytes"])
    return out
