"""Tests of the benchmark harness itself (run by hand; not in the tier-1 suite):

    JAX_PLATFORMS=cpu python -m pytest -q bench/tests
"""
import copy
import json
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
for p in (str(BENCH.parent / "src"), str(BENCH)):
    if p not in sys.path:
        sys.path.insert(0, p)

TINY = {
    "n_docs": 2048, "n_terms": 3000, "avg_doc_len": 60, "zipf_a": 1.2, "zipf_b": 2.7,
    "doc_len_sigma": 0.6,
}


def tiny_cell(traffic: str, config: str = "robust04-1of4", rate: float = 40.0):
    """A cell of the committed configuration and traffic, cut to a CPU-sized
    collection with 2 shards and a short training."""
    import spec

    conf = json.loads((BENCH / "configs" / f"{config}.json").read_text())
    conf = copy.deepcopy(conf)
    conf["collection"] = dict(TINY)
    conf["serve"]["n_shards"] = 2
    conf["train"]["steps"] = 20
    bench = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    return spec.Cell(
        name=f"tiny.{traffic}", chips=1, config=conf,
        traffic=json.loads((BENCH / "traffic" / f"{traffic}.json").read_text()),
        rate_qps=rate, end_to_end=list(bench["end_to_end"]),
        per_layer=[m for m in bench["per_layer"]],
    )


def zipf(shape: dict, seed: int):
    """A collection from the ``zipf_mandelbrot`` generator."""
    import spec

    return spec.collection({"generator": "zipf_mandelbrot", "collection": shape}, seed)


CPU = {"platform": "cpu", "kind": "cpu", "count": 1}


@pytest.fixture
def cpu_device():
    return dict(CPU)
