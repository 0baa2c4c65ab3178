"""Roofline analysis from dry-run artifacts (EXPERIMENTS.md §Roofline).

Hardware model: the per-chip peaks of ``PEAKS``, keyed by the device kind
JAX reports (``jax.devices()[0].device_kind``); a kind that is not in the
table is an error, never a default.
Terms (per device, per step):
  compute_s    = HLO_flops / bf16 peak
  memory_s     = HLO_bytes / HBM bandwidth
  collective_s = Σ collective bytes / interconnect bandwidth
The dominant term is the bottleneck; MODEL_FLOPS/HLO_FLOPS measures how much
compiled compute is "useful" (catches remat/redundancy waste).
"""
from __future__ import annotations

import json
from typing import Any

# Published per-chip peaks.  Source: Google Cloud documentation, "TPU v5e"
# (197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM at 819 GB/s, 1,600 Gbit/s
# chip-to-chip interconnect).  The int32 VPU throughput the index kernels
# use is not published: not measured.
PEAKS: dict[str, dict[str, float]] = {
    "TPU v5 lite": {
        "bf16_flops": 197e12,
        "int8_ops": 393e12,
        "hbm_bytes_per_s": 819e9,
        "ici_bytes_per_s": 1600e9 / 8,
    },
}


def peaks(device_kind: str) -> dict[str, float]:
    """The published peaks of one chip of ``device_kind``."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no published peaks for device kind {device_kind!r}; known: "
            f"{sorted(PEAKS)} (add the chip's peaks to PEAKS with their source)"
        ) from None


# 6·N·D with N = (active) params, D = tokens per step — per arch × shape
ARCH_PARAMS = {  # total / active parameter counts
    "phi4-mini-3.8b": (3.8e9, 3.8e9),
    "gemma2-2b": (2.6e9, 2.6e9),
    "gemma-2b": (2.5e9, 2.5e9),
    "deepseek-v2-lite-16b": (15.7e9, 2.4e9),
    "deepseek-v3-671b": (671e9, 37e9),
}


def model_flops(arch: str, shape: str, kind: str, batch: int, seq: int, n_dev: int) -> float | None:
    if arch not in ARCH_PARAMS:
        return None
    total, active = ARCH_PARAMS[arch]
    if kind == "train":
        tokens = batch * seq
        return 6.0 * active * tokens / n_dev
    if kind == "prefill":
        tokens = batch * seq
        return 2.0 * active * tokens / n_dev
    if kind == "decode":
        tokens = batch  # one new token per sequence
        return 2.0 * active * tokens / n_dev
    return None


SHAPE_DIMS = {
    "train_4k": (256, 4096, "train"),
    "prefill_32k": (32, 32768, "prefill"),
    "decode_32k": (128, 32768, "decode"),
    "long_500k": (1, 524288, "decode"),
}


def analyze(record: dict[str, Any]) -> dict[str, Any] | None:
    if record.get("status") != "ok":
        return None
    peak = peaks(record.get("device_kind", ""))
    flops = record["flops_per_device"]
    mem_bytes = record["bytes_per_device"]
    coll = sum(record["collective_bytes_per_device"].values())
    compute_s = flops / peak["bf16_flops"]
    memory_s = mem_bytes / peak["hbm_bytes_per_s"]
    collective_s = coll / peak["ici_bytes_per_s"]
    terms = {"compute": compute_s, "memory": memory_s, "collective": collective_s}
    dominant = max(terms, key=terms.get)
    out = dict(record)
    out.update(
        compute_s=compute_s,
        memory_s=memory_s,
        collective_s=collective_s,
        dominant=dominant,
        # fraction of the roofline-limited time spent in the dominant term —
        # perfect overlap would run at max(terms); serial would be sum(terms)
        roofline_s=max(terms.values()),
        balance=max(terms.values()) / max(1e-12, sum(terms.values())),
    )
    dims = SHAPE_DIMS.get(record["shape"])
    if dims and record["arch"] in ARCH_PARAMS:
        b, s, kind = dims
        mf = model_flops(record["arch"], record["shape"], kind, b, s, record["n_devices"])
        if mf:
            out["model_flops_per_device"] = mf
            out["useful_flop_frac"] = mf / max(flops, 1.0)
            out["mfu_upper_bound"] = mf / peak["bf16_flops"] / max(terms.values())
    return out


# ------------------------------------------------------ inverted-index model
# Cost model for the fused ranked-query dispatch (kernels.fused_query): the
# serving engine's RankedStats counts the packed stream bytes its ε-window
# probe lanes touch and the device array traffic of each dispatch
# (fused_stream_bytes / fused_device_bytes).  Achieved bytes/s against the
# chip's HBM roof says whether the fused path is bound by memory bandwidth
# or by dispatch/bookkeeping overhead.  The lanes' int32 VPU work has no
# published peak, so its roof is not measured.


def index_roofline(
    stream_bytes: int,
    device_bytes: int,
    lanes: int,
    seconds: float,
    queries: int,
    *,
    device_kind: str,
    kernel_seconds: float | None = None,
    bridge_seconds: float | None = None,
) -> dict[str, Any]:
    """Fused ranked dispatch accounting -> position vs the HBM-bandwidth roof.

    ``stream_bytes`` are the index bytes the dispatch's lanes read (the
    paper-facing number: what compression makes small); ``device_bytes`` the
    dispatch's array traffic (what HBM actually moves); ``lanes`` the probe
    lanes evaluated; ``seconds`` the measured wall time of the ranked pass
    serving ``queries`` queries on a chip of ``device_kind`` (``peaks``
    raises for a kind without published peaks).

    When the caller splits the wall into ``kernel_seconds`` (blocked on
    device execution) and ``bridge_seconds`` (host plan/pack/merge),
    achieved bandwidth — and with it ``fraction_of_hbm_roof`` — is computed
    against the *kernel* time, so the roof fraction measures the kernel,
    not Python; the wall-time figure stays reported as
    ``achieved_bytes_per_s_wall``.
    """
    hbm = peaks(device_kind)["hbm_bytes_per_s"]
    seconds = max(seconds, 1e-12)
    memory_s = device_bytes / hbm
    exec_s = max(kernel_seconds, 1e-12) if kernel_seconds else seconds
    achieved = device_bytes / exec_s
    out = {
        "device_kind": device_kind,
        "stream_bytes": int(stream_bytes),
        "device_bytes": int(device_bytes),
        "lanes": int(lanes),
        "seconds": seconds,
        "bytes_per_query": device_bytes / max(queries, 1),
        "hbm_roof_s": memory_s,
        "int_roof_s": "not measured",
        "achieved_bytes_per_s": achieved,
        "achieved_bytes_per_s_wall": device_bytes / seconds,
        "fraction_of_hbm_roof": achieved / hbm,
    }
    if kernel_seconds is not None:
        out["kernel_seconds"] = float(kernel_seconds)
    if bridge_seconds is not None:
        out["bridge_seconds"] = float(bridge_seconds)
    return out


def rows_from_file(path: str):
    with open(path) as f:
        records = json.load(f)
    rows = []
    for r in records:
        a = analyze(r)
        if a is None:
            rows.append((f"roofline/{r['arch']}/{r['shape']}", 0.0,
                         f"status={r['status']}"))
            continue
        extra = ""
        if "useful_flop_frac" in a:
            extra = f" useful_flops={a['useful_flop_frac']:.2f} mfu_bound={a['mfu_upper_bound']:.2f}"
        rows.append((
            f"roofline/{a['arch']}/{a['shape']}",
            a["roofline_s"] * 1e6,
            f"dominant={a['dominant']} compute_s={a['compute_s']:.4f} "
            f"memory_s={a['memory_s']:.4f} collective_s={a['collective_s']:.4f}{extra}",
        ))
    return rows
