"""Benchmark-regression gate: fresh BENCH_*.json vs committed baselines.

Compares the metrics that matter per benchmark file and fails (exit 1) when
any regresses beyond the tolerance:

  BENCH_learned_postings.json   bits_per_posting per codec    (lower is better)
  BENCH_guided_intersect.json   bytes_ratio, latency_ratio    (lower is better)
  BENCH_sharded_serve.json      latency_ratio (best sharded vs K=1, machine-
                                normalized within one run; lower is better)
  BENCH_ranked_topk.json        scored_fraction (postings MaxScore touches vs
                                exhaustive; deterministic), latency_ratio
                                (pruned vs exhaustive top-k, same run),
                                fused.latency_ratio (fused dispatch vs the
                                kernel multi-phase pipeline, same run)
  BENCH_serve_latency.json      trace_overhead_ratio (traced vs untraced
                                closed-loop service time through the sched/
                                process-replica path — TraceContext IPC,
                                span shipping and collation included),
                                latency_ratio (open-loop p99/p50 tail
                                amplification under Poisson arrivals)
  BENCH_serve_sustained.json    qps_ratio (serial fan-out vs the continuous-
                                batching scheduler, same run), overload
                                p99_over_deadline (admitted tail vs the
                                deadline budget under 4x overload)
  BENCH_dispatch_overhead.json  host_us_per_dispatch (host-bridge µs per
                                fused dispatch), bridge_over_kernel (host
                                bridge / device-blocked time, same run —
                                the bridge regrowing past the kernel is
                                the regression the arena work removed)

Storage/bytes metrics are deterministic (seeded corpora), so any movement is
a real code change.  The latency metric is the guided/full *ratio* measured
from interleaved repeats within one run, so it is machine-normalized; it
gets the same 15% tolerance plus an absolute floor (a shared CI runner's
microarchitecture can legitimately shift the ratio a little, but guided
falling to less than 2x the speed of full decode fails anywhere).
Absolute ns_per_probe/qps numbers are informational only — they are not
comparable across machines and are not gated.

Usage:
  python benchmarks/check_regression.py --baseline-dir . --fresh-dir fresh/
"""
from __future__ import annotations

import argparse
import json
import os
import sys

TOLERANCE = 0.15  # >15% worse than baseline fails

# (file, dotted-path of a lower-is-better metric, absolute floor the limit
# is never taken below — nonzero only for wall-clock-derived metrics)
METRICS = [
    ("BENCH_learned_postings.json", "codecs.hybrid.bits_per_posting", 0.0),
    ("BENCH_learned_postings.json", "codecs.plm.bits_per_posting", 0.0),
    ("BENCH_learned_postings.json", "codecs.rmi.bits_per_posting", 0.0),
    ("BENCH_learned_postings.json", "codecs.clustered/plm.bits_per_posting", 0.0),
    ("BENCH_guided_intersect.json", "bytes_ratio", 0.0),
    ("BENCH_guided_intersect.json", "store.bits_per_posting", 0.0),
    ("BENCH_guided_intersect.json", "latency_ratio", 0.5),
    # shard fan-out overhead (threads, planning, bitmap merge) relative to
    # the K=1 engine on the same run; the floor absorbs CI-runner thread
    # scheduling noise, but a sharded engine >2x slower fails anywhere
    ("BENCH_sharded_serve.json", "latency_ratio", 2.0),
    # MaxScore work-skipping: deterministic (seeded corpus), must stay well
    # under the exhaustive scorer's postings count
    ("BENCH_ranked_topk.json", "scored_fraction", 0.0),
    # pruned vs exhaustive top-k wall clock within one run; the floor absorbs
    # scheduling noise, but pruning >1.2x slower than brute force fails
    ("BENCH_ranked_topk.json", "latency_ratio", 1.2),
    # fused one-dispatch-per-bucket kernel vs the kernel-enabled multi-phase
    # pipeline, same run (machine-normalized); the floor is the acceptance
    # bar — the fused path must beat the many-dispatch pipeline anywhere
    ("BENCH_ranked_topk.json", "fused.latency_ratio", 1.0),
    # fused one-dispatch path vs the all-numpy host multi-phase engine, same
    # run; with the device-resident arena the single dispatch must beat the
    # host outright — not just cut the dispatch count
    ("BENCH_ranked_topk.json", "fused.latency_ratio_host", 1.0),
    # span tracer on vs off, interleaved passes within one run; the floor is
    # the design budget — tracing a served batch must stay within ~5%
    ("BENCH_serve_latency.json", "trace_overhead_ratio", 1.05),
    # open-loop p99/p50 under Poisson arrivals at fixed utilization; queueing
    # tails are noisy on shared runners, so the floor is generous — but a
    # tail blowing past 25x the median signals real head-of-line blocking
    ("BENCH_serve_latency.json", "latency_ratio", 25.0),
    # serial fan-out qps / scheduler qps within one run (machine-normalized);
    # the floor is the acceptance bar — the process-replica scheduler must at
    # least match serial serving at K shards on any machine
    ("BENCH_serve_sustained.json", "summary.qps_ratio", 1.0),
    # admitted p99 / deadline under 4x-capacity overload: deadline shedding
    # must keep the admitted tail within 2x the budget (shed, don't convoy)
    ("BENCH_serve_sustained.json", "overload.p99_over_deadline", 2.0),
    # host-bridge µs per fused dispatch (plan/pad/group/extract around the
    # device call); wall-clock, so the floor is generous — but the bridge
    # regrowing to several ms per dispatch fails anywhere
    ("BENCH_dispatch_overhead.json", "host_us_per_dispatch", 6000.0),
    # host bridge / device-blocked kernel time within one run (machine-
    # normalized); the floor is the acceptance bar — host work must stay
    # cheaper than the device execution it overlaps
    ("BENCH_dispatch_overhead.json", "bridge_over_kernel", 1.0),
]

def _lookup(obj, dotted: str):
    for part in dotted.split("."):
        if not isinstance(obj, dict) or part not in obj:
            return None
        obj = obj[part]
    return obj


def check(baseline_dir: str, fresh_dir: str, tolerance: float = TOLERANCE) -> list[str]:
    failures = []
    cache: dict[str, dict | None] = {}

    def load(d: str, name: str):
        path = os.path.join(d, name)
        if path not in cache:
            try:
                with open(path) as f:
                    cache[path] = json.load(f)
            except FileNotFoundError:
                cache[path] = None
        return cache[path]

    for fname, metric, floor in METRICS:
        base, fresh = load(baseline_dir, fname), load(fresh_dir, fname)
        if base is None:
            print(f"SKIP {fname}:{metric} — no committed baseline")
            continue
        if fresh is None:
            failures.append(f"{fname} missing from fresh results")
            continue
        b, f = _lookup(base, metric), _lookup(fresh, metric)
        if b is None:
            print(f"SKIP {fname}:{metric} — metric absent in baseline")
            continue
        if f is None:
            failures.append(f"{fname}:{metric} absent in fresh results")
            continue
        limit = max(b * (1 + tolerance), floor)
        verdict = "FAIL" if f > limit else "ok"
        print(f"{verdict:4s} {fname}:{metric}  baseline={b:.4f}  fresh={f:.4f}  limit={limit:.4f}")
        if f > limit:
            failures.append(f"{fname}:{metric} regressed {f:.4f} > {limit:.4f} (baseline {b:.4f})")

    return failures


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--baseline-dir", default=".", help="dir with committed BENCH_*.json")
    ap.add_argument("--fresh-dir", required=True, help="dir with freshly generated BENCH_*.json")
    ap.add_argument("--tolerance", type=float, default=TOLERANCE)
    args = ap.parse_args()
    failures = check(args.baseline_dir, args.fresh_dir, args.tolerance)
    if failures:
        print("\nbenchmark regression gate FAILED:")
        for f in failures:
            print(f"  - {f}")
        return 1
    print("\nbenchmark regression gate passed")
    return 0


if __name__ == "__main__":
    sys.exit(main())
