import os
os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=512 " + os.environ.get("XLA_FLAGS", "")
)
# ^ MUST precede any jax import: jax locks the device count on first init.
# (No `from __future__` here — it must be line 1, and XLA_FLAGS must come first;
#  this module targets py3.10+ where the annotations it needs are native.)

"""Multi-pod dry-run: lower + compile every (arch × shape × mesh) cell.

For each cell:    (see DESIGN.md §7, EXPERIMENTS.md §Dry-run)
  * build the step fn + abstract inputs from launch/steps.py
  * jit with in_shardings resolved from logical axes over the target mesh
  * .lower().compile() — proves the distribution config is coherent
  * record memory_analysis() + cost_analysis() + collective byte counts
    parsed from the optimized HLO (for §Roofline)

Usage:
  python -m repro.launch.dryrun --arch gemma2-2b --shape train_4k --multi-pod
  python -m repro.launch.dryrun --all [--multi-pod] [--out results.json]
"""
import argparse
import json
import re
import sys
import time
import traceback
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.common.config import ArchConfig, ShapeSpec
from repro.common.sharding import mesh_context, sharding_for_shape
from repro.configs import ARCH_IDS, get_arch
from repro.launch.mesh import make_production_mesh
from repro.launch.steps import CellBundle, build_cell
from repro.train import init_train_state

# ------------------------------------------------------------ HLO parsing
_COLL_RE = re.compile(
    r"(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start)?(?:\.\d+)?\s*=\s*([^\s]+)"
)
_SHAPE_RE = re.compile(r"(f32|bf16|f16|s32|u32|s8|u8|pred|f64|s64|s16|u16)\[([\d,]*)\]")

_BYTES = {
    "f64": 8, "s64": 8, "f32": 4, "s32": 4, "u32": 4,
    "bf16": 2, "f16": 2, "s16": 2, "u16": 2, "s8": 1, "u8": 1, "pred": 1,
}


def _parse_result_bytes(type_str: str) -> int:
    """Sum the element bytes of an HLO result type (tuples summed)."""
    total = 0
    for dt, dims in _SHAPE_RE.findall(type_str):
        n = 1
        if dims:
            for d in dims.split(","):
                if d:
                    n *= int(d)
        total += n * _BYTES[dt]
    return total


def collective_bytes(hlo_text: str) -> dict[str, int]:
    """Per-collective-kind output bytes summed over the module."""
    out: dict[str, int] = {}
    for line in hlo_text.splitlines():
        m = _COLL_RE.search(line)
        if not m:
            continue
        kind = m.group(1)
        # result type precedes the '=': e.g.  %ag = bf16[8,128]{...} all-gather(...)
        lhs = line.split("=", 1)
        type_part = lhs[1] if len(lhs) > 1 else line
        b = _parse_result_bytes(type_part.split(m.group(1))[0])
        out[kind] = out.get(kind, 0) + b
    return out


# ------------------------------------------------------------ dry-run core
def shardings_for(tree_axes: Any, tree_specs: Any, mesh) -> Any:
    """Map (logical-axes pytree, ShapeDtypeStruct pytree) -> NamedSharding pytree.

    Divisibility-aware: mesh axes that don't divide a dim fall back to
    replicated (e.g. MQA kv_heads=1, batch=1 decode)."""
    is_ax = lambda x: (isinstance(x, tuple) and all(isinstance(a, (str, type(None))) for a in x))
    return jax.tree.map(
        lambda ax, spec: sharding_for_shape(ax, spec.shape, mesh),
        tree_axes,
        tree_specs,
        is_leaf=is_ax,
    )


def dryrun_cell(arch_id: str, shape_name: str, *, multi_pod: bool = False,
                compile_only: bool = True) -> dict[str, Any]:
    cfg, shapes, skips = get_arch(arch_id)
    if shape_name in skips:
        return {
            "arch": arch_id, "shape": shape_name, "status": "skipped",
            "reason": skips[shape_name],
        }
    shape = next(s for s in shapes if s.name == shape_name)
    mesh = make_production_mesh(multi_pod=multi_pod)
    t0 = time.time()
    cell = build_cell(cfg, shape)

    param_sh = shardings_for(cell.param_axes, cell.param_specs, mesh)
    input_sh = shardings_for(cell.input_axes, cell.input_specs, mesh)

    with mesh_context(mesh):
        if cell.kind == "train":
            opt_specs = jax.eval_shape(lambda p: init_train_state(p, cell.opt_cfg),
                                       cell.param_specs)
            opt_axes = _opt_axes_like(cell.param_axes, opt_specs)
            opt_sh = shardings_for(opt_axes, opt_specs, mesh)
            jitted = jax.jit(cell.step, in_shardings=(param_sh, opt_sh, input_sh),
                             donate_argnums=(0, 1))
            lowered = jitted.lower(cell.param_specs, opt_specs, cell.input_specs)
        elif cell.kind == "decode":
            jitted = jax.jit(
                cell.step,
                in_shardings=(param_sh, input_sh["token"], input_sh["pos"], input_sh["caches"]),
                donate_argnums=(3,),
            )
            lowered = jitted.lower(
                cell.param_specs, cell.input_specs["token"], cell.input_specs["pos"],
                cell.input_specs["caches"],
            )
        elif cell.kind == "prefill":
            jitted = jax.jit(cell.step, in_shardings=(param_sh, input_sh["tokens"]))
            lowered = jitted.lower(cell.param_specs, cell.input_specs["tokens"])
        else:  # serve / retrieval
            jitted = jax.jit(cell.step, in_shardings=(param_sh, input_sh))
            lowered = jitted.lower(cell.param_specs, cell.input_specs)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower

    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    hlo = compiled.as_text()
    colls = collective_bytes(hlo)
    n_dev = mesh.devices.size
    result = {
        "arch": arch_id,
        "shape": shape_name,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "status": "ok",
        "kind": cell.kind,
        "device_kind": mesh.devices.flat[0].device_kind,
        "n_devices": int(n_dev),
        "lower_s": round(t_lower, 1),
        "compile_s": round(t_compile, 1),
        "flops_per_device": float(cost.get("flops", 0.0)),
        "bytes_per_device": float(cost.get("bytes accessed", 0.0)),
        "collective_bytes_per_device": colls,
        "memory": {
            "argument_bytes": mem.argument_size_in_bytes,
            "output_bytes": mem.output_size_in_bytes,
            "temp_bytes": mem.temp_size_in_bytes,
            "alias_bytes": mem.alias_size_in_bytes,
            "code_bytes": mem.generated_code_size_in_bytes,
        },
    }
    print(f"[dryrun] {arch_id} × {shape_name} × {result['mesh']}: OK "
          f"(lower {t_lower:.0f}s, compile {t_compile:.0f}s, "
          f"flops/dev {result['flops_per_device']:.3g}, "
          f"temp/dev {mem.temp_size_in_bytes/2**30:.2f} GiB)")
    print(f"  memory_analysis: {mem}")
    return result


def _opt_axes_like(param_axes: Any, opt_specs: Any) -> Any:
    """Optimizer-state axes: moments inherit the param's logical axes; the
    int8 'q'/'scale' blocks are replicated (they are 1-D reshapes)."""
    is_ax = lambda x: (isinstance(x, tuple) and all(isinstance(a, (str, type(None))) for a in x))

    def like(ax, spec):
        if isinstance(spec, dict) and "q" in spec:  # quantized moment mirrors
            # the PARAM's sharding exactly (q is param-shaped; scale drops the
            # last axis) — anything else forces involuntary resharding in the
            # Adam update (EXPERIMENTS.md §Perf iter 1).
            return {"q": ax, "scale": tuple(ax[:-1]) + (None,)}
        return ax

    from repro.train.optimizer import AdamState
    m_axes = jax.tree.map(like, param_axes,
                          opt_specs.m, is_leaf=lambda x: is_ax(x) or (isinstance(x, dict) and "q" in x))
    v_axes = jax.tree.map(like, param_axes,
                          opt_specs.v, is_leaf=lambda x: is_ax(x) or (isinstance(x, dict) and "q" in x))
    return AdamState(step=(), m=m_axes, v=v_axes)


def run_all(arch_ids, *, multi_pod: bool, out_path: str | None) -> list[dict]:
    results = []
    for arch_id in arch_ids:
        _, shapes, _ = get_arch(arch_id)
        for shape in shapes:
            try:
                results.append(dryrun_cell(arch_id, shape.name, multi_pod=multi_pod))
            except Exception as e:  # a failing cell is a bug — surface it loudly
                traceback.print_exc()
                results.append({
                    "arch": arch_id, "shape": shape.name,
                    "mesh": "2x16x16" if multi_pod else "16x16",
                    "status": "error", "error": f"{type(e).__name__}: {e}",
                })
            if out_path:
                with open(out_path, "w") as f:
                    json.dump(results, f, indent=1)
    n_ok = sum(r["status"] == "ok" for r in results)
    n_skip = sum(r["status"] == "skipped" for r in results)
    n_err = sum(r["status"] == "error" for r in results)
    print(f"\n[dryrun] {n_ok} ok / {n_skip} documented skips / {n_err} errors")
    return results


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--out", default=None)
    args = ap.parse_args()
    if args.all:
        results = run_all(ARCH_IDS, multi_pod=args.multi_pod, out_path=args.out)
        sys.exit(1 if any(r["status"] == "error" for r in results) else 0)
    res = dryrun_cell(args.arch, args.shape, multi_pod=args.multi_pod)
    print(json.dumps(res, indent=1))
    sys.exit(1 if res["status"] == "error" else 0)


if __name__ == "__main__":
    main()
