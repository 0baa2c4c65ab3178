"""The program's readings and its control's, seed by seed, in one process.

    python3 bench/control.py --workload <cell> --seeds 11,12,13 --seconds 51

For each seed the configuration is set up once, as ``run.py`` sets it up;
the cell's window is served by the program as configured and then, over the
same built parts, by the configuration's control (``system.assemble`` with
the ``control`` overrides of the configuration file: a program path that
breaks a guarantee the configuration states).  Each window is warmed as a
run warms it and compared with the plain reference, every answer due in it.
One JSON line per seed; the last line gives the lower reading (the largest
count of wrong or missing answers over the program's seeds) and the upper
reading (the smallest over the control's), from which the limits are set.
Needs a TPU, as a run does.
"""
from __future__ import annotations

import argparse
import json
import sys

import run  # puts src/ and bench/ on sys.path


def readings(cell, seed: int, seconds: float) -> dict:
    import compiles
    import system
    import traffic

    conf, mix = cell.config, cell.traffic
    meter = compiles.CompileMeter()
    inp = run.inputs(cell, seed, seconds)
    out = {"seed": seed}
    engine, _, parts = system.build(conf, inp.col, log=run.log)
    for side in ("program", "control"):
        if side == "control":
            engine = system.assemble(conf, parts, overrides=conf["control"][mix["mode"]])
        sv = run.serve(engine, inp, seconds, traffic.max_terms(mix), meter=meter)
        del engine
        _, wrong, missing = run.check(cell, inp, sv.win)
        out[side] = {"wrong_answers": wrong, "missing_answers": missing,
                     "compared": int(sv.win.answered.sum()),
                     "compilations_in_window": sv.compiles["lowered"]}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated")
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)

    import spec

    cell = spec.cell(args.workload)
    if cell.rate_qps is None or run.chip(cell.chips) is None:
        run.log(f"needs a fixed rate for {cell.name} and a TPU with {cell.chips} chip(s)")
        return 2
    run.configure_compile_cache()
    rows = []
    for seed in (int(s) for s in args.seeds.split(",")):
        rows.append(readings(cell, seed, args.seconds))
        print(json.dumps(rows[-1]), flush=True)

    def bad(r):
        return r["wrong_answers"] + r["missing_answers"]

    print(json.dumps({"cell": cell.name, "seeds": len(rows),
                      "lower": max(bad(r["program"]) for r in rows),
                      "upper": min(bad(r["control"]) for r in rows)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
