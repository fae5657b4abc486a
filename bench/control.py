"""Readings that the limits of ``correct`` are set from.

    python3 bench/control.py --workload <cell> --seeds 1,2,3 --seconds <s>
    python3 bench/control.py --workload <cell> --seeds 1,2,3 --seconds <s> \\
        --modes "" --fault stale_pool

Runs the cell once per seed in one process, exactly as ``run.py`` does,
and reads besides the program's logit gaps the control's (the reference
computed in float8, ``reference.py``) at the same positions; ``--modes
fp8,f32`` adds the float32 witness.  The control is judged by the cell's
own limits through the same ``check.verdict`` as the program, so each line
says whether the program and the control pass.  ``--fault`` plants one of
``faults.py``'s faults under the timed path first.  One JSON line per
seed.  The benchmark's own runs never run the control.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def main(argv=None):
    from bench import check, faults
    from bench import run as R
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--modes", default="fp8")
    ap.add_argument("--fault", choices=faults.NAMES)
    args = ap.parse_args(argv)
    manifest = R.load_json(ROOT / "BENCHMARK.json")
    cell = R.cell_of(manifest, args.workload)
    config = R.load_json(R.BENCH / "configs" / f"{cell['config']}.json")
    traffic = R.load_json(R.BENCH / "traffic" / f"{cell['traffic']}.json")
    limits = traffic["check"]["limits"]
    modes = tuple(m for m in args.modes.split(",") if m)
    if args.fault:
        faults.plant(args.fault)
    for seed in (int(s) for s in args.seeds.split(",")):
        result, checks = R.run_cell(manifest, cell, config, traffic, seed,
                                    args.seconds, False, time.perf_counter(),
                                    modes=modes)
        line = {"seed": seed, "fault": args.fault,
                "correct": result["correct"], "checks": checks,
                "metrics": result["metrics"],
                "readings": result["readings"]}
        for m in modes:
            c = check.verdict(result["readings"], limits, f"{m}_")
            line[f"{m}_checks"], line[f"{m}_correct"] = c, check.passed(c)
        print(json.dumps(line), flush=True)


if __name__ == "__main__":
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    main()
