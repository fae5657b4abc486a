"""Find an open-loop cell's knee: the highest offered rate it sustains.

    python3 bench/sweep.py --workload <open-loop cell> --rates 8,12,16 --seconds 15

Runs the cell once per rate in one process, exactly as ``run.py`` does
but with the traffic file's rate replaced, and prints one JSON line per
rate: requests due and the end-to-end metrics (each run's standard error
says how many requests still had no first token when the window closed).
Past the knee the queue grows all through the window, so the time to
first token climbs with the window's length and requests are still
waiting at its end.  The cell's rate is then set, once, in its traffic
file; the benchmark never searches.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def main(argv=None):
    from bench import run as R
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    manifest = R.load_json(ROOT / "BENCHMARK.json")
    cell = R.cell_of(manifest, args.workload)
    config = R.load_json(R.BENCH / "configs" / f"{cell['config']}.json")
    traffic = R.load_json(R.BENCH / "traffic" / f"{cell['traffic']}.json")
    for rate in (float(r) for r in args.rates.split(",")):
        result, _ = R.run_cell(manifest, cell, config,
                               dict(traffic, rate_per_s=rate), args.seed,
                               args.seconds, False, time.perf_counter())
        print(json.dumps({"rate_per_s": rate, "due": result["attempted"],
                          "correct": result["correct"],
                          "metrics": {k: v["value"] for k, v in
                                      result["metrics"].items()}}),
              flush=True)


if __name__ == "__main__":
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    main()
