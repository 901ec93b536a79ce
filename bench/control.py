"""Readings that set a cell's limits: the program's and the control's.

    python3 bench/control.py --workload <cell> --seeds 11,12,13 --seconds 50

For each seed, in one process: a run of the cell (set-up, a window of
``--seconds`` at the cell's own load, the seeded sample of finished
requests), then the harness's own comparison (``check.compare``) with the
cell's limits file twice:

* the program: its served tokens against the reference, as every run of
  the benchmark judges them;
* the control: the reference put in the program's place one precision
  below what the configuration states (float32 -> bfloat16); at each of
  the same positions the token its bfloat16 pass puts first is judged
  instead of the served one.  Its ``correct`` has to come out false.

Prints one JSON line per seed (each compared number with its limit, and
``correct``, for both) and a summary.  The benchmark's own runs never run
this.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

import numpy as np

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent))

import run  # noqa: E402


def readings(cell, rec, noise_seed: int) -> dict:
    out = {"seed": rec.seed, "window_compiles": rec.window_compiles,
           "setup_s": rec.setup_s}
    for side in ("program", "control"):
        t0 = time.monotonic()
        checks, judged, served = run.check.judge(
            cell, rec, noise_seed, control=side == "control")
        out[side] = {"correct": run.check.correct(checks),
                     "checks": checks,
                     # share of judged tokens that are not the served ones
                     "changed": float(np.mean(judged != served)),
                     "tokens": int(len(served)),
                     "seconds": time.monotonic() - t0}
    return out


def main():
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args()
    bench = run.load_json(run.ROOT / "BENCHMARK.json")
    cell = run.cell_of(bench, args.workload)
    device = run.require_chips(cell.chips)
    run.enable_compile_cache()
    clock = run.CompileClock()
    out = []
    for s in args.seeds.split(","):
        rec = run.measure(cell, int(s), args.seconds, False, device,
                          clock=clock, t0=time.monotonic())
        r = readings(cell, rec, run.noise_seed(int(s)))
        print(json.dumps(r), flush=True)
        out.append(r)
        del rec
    summary = {side: {name: [r[side]["checks"][name]["value"] for r in out]
                      for name in out[0][side]["checks"]}
               for side in ("program", "control")}
    print(json.dumps({"summary": summary}), flush=True)


if __name__ == "__main__":
    main()
