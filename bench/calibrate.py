"""Readings behind the limits of ``correct``, for one cell, in one process.

    python bench/calibrate.py --workload <cell> --seeds 1,2,3 --seconds <s>

For each seed it makes one run of the cell at its own size and load (as
``bench/run.py`` does, without the profiler) and prints the numbers compared
twice: the program against the reference (the lower reading) and the
control, the reference in bfloat16 put in the program's place, against the
reference (the upper reading).  One JSON line per seed, then a summary.
"""

from __future__ import annotations

import argparse
import json
import sys
import time

from run import cell_parts, load_spec, require_chips, run_cell


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated")
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    parts = cell_parts(load_spec(), args.workload)
    devices = require_chips(int(parts["cell"]["chips"]))
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    lower, upper = {}, {}
    for seed in (int(s) for s in args.seeds.split(",")):
        res = run_cell(parts, seed, args.seconds, False, devices,
                       t_process=time.perf_counter(), control=True)
        line = {"seed": seed, "correct": res["correct"],
                "program": {k: c["value"] for k, c in res["checks"].items()},
                "control": {k: c["value"] for k, c in res["control_checks"].items()}}
        print(json.dumps(line), flush=True)
        for k, v in line["program"].items():
            lower[k] = max(lower.get(k, v), v)
        for k, v in line["control"].items():
            upper[k] = min(upper.get(k, v), v)
    print(json.dumps({"workload": args.workload, "lower_max": lower,
                      "control_min": upper}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
