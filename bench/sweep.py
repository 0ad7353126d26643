"""Find the highest arrival rate an open-loop cell sustains, by a sweep.

    python bench/sweep.py --workload <cell> --seed <n> --seconds <s> --rates 150,200,250

For each rate it makes one run of the cell with that ``rate_per_s`` (as
``bench/run.py`` does, without the profiler) and prints the rate offered,
the rate completed within the window, the latency median and 99th
percentile, and how many requests due in the window were still unanswered
when the drain ended.  A rate is sustained while the completed rate keeps up
with the offered one and the tail does not grow with the run.
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
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True, help="comma-separated, per second")
    args = ap.parse_args(argv)
    parts = cell_parts(load_spec(), args.workload)
    devices = require_chips(int(parts["cell"]["chips"]))
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    for rate in (float(r) for r in args.rates.split(",")):
        t = time.perf_counter()
        res = run_cell(parts, args.seed, args.seconds, False, devices,
                       sizes={"traffic": {"rate_per_s": rate}}, t_process=t)
        m = {k: v["value"] for k, v in res["metrics"].items()}
        print(json.dumps({"rate_per_s": rate, "attempted": res["attempted"],
                          "failed": res["failed"], "correct": res["correct"],
                          "wall_s": time.perf_counter() - t, **res["window"], **m}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
