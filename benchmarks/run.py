"""Benchmark harness: one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV (plus a human table to stderr).

``--only TAG`` runs a single module (e.g. ``--only kernels``); ``--json PATH``
merges this run's rows into a JSON perf trajectory (a list of runs, newest
last) so regressions are diffable across PRs:

    PYTHONPATH=src:. python benchmarks/run.py --only kernels --json BENCH_kernels.json

The trajectory is append-only: prior entries are never dropped, and an
unreadable/clobbered file is preserved as ``<PATH>.bak`` rather than being
overwritten (``load_trajectory`` / ``append_run``; tested in
``tests/test_bench_json.py``).
"""

import argparse
import json
import os
import platform
import sys
import time


def bench_env() -> dict:
    """Per-run environment metadata stored with each trajectory entry, so a
    perf regression can be attributed (new jax? different backend? interpret
    mode?) before anyone stares at numbers.  jax imports lazily: loading the
    trajectory tooling must not drag in the accelerator stack."""
    env = {
        "host": platform.node(),
        "platform": platform.platform(),
        "python": platform.python_version(),
    }
    try:
        import jax
        env["jax"] = jax.__version__
        env["backend"] = jax.default_backend()
        env["device_count"] = jax.device_count()
    except Exception:  # pragma: no cover - jax always present in this image
        env["jax"] = None
    # Pallas kernels auto-select interpret mode off-TPU (see repro.core.lstm);
    # record the effective mode so compiled vs interpret rows never mix.
    env["pallas_interpret"] = env.get("backend") not in ("tpu",)
    return env


def load_trajectory(path: str) -> list:
    """Read an existing perf trajectory, never losing data.

    Returns the list of prior runs.  A missing file yields ``[]``; an
    unreadable or non-list file is moved aside to ``<path>.bak[N]`` (instead
    of being silently overwritten on the next save) and ``[]`` is returned.
    """
    if not os.path.exists(path):
        return []
    try:
        with open(path) as f:
            history = json.load(f)
        if isinstance(history, list):
            return history
        reason = f"non-list JSON ({type(history).__name__})"
    except (json.JSONDecodeError, OSError) as e:
        reason = str(e)
    bak = f"{path}.bak"
    n = 0
    while os.path.exists(bak):
        n += 1
        bak = f"{path}.bak{n}"
    os.replace(path, bak)
    print(f"[bench] {path} was {reason}; preserved as {bak}", file=sys.stderr)
    return []


def append_run(path: str, rows: list, only: str | None = None,
               now: str | None = None, env: dict | None = None) -> int:
    """Merge this run into the trajectory at ``path`` (append-only history).

    Prior entries are always kept — corrupt files are backed up by
    ``load_trajectory`` rather than clobbered — and the write is
    temp-file + rename so an interrupted run can't truncate the history.
    ``env`` (see ``bench_env``) is stored alongside the rows; older entries
    without it stay valid.  Returns the new number of runs in the trajectory.
    """
    history = load_trajectory(path)
    entry = {
        "time": now or time.strftime("%Y-%m-%dT%H:%M:%S"),
        "only": only,
        "rows": rows,
    }
    if env is not None:
        entry["env"] = env
    history.append(entry)
    tmp = f"{path}.tmp"
    with open(tmp, "w") as f:
        json.dump(history, f, indent=1)
    os.replace(tmp, path)
    return len(history)


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None, help="run a single module by tag")
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="append rows to a JSON perf-trajectory file")
    args = ap.parse_args(argv)

    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    from benchmarks import (churn, fig6_frac_bits, fig35_breakdown,
                            kernel_bench, roofline_report, table1_lut_depth,
                            table2_resources, table3_throughput)

    modules = [
        ("table1", table1_lut_depth),
        ("fig6", fig6_frac_bits),
        ("table2", table2_resources),
        ("table3", table3_throughput),
        ("fig35", fig35_breakdown),
        ("kernels", kernel_bench),
        ("churn", churn),
        ("roofline", roofline_report),
    ]
    if args.only is not None:
        modules = [(tag, mod) for tag, mod in modules if tag == args.only]
        if not modules:
            sys.exit(f"unknown --only tag {args.only!r}")

    print("name,us_per_call,derived")
    all_rows = []
    failures = 0
    for tag, mod in modules:
        try:
            for row in mod.run():
                derived = str(row["derived"]).replace(",", ";")
                print(f"{row['name']},{row['us_per_call']},{derived}")
                entry = {"name": row["name"],
                         "us_per_call": row["us_per_call"],
                         "derived": derived}
                # dispersion fields from timeit_stats rows, when present
                for k in ("p50_us", "p95_us", "p99_us", "cv", "n"):
                    if k in row:
                        entry[k] = row[k]
                all_rows.append(entry)
        except Exception as e:  # keep the harness running
            failures += 1
            print(f"{tag}/ERROR,0,{type(e).__name__}: {str(e)[:120]}".replace(",", ";"))
            print(f"[bench] {tag} failed: {e}", file=sys.stderr)

    if args.json:
        n_runs = append_run(args.json, all_rows, only=args.only,
                            env=bench_env())
        print(f"[bench] appended {len(all_rows)} rows to {args.json} "
              f"({n_runs} runs in trajectory)", file=sys.stderr)

    if failures:
        sys.exit(1)


if __name__ == "__main__":
    main()
