"""The program's own spans in one profiler trace, and the device idle under them.

    python3 bench/program_spans.py --workload <cell> --seed <n> --seconds <s>
    python3 bench/program_spans.py --xplane <trace.xplane.pb[.gz]> [--workload <cell>]

``repro.obs`` with its profiler sink (``enable_tracing(profiler=True)``)
enters one ``jax.profiler.TraceAnnotation`` per span: in a ``.xplane.pb``
they lie on the ``/host:CPU`` plane, names starting ``fleet/``, their args as
event stats, on the clock of the device's ``XLA Ops`` events.  With the
benchmark's spans and the device ops that ``trace_reduce.collect`` gives:

* per program span name: its count, total and self seconds (self = the
  duration less the time its child spans cover), and the device-idle
  seconds in its self time: each idle nanosecond goes to the innermost span
  open over it;
* per benchmark span, its device-idle seconds that lie under some program
  span;
* the idle gaps, labelled ``<bench span>><program span>`` (e.g.
  ``pump>fleet/state_write``) where one program span holds the gap's largest
  share, else as ``trace_reduce`` labels them;
* the ``(occupied, t_step)`` of each engine call from ``fleet/dispatch``'s
  args, and the work ``bench/work.py`` counts for them.

With ``--workload`` and no ``--xplane`` the script makes one traced run of
the cell through ``run.run_cell`` with the program's spans on for the whole
run (the profiler keeps those of the traced stretch), keeps the trace
(``--keep``), and reduces it.  That needs the cell's TPUs, as ``bench/run.py``
does; the harness's own log line ``traced N engine steps: X operations``
gives ``bench/work.py``'s count of the loop's steps beside the count from
the dispatch args logged here.  With ``--xplane`` it reduces a kept trace on
any platform.  The log goes to standard error; the last line of standard
output is one JSON object.
"""

from __future__ import annotations

import argparse
import bisect
import collections
import gzip
import json
import pathlib
import shutil
import sys
import tempfile

ROOT = pathlib.Path(__file__).resolve().parents[1]
for p in (ROOT / "src", ROOT):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

from bench import trace_reduce as tr  # noqa: E402

PROGRAM_PREFIX = "fleet/"


def log(msg: str) -> None:
    print(f"[spans] {msg}", file=sys.stderr, flush=True)


def load(path: str):
    """A ``ProfileData`` from a ``.xplane.pb``, gzipped or not."""
    from jax.profiler import ProfileData

    raw = pathlib.Path(path).read_bytes()
    if path.endswith(".gz"):
        raw = gzip.decompress(raw)
    return ProfileData.from_serialized_xspace(raw)


def collect(profile):
    """The program's spans on the host plane: ``[(name, start_ns, end_ns,
    stats)]``, ``stats`` the span's args as a dict."""
    spans = []
    for plane in profile.planes:
        if plane.name == tr.HOST_PLANE:
            for line in plane.lines:
                spans.extend((e.name, e.start_ns, e.end_ns, dict(e.stats))
                             for e in line.events
                             if e.name.startswith(PROGRAM_PREFIX))
    return spans


def dispatches(program) -> list[tuple[int, int]]:
    """``(occupied, t_step)`` of each engine call, from the args of the
    program's ``fleet/dispatch`` spans, in order."""
    return [(int(a["occupied"]), int(a["t_step"]))
            for n, _, _, a in sorted(program, key=lambda x: x[1])
            if n == "fleet/dispatch"]


def innermost(spans):
    """Disjoint ``[(start, end, name, root)]``, sorted: every instant under
    some span, given to the innermost span open then, and ``root``, the
    outermost one.  ``spans`` nest (``[(name, start, end)]``); a child that
    outlasts its parent is cut at the parent's end."""
    out = []
    stack = []                             # [(name, end)], outermost first
    t = 0

    def close(until):
        nonlocal t
        while stack and stack[-1][1] <= until:
            name, end = stack.pop()
            if end > t:
                out.append((t, end, name, stack[0][0] if stack else name))
                t = end

    for name, s, e in sorted(spans, key=lambda x: (x[1], -x[2])):
        close(s)
        if stack:
            if s > t:
                out.append((t, s, stack[-1][0], stack[0][0]))
            e = min(e, stack[-1][1])
        t = s
        stack.append((name, e))
    close(float("inf"))
    return out


def reduce(spans, devices, program, n_gaps: int = 10) -> dict:
    """``spans`` and ``devices`` as ``trace_reduce.collect`` gives them,
    ``program`` as ``collect`` does; see the module docstring.  The window
    and the busy union are ``trace_reduce.reduce``'s."""
    if not spans:
        raise ValueError("the trace holds no benchmark span")
    if not devices:
        raise ValueError("the trace holds no device plane")
    w0 = min(s for _, s, _ in spans)
    w1 = max(e for _, _, e in spans)
    leaf = sorted(spans, key=lambda x: x[1])
    n_dev = len(devices)
    names = {n for n, _, _, _ in program}
    pieces = innermost(spans + [(n, s, e) for n, s, e, _ in program])
    piece_ends = [e for _, e, _, _ in pieces]
    idle_inner = collections.Counter()     # innermost span -> idle ns
    idle_program = collections.Counter()   # outermost span -> idle ns in it
                                           # under some program span
    gaps = []
    for ops in devices.values():
        merged = tr.union((max(s, w0), min(e, w1)) for _, s, e in ops
                          if e > w0 and s < w1)
        edges = [w0] + [x for se in merged for x in se] + [w1]
        for g0, g1 in zip(edges[::2], edges[1::2]):
            if g1 <= g0:
                continue
            share = collections.Counter()
            for name, s, e in leaf:
                if s >= g1:
                    break
                share[name] += tr.overlap(g0, g1, s, e)
            share["other"] = (g1 - g0) - sum(share.values())
            top = max(share.items(), key=lambda kv: kv[1])[0]
            inner = collections.Counter()
            k = bisect.bisect_right(piece_ends, g0)
            while k < len(pieces) and pieces[k][0] < g1:
                p0, p1, name, root = pieces[k]
                k += 1
                o = tr.overlap(g0, g1, p0, p1)
                inner[name] += o
                if name in names:
                    idle_program[root] += o
            idle_inner.update(inner)
            inner["other"] = (g1 - g0) - sum(inner.values())
            name = max(inner.items(), key=lambda kv: kv[1])[0]
            if name in names:
                top = f"{top}>{name}"
            gaps.append(((g1 - g0) * 1e-9 / n_dev, top))
    gaps.sort(reverse=True)
    table = {n: {"count": 0, "total_s": 0.0, "self_s": 0.0,
                 "idle_s": idle_inner[n] * 1e-9 / n_dev} for n in sorted(names)}
    for n, s, e, _ in program:
        table[n]["count"] += 1
        table[n]["total_s"] += (e - s) * 1e-9
    for p0, p1, name, _ in pieces:
        if name in table:
            table[name]["self_s"] += (p1 - p0) * 1e-9
    return {
        "spans": table,
        "idle_s_under_program": {k: v * 1e-9 / n_dev
                                 for k, v in idle_program.items() if v > 0},
        "idle_gaps": [[name, s] for s, name in gaps[:n_gaps]],
    }


def summary(table: dict) -> dict:
    """Per-stream and per-step host times from the span table, each where its
    spans are there: admission per admitted stream (``fleet/submit`` over the
    count of ``fleet/state_write``, one per stream given a slot), the state
    write per stream, the whole engine step, and the wait on the device per
    step."""
    out = {}
    submit, write = table.get("fleet/submit"), table.get("fleet/state_write")
    step, wait = table.get("fleet/step"), table.get("fleet/wait")
    if submit and write:
        out["submit_us_per_stream"] = submit["total_s"] * 1e6 / write["count"]
    if write:
        out["state_write_us_per_stream"] = write["total_s"] * 1e6 / write["count"]
    if step:
        out["step_ms"] = step["total_s"] * 1e3 / step["count"]
    if step and wait:
        out["wait_ms_per_step"] = wait["total_s"] * 1e3 / step["count"]
    return out


def traced_run(parts: dict, seed: int, seconds: float, keep: str) -> dict:
    """One traced run of the cell with the program's spans on; the raw trace
    is copied to ``keep``."""
    from bench import run
    from repro import obs
    from repro.compile_cache import enable_compile_cache

    devices = run.require_chips(int(parts["cell"]["chips"]))
    log(f"{parts['cell']['name']} on {len(devices)} x "
        f"{devices[0].device_kind}, compile cache {enable_compile_cache()}")
    obs.enable_tracing(profiler=True)
    try:
        return run.run_cell(parts, seed, seconds, True, devices, keep_trace=keep)
    finally:
        obs.disable_tracing()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", help="the cell: run it, or count the work "
                    "of --xplane's dispatches with its configuration")
    ap.add_argument("--seed", type=int)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--xplane", help="reduce this kept trace instead of a run")
    ap.add_argument("--keep", help="copy the run's raw trace here")
    args = ap.parse_args(argv)
    if args.xplane is None and (args.workload is None or args.seed is None):
        ap.error("give --xplane, or --workload and --seed")
    from bench import run, work

    parts = (run.cell_parts(run.load_spec(), args.workload)
             if args.workload else None)
    out = {}
    path = args.xplane
    if path is None:
        tmp = tempfile.mkdtemp(prefix="program_spans_")
        path = args.keep or str(pathlib.Path(tmp) / "trace.xplane.pb")
        out["result"] = traced_run(parts, args.seed, args.seconds, path)
    profile = load(path)
    if args.xplane is None:
        shutil.rmtree(tmp, ignore_errors=True)
    spans, devices = tr.collect(profile)
    program = collect(profile)
    if not program:
        log("no program span in the trace")
    red = reduce(spans, devices, program)
    bench_idle = tr.reduce(spans, devices)["idle_s_by_span"]
    log("program spans: count, total s, self s, device-idle s in self")
    for name, r in red["spans"].items():
        log(f"  {name}: {r['count']} {r['total_s']:.6f} {r['self_s']:.6f} "
            f"{r['idle_s']:.6f}")
    calls = dispatches(program)
    out["dispatch"] = {"calls": len(calls),
                       "slot_timesteps": sum(o * t for o, t in calls)}
    if parts is not None:
        out["dispatch"]["operations"] = sum(
            work.call_work(parts["config"], o, t)[0] for o, t in calls)
    log(f"work from {len(calls)} fleet/dispatch spans: {out['dispatch']}")
    out["idle_s_by_span"] = bench_idle
    out["idle_s_under_program"] = red["idle_s_under_program"]
    for name, idle in bench_idle.items():
        under = red["idle_s_under_program"].get(name, 0.0)
        if idle > 0 and name != "other":
            log(f"program spans hold {under:.6f} s of the {idle:.6f} s of "
                f"device idle in bench.{name} ({100.0 * under / idle:.3f}%)")
    log(f"longest idle gaps: {red['idle_gaps']}")
    out.update(spans=red["spans"], summary=summary(red["spans"]),
               idle_gaps=red["idle_gaps"])
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
