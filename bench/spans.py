"""The program's own spans for the per-layer readers, and a traced run
that records them.

The program annotates its hot paths with ``tao/<layer>.<part>`` spans
(``src/repro/spans.py``), each with ``call``, the simulate or transfer call
it belongs to.  ``program(t)`` gives them for a ``TraceView`` as
``(start_ns, end_ns, name, args)``, sorted: from ``t.program`` where a
recorded trace set it, else from the run's profiler trace, which is still
on disk while the readers run.  A program without such spans gives none,
and every reader below then returns None.

    python bench/spans.py --workload <cell> --seed <n> [--record PATH] [--whole-ms MS]

makes one traced run of the cell, as ``bench/run.py --trace 1`` does,
prints on an earlier line how much of the chip's idle time inside the
benchmark's request spans lies inside the program's spans below each call
(``# spans: ...``) and, last, the result line.  ``--record`` writes a
recorded trace with the program spans for ``bench/tests``: the shortest
whole call of at most ``--whole-ms`` milliseconds, with a millisecond
either side, or else 60 ms from the middle of the window's first call.
"""
from __future__ import annotations

import bisect
import glob
import json
import os
import sys
import time
from typing import Dict, List, Optional

if __name__ == "__main__":
    T_START = time.perf_counter()
    _root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path[:0] = [_root, os.path.join(_root, "src")]

from bench import harness  # noqa: E402

# the spans that open a call; every other span lies below one
CALLS = ("tao/engine.simulate", "tao/train.run")


def _read(t) -> List[tuple]:
    """The ``tao/`` spans of the newest profiler trace under the
    benchmark's cache, if its ``bench:window`` span is ``t``'s window."""
    from jax.profiler import ProfileData

    files = glob.glob(os.path.join(harness.CACHE, "*", "trace", "**", "*.xplane.pb"),
                      recursive=True)
    if not files:
        return []
    pd = ProfileData.from_file(max(files, key=os.path.getmtime))
    out, window = [], None
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("tao/"):
                    out.append((e.start_ns, e.end_ns, e.name, dict(e.stats)))
                elif e.name == "bench:window":
                    window = (e.start_ns, e.end_ns)
    if window != (t.t0, t.t1):
        return []
    return sorted(out, key=lambda sp: sp[:3])


def program(t) -> List[tuple]:
    p = getattr(t, "program", None)
    if p is None:
        p = t.program = _read(t)
    return p


def named(t, name: str) -> List[tuple]:
    """The ``tao/<name>`` spans that lie in the traced window."""
    return [sp for sp in program(t) if sp[2] == f"tao/{name}"
            and t.t0 <= sp[0] and sp[1] <= t.t1]


def idle_ns(t, intervals, chip: int = 0) -> float:
    """Nanoseconds in which no operation runs on ``chip``, inside the union
    of ``intervals`` (``(start_ns, end_ns, ...)``): what
    ``t.uncovered_ns`` gives summed over that union, with the chip's busy
    union made once."""
    memo = t.__dict__.setdefault("_spans_busy", {})
    if chip not in memo:
        busy = t.union(t.devices[chip])
        memo[chip] = (busy, [e for _, e in busy])
    busy, ends = memo[chip]
    idle = 0
    for s, e in t.union(intervals):
        covered = 0
        for bs, be in busy[bisect.bisect_right(ends, s):]:
            if bs >= e:
                break
            covered += min(be, e) - max(bs, s)
        idle += (e - s) - covered
    return idle


def exposed_ms(t, part: str, per: str) -> Optional[float]:
    """Chip-0 idle milliseconds inside the ``tao/<part>`` spans, per
    ``tao/<per>`` span.  Both lie in the traced window; a part counts when
    its call is one of the ``per`` spans' calls."""
    units = named(t, per)
    if not units or not t.devices:
        return None
    calls = {sp[3]["call"] for sp in units}
    return idle_ns(t, [sp for sp in named(t, part) if sp[3]["call"] in calls]) / len(units) / 1e6


# the executables the readers match, whose launches lie inside a call
LAUNCHES = r"^jit_(body|_fused_padded|step)\b"


def coverage(t, bench_span: str) -> Dict:
    """Chip-0 idle ms inside the benchmark's ``bench:<bench_span>`` spans,
    the part of it that lies inside program spans below a call, and how
    many of chip 0's launches of the step and kernel executables in the
    window lie inside a call's span (the two clocks agree if all do)."""
    outer = [(s, e) for s, e, _ in t.spans_named(bench_span)]
    parts = [(max(s, a), min(e, b)) for a, b in outer
             for s, e, name, _ in program(t) if name not in CALLS and s < b and e > a]
    idle, inside = idle_ns(t, outer), idle_ns(t, parts)
    calls = [sp for sp in program(t) if sp[2] in CALLS]
    launches = [ev for ev in t.matching(LAUNCHES, line="modules")[0]
                if t.t0 <= ev[0] and ev[1] <= t.t1]
    held = [ev for ev in launches if any(c[0] <= ev[0] and ev[1] <= c[1] for c in calls)]
    return {f"idle_in_{bench_span}_ms": idle / 1e6, "idle_in_parts_ms": inside / 1e6,
            "covered_pct": 100.0 * inside / idle if idle else None,
            "program_spans": len(program(t)), "launches_inside_calls": [len(held), len(launches)]}


def record(cell, t, path: str, ms: int = 60, whole_ms: int = 0) -> None:
    """A recorded trace with the program spans: ``harness.record_fixture``'s
    format, ``program`` added to the cut.  The cut is the shortest whole
    call of at most ``whole_ms`` milliseconds with a millisecond either
    side, or else ``ms`` milliseconds from the middle of the window's
    first call (of the window, where it holds none)."""
    calls = [sp for sp in program(t) if sp[2] in CALLS and t.t0 <= sp[0] and sp[1] <= t.t1]
    whole = [sp for sp in calls if sp[1] - sp[0] <= whole_ms * 1_000_000]
    if whole:
        s, e, *_ = min(whole, key=lambda sp: sp[1] - sp[0])
        t0, t1 = int(s) - 1_000_000, int(e) + 1_000_000
    else:
        s, e = calls[0][:2] if calls else (t.t0, t.t1)
        t0 = int(s + e) // 2
        t1 = t0 + ms * 1_000_000
    cut = t.cut(t0, t1)
    cut["devices"] = [[[s, e, harness.op_name(n)] for s, e, n in evs] for evs in cut["devices"]]
    cut["program"] = [[s, e, n, a] for s, e, n, a in program(t) if e > t0 and s < t1]
    share = (t1 - t0) / max(t.t1 - t.t0, 1)
    work = {k: v * share for k, v in t.work.items()}
    small = from_cut(cut, work, t.config, t.peak, t.traffic)
    expect = {}
    for m in cell.per_layer:
        v = harness.load_module("metrics", m["name"]).read(small)
        if v is not None:
            expect[m["name"]] = v
    bm = harness.load_json(harness.ROOT, "BENCHMARK.json")
    config = {w["name"]: w["config"] for w in bm["workloads"]}[cell.name]
    with open(path, "w") as f:
        json.dump({"workload": cell.name, "config": config, "traffic": t.traffic, "work": work,
                   "cut": cut, "expect": expect}, f)


def from_cut(cut: Dict, work, config, peak, traffic):
    """``TraceView.from_cut`` with the cut's program spans."""
    t = harness.TraceView.from_cut(cut, work, config, peak, traffic)
    t.program = [(int(s), int(e), str(n), dict(a)) for s, e, n, a in cut.get("program", [])]
    return t


def main(argv=None) -> int:
    import argparse

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--record", default=None, metavar="PATH")
    ap.add_argument("--whole-ms", type=int, default=0, metavar="MS")
    args = ap.parse_args(argv)
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                          os.path.join(harness.ROOT, ".cache", "jax"))

    # the harness's traced run, with this module's reading of each trace
    # before the harness deletes it
    def with_spans(cell, tv):
        drv_span = harness.load_module("drivers", cell.traffic["driver"]).Driver.SPAN
        harness.log(f"# spans: {json.dumps(coverage(tv, drv_span))}")
        if args.record:
            record(cell, tv, args.record, whole_ms=args.whole_ms)
        return read_all(cell, tv)

    read_all = harness.layer_metrics
    harness.layer_metrics = with_spans
    run_args = argparse.Namespace(workload=args.workload, seed=args.seed, seconds=args.seconds,
                                  trace=1, rehearse=False)
    result = harness.run_cell(run_args, T_START)
    if result is None:
        return 2
    harness.emit(result)
    return 0


if __name__ == "__main__":
    sys.exit(main())
