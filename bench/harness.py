"""The benchmark harness: one run of one cell.

Everything that belongs to one cell is found by name, from
``BENCHMARK.json``:

  bench/configs/<config>.json     widths, precision, batch, plan
  bench/traffic/<traffic>.json    the driver's name, its parameters, limits
  bench/drivers/<driver>.py       ``Driver``: set-up, one request, check
  bench/metrics/<metric>.py       ``value(window)`` for an end-to-end metric,
                                  ``read(trace)`` for a per-layer one

A run: set-up (weights from the seed, traffic, warm-up of every shape the
traffic uses) -> a closed-loop window of ``--seconds`` that ends when the
last request started before the deadline completes -> peak device memory
-> the program's state is released -> the check against the plain
reference (``bench/reference``) -> one JSON result line.
"""
from __future__ import annotations

import contextlib
import dataclasses
import glob
import hashlib
import importlib.util
import json
import os
import shutil
import sys
import time
import traceback
from typing import Dict, List, Optional

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "bench")
CACHE = os.path.join(ROOT, ".cache", "bench")

# the traced window is cut to this many seconds: enough requests for the
# per-layer metrics, a trace small enough to read back in seconds
TRACE_SECONDS = 6.0

# tiny widths for ``--rehearse`` on the CPU (Pallas in interpret mode)
REHEARSE_WIDTHS = {"window": 33, "d_model": 64, "n_heads": 4, "n_layers": 2, "d_ff": 128,
                   "d_cat": 16, "n_buckets": 32, "n_queue": 4, "n_mem": 8, "batch_size": 16}
REHEARSE_SCALE = 1 / 64


def load_json(*parts) -> Dict:
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def load_module(kind: str, name: str):
    """``bench/<kind>/<name>.py`` as a module (names may hold dots)."""
    path = os.path.join(BENCH, kind, f"{name}.py")
    spec = importlib.util.spec_from_file_location(f"bench_{kind}_{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _entropy(seed: int, salt) -> List[int]:
    """Seed-sequence entropy: the seed (any size) and salts, strings hashed."""
    out = [int(seed)]
    for s in salt:
        out.append(int(s) if isinstance(s, int)
                   else int.from_bytes(hashlib.blake2b(str(s).encode(), digest_size=4).digest(), "little"))
    return out


def derive_seed(seed: int, *salt) -> int:
    """A 31-bit seed derived from the run's seed (which may exceed 32 bits)."""
    return int(np.random.default_rng(_entropy(seed, salt)).integers(2**31 - 1))


# ---------------------------------------------------------------------------
# the cell
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: Dict       # widths etc. (rehearsal: tiny widths)
    traffic: Dict
    end_to_end: List[Dict]
    per_layer: List[Dict]

    @classmethod
    def load(cls, workload: str, rehearse: bool = False, entry: Optional[Dict] = None) -> "Cell":
        """The cell ``workload`` of ``BENCHMARK.json`` (``entry``: a cell
        given in its place, for rehearsing one that is not there yet)."""
        bm = load_json(ROOT, "BENCHMARK.json")
        cells = {w["name"]: w for w in bm["workloads"]}
        if entry is None and workload not in cells:
            raise SystemExit(f"unknown workload {workload!r}; known: {sorted(cells)}")
        w = entry or cells[workload]
        config = load_json(BENCH, "configs", f"{w['config']}.json")
        if rehearse:
            config = {**config, **REHEARSE_WIDTHS}
        traffic = load_json(BENCH, "traffic", f"{w['traffic']}.json")

        def mine(m):
            return "workloads" not in m or workload in m["workloads"]

        return cls(workload, w["chips"], config, traffic,
                   [m for m in bm["end_to_end"] if mine(m)],
                   [m for m in bm["per_layer"] if mine(m)])


class Run:
    """What a driver sees: the cell, the seed, sizes, spans and a log."""

    def __init__(self, cell: Cell, seed: int, rehearse: bool, tracing: bool = False):
        self.cell = cell
        self.config = cell.config
        self.traffic = cell.traffic
        self.seed = seed
        self.rehearse = rehearse
        self.tracing = tracing
        self.chips = cell.chips

    def size(self, n: int, floor: int = 1) -> int:
        """A traffic size, cut for a rehearsal."""
        return max(floor, int(n * REHEARSE_SCALE)) if self.rehearse else int(n)

    def rng(self, *salt) -> np.random.Generator:
        return np.random.default_rng(_entropy(self.seed, salt))

    def cache(self, *parts) -> str:
        """A path under the checkout's git-ignored ``.cache/bench``."""
        sub = "rehearse" if self.rehearse else "chip"
        path = os.path.join(CACHE, sub, *parts)
        os.makedirs(os.path.dirname(path), exist_ok=True)
        return path

    @contextlib.contextmanager
    def span(self, name: str):
        if self.tracing:
            import jax

            with jax.profiler.TraceAnnotation(f"bench:{name}"):
                yield
        else:
            yield

    def tao_config(self):
        from repro.core import FeatureConfig, TaoConfig

        c = self.config
        return TaoConfig(
            window=c["window"], d_model=c["d_model"], n_heads=c["n_heads"],
            n_layers=c["n_layers"], d_ff=c["d_ff"], d_cat=c["d_cat"],
            features=FeatureConfig(n_buckets=c["n_buckets"], n_queue=c["n_queue"],
                                   n_mem=c["n_mem"]),
            dtype=c["dtype"],
        )


def log(*a) -> None:
    print(*a, flush=True)


# ---------------------------------------------------------------------------
# compile events (jax.monitoring)
# ---------------------------------------------------------------------------


class CompileCounter:
    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax

        self.count = 0
        self.seconds = 0.0

        def listen(event, duration, **kw):
            if event == self.EVENT:
                self.count += 1
                self.seconds += duration

        jax.monitoring.register_event_duration_secs_listener(listen)


# ---------------------------------------------------------------------------
# the window
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class Window:
    """The measured window: one record per request, host clock."""

    t_open: float
    t_close: float = 0.0
    requests: List[Dict] = dataclasses.field(default_factory=list)
    failed: int = 0

    @property
    def seconds(self) -> float:
        return self.t_close - self.t_open

    def total(self, key: str) -> float:
        return float(sum(r.get(key, 0) for r in self.requests))


def closed_loop(drv, run: Run, seconds: float) -> Window:
    """One client: the next request starts when the previous one returns;
    no request starts after ``seconds``; the window closes when the last
    one completes."""
    win = Window(t_open=time.perf_counter())
    deadline = win.t_open + seconds
    i = 0
    while True:
        t0 = time.perf_counter()
        if t0 >= deadline:
            break
        try:
            with run.span(drv.SPAN):
                rec = drv.request(i)
        except Exception:
            traceback.print_exc()
            win.failed += 1
            rec = {}
        rec.update(t0=t0, t1=time.perf_counter(), index=i)
        win.requests.append(rec)
        i += 1
    win.t_close = time.perf_counter()
    return win


# ---------------------------------------------------------------------------
# device facts
# ---------------------------------------------------------------------------


def device_info(chips: int) -> Dict:
    import jax

    devs = jax.devices()[:chips]
    peak = 0
    for d in devs:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": peak}


def peaks_for(kind: str) -> Dict:
    table = load_json(BENCH, "peaks.json")
    if kind not in table:
        raise KeyError(f"device kind {kind!r} is not in bench/peaks.json")
    return table[kind]


# ---------------------------------------------------------------------------
# the profiler trace
# ---------------------------------------------------------------------------


class TraceView:
    """A profiler trace reduced to what the per-layer readers use.

    ``devices``: for each chip used, its operations ``(start_ns, end_ns,
    name)`` on the "XLA Ops" line, sorted.  ``modules``: the same for the
    "XLA Modules" line (one event per executable launch).  ``spans``: the
    benchmark's ``bench:*`` annotations ``(start_ns, end_ns, name)``.
    ``t0``/``t1``: the traced window (the ``bench:window`` span).
    ``work``: the counts the harness summed over the traced requests.
    """

    def __init__(self, devices, modules, spans, t0, t1, work, config, peak, traffic):
        self.devices = devices
        self.modules = modules
        self.spans = spans
        self.t0, self.t1 = t0, t1
        self.work = work
        self.config = config
        self.peak = peak
        self.traffic = traffic

    @property
    def seconds(self) -> float:
        return (self.t1 - self.t0) / 1e9

    def cut(self, t0: int, t1: int) -> Dict:
        """The events of [t0, t1) as plain lists (a small recorded trace)."""
        def keep(evs):
            return [list(ev) for ev in evs if ev[1] > t0 and ev[0] < t1]

        return {"t0": t0, "t1": t1, "devices": [keep(d) for d in self.devices],
                "modules": [keep(m) for m in self.modules], "spans": keep(self.spans)}

    @classmethod
    def from_cut(cls, cut: Dict, work, config, peak, traffic) -> "TraceView":
        def tup(evs):
            return [(int(s), int(e), str(n)) for s, e, n in evs]

        return cls([tup(d) for d in cut["devices"]], [tup(m) for m in cut["modules"]],
                   tup(cut["spans"]), cut["t0"], cut["t1"], work, config, peak, traffic)

    @staticmethod
    def union(intervals) -> List[tuple]:
        out = []
        for s, e, *_ in sorted(intervals):
            if out and s <= out[-1][1]:
                if e > out[-1][1]:
                    out[-1][1] = e
            else:
                out.append([s, e])
        return [tuple(x) for x in out]

    def busy_ns(self, chip: int) -> float:
        return sum(e - s for s, e in self.union(self.devices[chip]))

    def busy_s(self) -> float:
        """Seconds in which an operation ran, averaged over the chips."""
        return sum(self.busy_ns(c) for c in range(len(self.devices))) / len(self.devices) / 1e9

    def matching(self, pattern: str, line: str = "ops") -> List[List[tuple]]:
        """Per chip, the events whose name matches ``pattern`` (re.search)."""
        import re

        rx = re.compile(pattern)
        src = self.devices if line == "ops" else self.modules
        return [[ev for ev in evs if rx.search(ev[2])] for evs in src]

    def uncovered_ns(self, s: int, e: int, chip: int = 0) -> float:
        """Nanoseconds of [s, e) in which no operation runs on ``chip``."""
        covered = 0
        for bs, be in self.union(self.devices[chip]):
            lo, hi = max(bs, s), min(be, e)
            if hi > lo:
                covered += hi - lo
        return (e - s) - covered

    def spans_named(self, name: str) -> List[tuple]:
        return [sp for sp in self.spans if sp[2] == f"bench:{name}"]


def read_trace(trace_dir: str, chips: int) -> tuple:
    """(devices, modules, spans) from the newest ``.xplane.pb`` under
    ``trace_dir``."""
    from jax.profiler import ProfileData

    files = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True),
                   key=os.path.getmtime)
    pd = ProfileData.from_file(files[-1])
    devices, modules, spans = {}, {}, []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:") and "Core" not in plane.name:
            idx = int(plane.name.split(":")[2].split()[0])
            for line in plane.lines:
                if line.name == "XLA Ops":
                    devices[idx] = sorted((e.start_ns, e.end_ns, e.name) for e in line.events)
                elif line.name == "XLA Modules":
                    modules[idx] = sorted((e.start_ns, e.end_ns, e.name) for e in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith("bench:"):
                        spans.append((e.start_ns, e.end_ns, e.name))
    ids = sorted(devices)[:chips]
    return ([devices[i] for i in ids], [modules.get(i, []) for i in ids], sorted(spans))


def record_fixture(cell: Cell, tv: TraceView, path: str, ms: int = 60) -> None:
    """A small recorded trace for ``bench/tests``: ``ms`` milliseconds from
    the middle of the traced window, the work scaled to that share of it,
    and what every per-layer reader reads there."""
    t0 = (tv.t0 + tv.t1) // 2
    cut = tv.cut(t0, t0 + ms * 1_000_000)
    cut["devices"] = [[[s, e, op_name(n)] for s, e, n in evs] for evs in cut["devices"]]
    share = ms * 1e6 / max(tv.t1 - tv.t0, 1)
    work = {k: v * share for k, v in tv.work.items()}
    small = TraceView.from_cut(cut, work, tv.config, tv.peak, tv.traffic)
    expect = {}
    for m in cell.per_layer:
        v = load_module("metrics", m["name"]).read(small)
        if v is not None:
            expect[m["name"]] = v
    bm = load_json(ROOT, "BENCHMARK.json")
    config = {w["name"]: w["config"] for w in bm["workloads"]}[cell.name]
    with open(path, "w") as f:
        json.dump({"workload": cell.name, "config": config, "traffic": tv.traffic, "work": work,
                   "cut": cut, "expect": expect}, f)


def op_name(name: str) -> str:
    """``%fusion.12 = f32[...] fusion(...), ...`` -> ``fusion.12``."""
    return name.split(" = ", 1)[0].lstrip("%")


def breakdown(tv: TraceView) -> Dict:
    """The device operations that took most time (seconds per chip) and the
    longest idle gaps of chip 0, each named by the benchmark span open in
    it."""
    from collections import defaultdict

    ops = defaultdict(float)
    for evs in tv.devices:
        for s, e, name in evs:
            ops[op_name(name)] += (e - s) / 1e9 / len(tv.devices)
    top = sorted(ops.items(), key=lambda kv: -kv[1])[:10]
    gaps = []
    busy = tv.union(tv.devices[0]) if tv.devices else []
    edges = [tv.t0] + [x for b in busy for x in b] + [tv.t1]
    inner = [sp for sp in tv.spans if sp[2] != "bench:window"]
    for s, e in zip(edges[0::2], edges[1::2]):
        s, e = max(s, tv.t0), min(e, tv.t1)
        if e <= s:
            continue
        mid = (s + e) / 2
        label = "between_requests"
        for ss, se, name in inner:
            if ss <= mid < se:
                label = name[len("bench:"):]
        gaps.append([label, (e - s) / 1e9])
    gaps.sort(key=lambda g: -g[1])
    return {"device_ops": [list(kv) for kv in top], "idle_gaps": gaps[:10]}


# ---------------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------------


def e2e_metrics(cell: Cell, win: Window, setup_s: float) -> Dict:
    out = {}
    for m in cell.end_to_end:
        if m["name"] == "setup_s":
            v = setup_s
        else:
            v = load_module("metrics", m["name"]).value(win)
        out[m["name"]] = {"value": v, "unit": m["unit"]}
    return out


def layer_metrics(cell: Cell, tv: TraceView) -> Dict:
    out = {}
    for m in cell.per_layer:
        v = load_module("metrics", m["name"]).read(tv)
        if v is not None:
            out[m["name"]] = {"value": v, "unit": m["unit"]}
    return out


def run_cell(args, t_start: float, require_chip: bool = True) -> Optional[Dict]:
    """Set-up, window, check; returns the result dict (None when the
    device check fails)."""
    import jax

    cell = Cell.load(args.workload, rehearse=args.rehearse, entry=getattr(args, "entry", None))
    devs = jax.devices()
    log(f"# device: platform={devs[0].platform} kind={devs[0].device_kind} count={len(devs)}")
    if require_chip and (devs[0].platform != "tpu" or len(devs) < cell.chips):
        print(f"no TPU with {cell.chips} chip(s) found (platform {devs[0].platform}, "
              f"{len(devs)} device(s)); rehearse on the CPU with --rehearse", file=sys.stderr)
        return None
    if len(devs) < cell.chips:
        print(f"cell needs {cell.chips} devices, found {len(devs)}", file=sys.stderr)
        return None

    from repro.engine import enable_persistent_cache, persistent_cache_status

    enable_persistent_cache()
    compiles = CompileCounter()
    run = Run(cell, args.seed, args.rehearse, tracing=bool(args.trace))
    drv = load_module("drivers", cell.traffic["driver"]).Driver(run)
    drv.setup()
    seconds = min(args.seconds, TRACE_SECONDS) if args.trace else args.seconds
    trace_dir = run.cache("trace", cell.name)
    if args.trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        jax.profiler.start_trace(trace_dir)
    c_open = compiles.count
    t_open = time.perf_counter()
    setup_s = t_open - t_start
    with run.span("window"):
        win = closed_loop(drv, run, seconds)
    if args.trace:
        jax.profiler.stop_trace()
    c_window = compiles.count - c_open
    dev = device_info(cell.chips)
    log(f"# window: {win.seconds:.6f} s, {len(win.requests)} requests, {win.failed} failed, "
        f"set-up {setup_s:.6f} s")
    log(f"# compiles: in window {c_window}, in set-up {c_open} ({compiles.seconds:.3f} s of XLA)")
    log(f"# cache: {json.dumps(persistent_cache_status())}")
    gaps = [b["t0"] - a["t1"] for a, b in zip(win.requests, win.requests[1:])]
    if gaps:
        log(f"# client: gap between requests max {max(gaps) * 1e3:.3f} ms, "
            f"mean {np.mean(gaps) * 1e3:.3f} ms (closed loop: no schedule to fall behind)")
    for line in drv.counters():
        log(f"# {line}")
    log(f"# device: {json.dumps(dev)}")

    result = {"attempted": len(win.requests), "failed": win.failed, "device": dev}
    if args.trace:
        devices, modules, spans = read_trace(trace_dir, cell.chips)
        wspan = [sp for sp in spans if sp[2] == "bench:window"]
        t0, t1 = (wspan[0][0], wspan[0][1]) if wspan else (0, 0)
        work = {k: win.total(k) for k in ("instructions", "windows")}
        tv = TraceView(devices, modules, spans, t0, t1, work, cell.config,
                       peaks_for(dev["kind"]) if dev["platform"] == "tpu" else None, cell.traffic)
        if devices and getattr(args, "record_trace", None):
            record_fixture(cell, tv, args.record_trace)
        if devices:
            result["metrics"] = layer_metrics(cell, tv)
            result["device"].update(busy_s=tv.busy_s(), window_s=tv.seconds)
            result["breakdown"] = breakdown(tv)
        else:
            result["metrics"] = {}
            log("# trace: no device plane (not a TPU); per-layer metrics not read")
        shutil.rmtree(trace_dir, ignore_errors=True)
    else:
        result["metrics"] = e2e_metrics(cell, win, setup_s)
    drv.release()
    readings = drv.check(win)
    log(f"# readings: {json.dumps(readings)}")
    # the numbers compared are those the traffic file gives a limit; a
    # missing one (nothing was compared) fails
    checks = [(k, readings.get(k), lim) for k, lim in cell.traffic["limits"].items()]
    result["correct"] = win.failed == 0 and all(v is not None and v <= lim for _, v, lim in checks)
    result["checks"] = {k: {"value": v, "limit": lim} for k, v, lim in checks}
    for k, v, lim in checks:
        print(f"check {k}: {v!r} (limit {lim!r})", file=sys.stderr)
    return result


def emit(result: Dict) -> None:
    """The result line: ``checks`` comes last."""
    keys = ["correct", "attempted", "failed", "metrics", "device", "breakdown"]
    out = {k: result[k] for k in keys if k in result}
    out["checks"] = result["checks"]
    sys.stderr.flush()
    print(json.dumps(out), flush=True)
