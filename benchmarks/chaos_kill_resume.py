"""Kill-and-resume smoke: SIGKILL a resumable sweep, resume, compare.

The real-process version of the chaos suite's in-process crash test:

1. a reference child runs a 4-job DSE-style sweep uninterrupted;
2. a victim child runs the same sweep with ``resume_key`` against an
   ``ArtifactStore``, with a ``REPRO_FAULT_PLAN`` delay fault parking it
   mid-job after 2 progress manifests have landed — the parent SIGKILLs
   it there (a genuinely torn process, not a polite exception);
3. a resume child re-runs the identical invocation and must skip the 2
   completed jobs, extract 0 features (the remainder's features come
   from the store), and produce metrics bit-identical to the reference.

CI's chaos-smoke job runs ``python -m benchmarks.chaos_kill_resume``.
Exit code 0 = all assertions held.
"""
from __future__ import annotations

import argparse
import json
import os
import signal
import subprocess
import sys
import tempfile
import time

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_SRC = os.path.join(_ROOT, "src")

_RESUME_KEY = "chaos-kill-resume"
_N_DONE_BEFORE_KILL = 2   # manifests published before the victim parks
_PARK_S = 600.0           # far longer than the parent's kill latency
_VICTIM_PLAN = json.dumps({
    "faults": [{
        "site": "scheduler.consume",
        "kind": "delay",
        "after": _N_DONE_BEFORE_KILL,
        "delay_s": _PARK_S,
    }],
})


# ---------------------------------------------------------------------------
# child: one sweep run, result on stdout


def _child(store_root: str, resume_key: str) -> None:
    import jax
    import numpy as np

    from repro.api import ArtifactStore
    from repro.core import init_tao
    from repro.engine import EngineConfig
    from repro.engine.scheduler import SweepJob, TraceSweeper
    from repro.resilience import FaultPlan, inject

    from benchmarks.common import TEST_LEN, session, tao_config

    cfg = tao_config()
    s = session()
    t1 = s.capture("mcf", TEST_LEN).functional
    t2 = s.capture("dee", max(cfg.window * 3, TEST_LEN // 2)).functional
    p1 = init_tao(jax.random.PRNGKey(0), cfg)
    p2 = init_tao(jax.random.PRNGKey(1), cfg)
    jobs = [
        SweepJob("m1/a", p1, t1), SweepJob("m1/b", p1, t2),
        SweepJob("m2/a", p2, t1), SweepJob("m2/b", p2, t2),
    ]
    store = ArtifactStore(store_root) if store_root else None
    # arm the CI chaos knob if set (inject(None) is a pass-through) —
    # the victim run parks on a delay fault here until SIGKILLed
    with inject(FaultPlan.from_env()):
        report = TraceSweeper(cfg, EngineConfig(batch_size=8),
                              store=store).run(
            jobs, resume_key=resume_key or None)
    out = {
        "jobs_skipped": report.jobs_skipped,
        "features_extracted": report.features_extracted,
        "features_from_store": report.features_from_store,
        "num_traces": report.num_traces,
        "metrics": {
            key: {m: np.asarray(v).tolist() for m, v in r.metrics.items()}
            for key, r in report.results.items()
        },
    }
    print("RESULT " + json.dumps(out), flush=True)


# ---------------------------------------------------------------------------
# parent: orchestrate ref / victim / resume


def _spawn(store_root: str, resume_key: str, extra_env=None):
    env = os.environ.copy()
    env["PYTHONPATH"] = os.pathsep.join(
        filter(None, [_SRC, env.get("PYTHONPATH")]))
    # Off the chip by design: this checks crash-resume of the host-side
    # progress manifests, and the victim child is SIGKILLed mid-sweep —
    # never do that to a process holding a TPU.
    env.setdefault("JAX_PLATFORMS", "cpu")
    env.pop("REPRO_FAULT_PLAN", None)
    env.update(extra_env or {})
    return subprocess.Popen(
        [sys.executable, "-m", "benchmarks.chaos_kill_resume",
         "--child", "--store", store_root, "--resume-key", resume_key],
        cwd=_ROOT, env=env,
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
    )


def _result(proc, label: str, timeout_s: float = 600.0) -> dict:
    out, _ = proc.communicate(timeout=timeout_s)
    if proc.returncode != 0:
        sys.stderr.write(out)
        raise SystemExit(f"{label} child failed rc={proc.returncode}")
    for line in out.splitlines():
        if line.startswith("RESULT "):
            return json.loads(line[len("RESULT "):])
    sys.stderr.write(out)
    raise SystemExit(f"{label} child printed no RESULT line")


def _progress_count(store_root: str) -> int:
    kdir = os.path.join(store_root, "objects", "sweep_progress")
    if not os.path.isdir(kdir):
        return 0
    return sum(
        len(os.listdir(os.path.join(kdir, prefix)))
        for prefix in os.listdir(kdir)
    )


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--child", action="store_true")
    ap.add_argument("--store", default="")
    ap.add_argument("--resume-key", default="")
    args = ap.parse_args()
    if args.child:
        _child(args.store, args.resume_key)
        return

    with tempfile.TemporaryDirectory(prefix="chaos-resume-") as tmp:
        store = os.path.join(tmp, "store")

        print("chaos_kill_resume: reference run ...", flush=True)
        ref = _result(_spawn("", ""), "reference")
        assert ref["num_traces"] == 4, ref

        print("chaos_kill_resume: victim run (will be SIGKILLed) ...",
              flush=True)
        victim = _spawn(store, _RESUME_KEY,
                        extra_env={"REPRO_FAULT_PLAN": _VICTIM_PLAN})
        deadline = time.monotonic() + 300.0
        while _progress_count(store) < _N_DONE_BEFORE_KILL:
            if victim.poll() is not None:
                out, _ = victim.communicate()
                sys.stderr.write(out)
                raise SystemExit(
                    "victim exited before publishing enough progress "
                    f"(rc={victim.returncode})")
            if time.monotonic() > deadline:
                victim.kill()
                raise SystemExit("timed out waiting for victim progress")
            time.sleep(0.05)
        os.kill(victim.pid, signal.SIGKILL)
        victim.communicate()
        print(f"chaos_kill_resume: killed victim pid={victim.pid} with "
              f"{_progress_count(store)} manifests published", flush=True)

        print("chaos_kill_resume: resume run ...", flush=True)
        res = _result(_spawn(store, _RESUME_KEY), "resume")

        assert res["jobs_skipped"] == _N_DONE_BEFORE_KILL, res
        assert res["features_extracted"] == 0, res
        assert res["num_traces"] == 4, res
        assert set(res["metrics"]) == set(ref["metrics"]), (
            sorted(res["metrics"]), sorted(ref["metrics"]))
        for key in ref["metrics"]:
            assert res["metrics"][key] == ref["metrics"][key], (
                f"metrics diverge for {key}")
        print("chaos_kill_resume: OK — resume skipped "
              f"{res['jobs_skipped']} jobs, extracted 0 features, "
              "metrics bit-identical to the uninterrupted run", flush=True)


if __name__ == "__main__":
    main()
