"""Chaos smoke: keyed randomized fault schedules, SIGKILL mid-run,
corrupted-newest checkpoints — resume must be EXACT.

    PYTHONPATH=src python tools/chaos_smoke.py --schedules 3

Each schedule draws a fault scenario + fault seed + kill point from a
deterministic RNG and then:

1. Runs the faulted service as a REFERENCE subprocess, uninterrupted,
   with durable checkpoints + GC (``--keep-last-k``); its final
   checkpoint is the ground-truth state at ``EVENTS`` events.
2. Runs the identical configuration as a VICTIM subprocess, waits for
   the schedule's checkpoint count, and SIGKILLs it.
3. CORRUPTS the newest surviving checkpoint (torn-write stand-in) —
   resume must fall back a generation across the GC frontier.
4. Resumes in a fresh subprocess and compares final checkpoints:
   ``model_err == 0.0`` (bit-identical — same binary, same keyed
   draws), identical merge traces, a schema-valid v2 trace export,
   constant per-edge merge mass, and bounded SLO degradation vs the
   fault-free baseline.

The fault-free baseline runs as a subprocess too, and the exported
trace is read back through the service's validating loader in a child:
this process never imports JAX, so on a machine with an accelerator the
children get the device (each in turn) and inherit the platform from
the environment.

Exit code 0 on success; any assertion failure is fatal (CI red).
"""
from __future__ import annotations

import argparse
import json
import os
import shutil
import signal
import subprocess
import sys
import tempfile
import time

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "src"))

from repro.checkpoint import latest_checkpoint, load_pytree  # noqa: E402

UES, EDGES, MAX_STALENESS = 16, 3, 3
EVENTS = 100
CKPT_EVERY = 10
KEEP_LAST_K = 3
SEGMENTS = "deterministic:1.0:40,heavy_tail_compute:0.8:inf"
SCENARIOS = ("ue_churn", "edge_outage", "lossy_uplink")
SLO_FACTOR = 10.0           # smoke bound; bench_chaos holds the tight 2x
TIMEOUT = 300.0


# Reads a trace export through the service's validating loader; run in a
# child so that this process stays off JAX.
_LOAD_TRACE = """
import json, sys
from repro.launch.service import load_service_trace_jsonl
print(json.dumps(load_service_trace_jsonl(sys.argv[1])))
"""


def _cmd(ckpt_dir, scenario=None, fault_seed=0, *, resume=False,
         trace=None):
    cmd = [sys.executable, "-m", "repro.launch.service",
           "--ues", str(UES), "--edges", str(EDGES),
           "--max-staleness", str(MAX_STALENESS),
           "--segments", SEGMENTS, "--max-updates", str(EVENTS),
           "--ckpt-dir", ckpt_dir, "--ckpt-every", str(CKPT_EVERY),
           "--keep-last-k", str(KEEP_LAST_K)]
    if scenario:
        cmd += ["--fault-scenario", scenario,
                "--fault-seed", str(fault_seed)]
    if resume:
        cmd.append("--resume")
    if trace:
        cmd += ["--trace", trace]
    return cmd


def _final_state(ckpt_dir):
    tree, _meta = load_pytree(latest_checkpoint(ckpt_dir))
    g = np.asarray(tree["g"], np.float32)
    trace = json.loads(str(np.asarray(tree["trace_json"])))
    return g, trace


def _load_trace(path, env):
    out = subprocess.run([sys.executable, "-c", _LOAD_TRACE, path],
                         env=env, cwd=REPO, capture_output=True, text=True,
                         timeout=TIMEOUT)
    assert out.returncode == 0, f"trace export rejected: {out.stderr}"
    header, records = json.loads(out.stdout.splitlines()[-1])
    return header, records


def _merges(trace):
    return [(round(r["t"], 9), r["edge"], r["cycle"], round(r["mass"], 9))
            for r in trace if r["kind"] == "merge"]


def _p95(trace):
    lat = [r["latency"] for r in trace if r["kind"] == "merge"]
    return float(np.percentile(lat, 95)) if lat else 0.0


def _run_schedule(i, env, baseline_p95):
    rng = np.random.default_rng(1000 + i)
    scenario = SCENARIOS[i % len(SCENARIOS)]
    fault_seed = int(rng.integers(0, 2**31 - 1))
    kill_after = int(rng.integers(2, 5))    # checkpoints before SIGKILL
    print(f"[chaos-smoke] schedule {i}: scenario={scenario} "
          f"fault_seed={fault_seed} kill_after={kill_after} ckpts")

    ref_dir = tempfile.mkdtemp(prefix=f"chaos_ref_{i}_")
    vic_dir = tempfile.mkdtemp(prefix=f"chaos_vic_{i}_")
    try:
        rc = subprocess.run(
            _cmd(ref_dir, scenario, fault_seed), env=env, cwd=REPO,
            stdout=subprocess.DEVNULL, timeout=TIMEOUT).returncode
        assert rc == 0, f"reference run failed (rc={rc})"
        ref_g, ref_trace = _final_state(ref_dir)

        victim = subprocess.Popen(
            _cmd(vic_dir, scenario, fault_seed), env=env, cwd=REPO,
            stdout=subprocess.DEVNULL, stderr=subprocess.STDOUT)
        deadline = time.time() + TIMEOUT
        try:
            while True:
                done = len([f for f in os.listdir(vic_dir)
                            if f.startswith("ckpt-")
                            and f.endswith(".npz")])
                if done >= kill_after or victim.poll() is not None:
                    break
                assert time.time() < deadline, \
                    "timed out waiting for victim checkpoints"
                time.sleep(0.05)
            if victim.poll() is None:
                victim.send_signal(signal.SIGKILL)
                victim.wait(timeout=30)
        finally:
            if victim.poll() is None:
                victim.kill()
        # A fast victim may finish the whole budget before the kill
        # lands; that degenerates to plain restart-parity — still valid.
        killed = victim.returncode == -signal.SIGKILL
        print(f"[chaos-smoke]   victim "
              f"{'SIGKILLed' if killed else 'finished'} "
              f"(rc={victim.returncode})")

        newest = latest_checkpoint(vic_dir)
        with open(newest, "r+b") as f:      # torn-write stand-in
            f.truncate(max(os.path.getsize(newest) // 2, 1))
        print(f"[chaos-smoke]   corrupted {os.path.basename(newest)}")

        trace_path = os.path.join(vic_dir, "trace.jsonl")
        rc = subprocess.run(
            _cmd(vic_dir, scenario, fault_seed, resume=True,
                 trace=trace_path),
            env=env, cwd=REPO, stdout=subprocess.DEVNULL,
            timeout=TIMEOUT).returncode
        assert rc == 0, f"resume run failed (rc={rc})"

        got_g, got_trace = _final_state(vic_dir)
        err = float(np.abs(got_g - ref_g).max())
        assert err == 0.0, f"schedule {i}: model_err={err} != 0.0"
        assert _merges(got_trace) == _merges(ref_trace), \
            f"schedule {i}: merge trace diverged after resume"
        assert any(r["kind"] == "resume" for r in got_trace), \
            f"schedule {i}: no resume record"

        # the exported trace must pass the validating loader
        header, records = _load_trace(trace_path, env)
        assert header["version"] == 2

        # per-edge merge mass is conserved (same cohort, every cycle)
        mass = {}
        for r in records:
            if r["kind"] == "merge":
                assert r["mass"] > 0.0
                mass.setdefault(r["edge"], r["mass"])
                assert abs(r["mass"] - mass[r["edge"]]) < 1e-9, \
                    f"schedule {i}: edge {r['edge']} mass drifted"

        # GC bounded the directory (corrupted strays aside, the live
        # generations are at most keep_last_k + the in-flight save)
        live = [f for f in os.listdir(vic_dir) if f.startswith("ckpt-")]
        assert len(live) <= KEEP_LAST_K + 1, \
            f"schedule {i}: GC left {len(live)} checkpoints"

        p95 = _p95(got_trace)
        assert p95 <= SLO_FACTOR * baseline_p95, (
            f"schedule {i}: faulted p95={p95:.3f}s exceeds "
            f"{SLO_FACTOR}x fault-free baseline {baseline_p95:.3f}s")
        n_shed = sum(1 for r in records if r["kind"] == "shed-fault")
        print(f"[chaos-smoke]   OK model_err=0.0 "
              f"merges={len(_merges(got_trace))} shed-fault={n_shed} "
              f"p95={p95:.3f}s (<= {SLO_FACTOR}x {baseline_p95:.3f}s)")
    finally:
        shutil.rmtree(ref_dir, ignore_errors=True)
        shutil.rmtree(vic_dir, ignore_errors=True)


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--schedules", type=int, default=3)
    args = ap.parse_args(argv)

    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"))

    # fault-free baseline for the SLO bound (subprocess)
    base_dir = tempfile.mkdtemp(prefix="chaos_base_")
    try:
        rc = subprocess.run(_cmd(base_dir), env=env, cwd=REPO,
                            stdout=subprocess.DEVNULL,
                            timeout=TIMEOUT).returncode
        assert rc == 0, f"fault-free baseline run failed (rc={rc})"
        baseline_p95 = _p95(_final_state(base_dir)[1])
    finally:
        shutil.rmtree(base_dir, ignore_errors=True)
    print(f"[chaos-smoke] fault-free baseline p95={baseline_p95:.3f}s")

    for i in range(args.schedules):
        _run_schedule(i, env, baseline_p95)
    print(f"[chaos-smoke] OK ({args.schedules} schedules)")


if __name__ == "__main__":
    main()
