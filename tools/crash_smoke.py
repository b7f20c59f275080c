"""Crash-recovery smoke: SIGKILL the always-on service mid-run, resume,
assert parity with an uninterrupted run.

    PYTHONPATH=src python tools/crash_smoke.py

1. Runs the reference service as a SUBPROCESS to ``EVENTS`` cloud events
   and keeps its final checkpoint (model + merge trace).
2. Launches the same configuration as another SUBPROCESS
   (``python -m repro.launch.service``) with durable checkpoints every
   ``CKPT_EVERY`` events, waits until at least two checkpoints exist,
   and ``kill -9``s it — an unclean death at an arbitrary point,
   possibly mid-checkpoint (the atomic tmp+rename writer must leave the
   previous file intact).
3. Launches a fresh subprocess with ``--resume``; it restores the
   newest valid checkpoint and finishes the budget.
4. Compares the resumed run's FINAL checkpoint (the state at exactly
   ``EVENTS`` events, pre-drain) against the reference: the merge trace
   must match event-for-event and the published model to <= 1e-6.

Every run is a child process and this one never imports JAX, so on a
machine with an accelerator the children get the device (each in turn)
and inherit the platform from the environment.

Exit code 0 on success; any assertion failure is fatal (CI red).
"""
from __future__ import annotations

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

UES, EDGES, MAX_STALENESS = 24, 4, 4
EVENTS = 160
CKPT_EVERY = 10
SEGMENTS = "iid_campus:1.0:40,iid_campus:4.0:60,iid_campus:1.0:inf"
KILL_AFTER_CKPTS = 2
TIMEOUT = 300.0



def _service_cmd(ckpt_dir: str, resume: bool):
    cmd = [sys.executable, "-m", "repro.launch.service",
           "--ues", str(UES), "--edges", str(EDGES),
           "--max-staleness", str(MAX_STALENESS),
           "--segments", SEGMENTS, "--max-updates", str(EVENTS),
           "--ckpt-dir", ckpt_dir, "--ckpt-every", str(CKPT_EVERY)]
    if resume:
        cmd.append("--resume")
    return cmd


def _final_state(ckpt_dir: str):
    """(published model, merge records) of the newest checkpoint."""
    final = latest_checkpoint(ckpt_dir)
    assert final is not None, f"no checkpoint in {ckpt_dir}"
    tree, _meta = load_pytree(final)
    trace = json.loads(str(np.asarray(tree["trace_json"])))
    merges = [(round(r["t"], 9), r["edge"], r["cycle"])
              for r in trace if r["kind"] == "merge"]
    return np.asarray(tree["g"], np.float32), merges, trace


def main() -> None:
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"))
    ref_dir = tempfile.mkdtemp(prefix="crash_smoke_ref_")
    tmp = tempfile.mkdtemp(prefix="crash_smoke_")
    try:
        print(f"[crash-smoke] reference run ({EVENTS} events, subprocess)")
        rc = subprocess.run(_service_cmd(ref_dir, resume=False), env=env,
                            cwd=REPO, stdout=subprocess.DEVNULL,
                            timeout=TIMEOUT).returncode
        assert rc == 0, f"reference run failed (rc={rc})"
        ref_g, ref_merges, _ = _final_state(ref_dir)

        print("[crash-smoke] victim subprocess + SIGKILL after "
              f"{KILL_AFTER_CKPTS} checkpoints")
        victim = subprocess.Popen(_service_cmd(tmp, resume=False),
                                  env=env, cwd=REPO,
                                  stdout=subprocess.DEVNULL,
                                  stderr=subprocess.STDOUT)
        deadline = time.time() + TIMEOUT
        try:
            while True:
                n = len([f for f in os.listdir(tmp)
                         if f.startswith("ckpt-") and f.endswith(".npz")])
                if n >= KILL_AFTER_CKPTS:
                    break
                if victim.poll() is not None:
                    raise AssertionError(
                        f"victim exited (rc={victim.returncode}) before "
                        f"{KILL_AFTER_CKPTS} checkpoints appeared")
                if time.time() > deadline:
                    raise AssertionError(
                        "timed out waiting for victim checkpoints")
                time.sleep(0.05)
            victim.send_signal(signal.SIGKILL)
            victim.wait(timeout=30)
        finally:
            if victim.poll() is None:
                victim.kill()
        assert victim.returncode == -signal.SIGKILL, \
            f"victim should die by SIGKILL, rc={victim.returncode}"
        print(f"[crash-smoke] killed at {n} checkpoints "
              f"(rc={victim.returncode})")

        print("[crash-smoke] resume subprocess")
        rc = subprocess.run(_service_cmd(tmp, resume=True), env=env,
                            cwd=REPO, timeout=TIMEOUT).returncode
        assert rc == 0, f"resume run failed (rc={rc})"

        g, merges, trace = _final_state(tmp)
        resumes = sum(1 for r in trace if r["kind"] == "resume")

        assert resumes >= 1, "resumed run recorded no resume event"
        first_diff = next((i for i, (x, y) in
                           enumerate(zip(merges, ref_merges)) if x != y),
                          "length")
        assert merges == ref_merges, (
            f"resumed merge trace diverged: {len(merges)} vs "
            f"{len(ref_merges)} records; first diff at {first_diff}")
        err = float(np.abs(g - ref_g).max())
        print(f"[crash-smoke] trace match ({len(merges)} merges), "
              f"model_err={err:.2e}")
        assert err <= 1e-6, f"final model diverged: {err}"
        print("[crash-smoke] OK")
    finally:
        shutil.rmtree(ref_dir, ignore_errors=True)
        shutil.rmtree(tmp, ignore_errors=True)


if __name__ == "__main__":
    main()
