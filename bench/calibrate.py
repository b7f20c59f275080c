"""Readings that the correctness limits are set from, on the chip.

    python bench/calibrate.py --workload <cell> --seeds 1,2,3 \
        [--control-seeds 1,2,3] [--faults half_batch,... --fault-seeds 1,2,3] \
        [--seconds 10] [--out readings.jsonl]

For each seed, builds the cell as a run does and prints the numbers that
decide ``correct`` (``program``); for each control seed it also prints
them with the reference computed in bfloat16 put in the program's place
(``control``), which a sound limit must reject, and those of the
program with each of ``--faults`` planted (``bench/faults.py``).  Sync cells need no
measured window; the service cell runs one of ``--seconds``.  All seeds
run in this one process, so that they share its compiled programs.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from bench import harness  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--faults", default="",
                    help="plant each of these bench.faults in turn and "
                         "read the numbers with it, on the fault seeds")
    ap.add_argument("--fault-seeds", default="")
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    seeds = [int(s) for s in args.seeds.split(",") if s]
    controls = {int(s) for s in args.control_seeds.split(",") if s}
    fault_seeds = {int(s) for s in args.fault_seeds.split(",") if s}
    faults = [f for f in args.faults.split(",") if f]

    reg = harness.Registry(ROOT)
    w = reg.cell(args.workload)
    cfg = reg.config(w["config"])
    traffic = reg.traffic(w["traffic"])
    try:
        harness.require_chip(int(w["chips"]))
    except harness.NoChip as e:
        print(f"calibrate: {e}", file=sys.stderr)
        return 2
    harness.use_compile_cache(ROOT)
    sys.path.insert(0, os.path.join(ROOT, "src"))
    driver = reg.driver(traffic["driver"])
    model = reg.model(cfg["model"])
    out = open(args.out, "a") if args.out else None
    try:
        for seed in seeds:
            t0 = time.perf_counter()
            cell = harness.Cell(name=args.workload, cfg=cfg, traffic=traffic,
                                model=model, seed=seed, chips=int(w["chips"]))
            session = harness.Session(t0, args.seconds, None)
            res = driver.calibrate(
                cell, session, seed in controls,
                faults=faults if seed in fault_seeds else ())
            line = json.dumps(dict(workload=args.workload, seed=seed,
                                   seconds=time.perf_counter() - t0, **res))
            print(line, flush=True)
            if out:
                out.write(line + "\n")
                out.flush()
    finally:
        if out:
            out.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
