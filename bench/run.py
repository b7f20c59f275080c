"""One benchmark run of one cell, on the chips of this machine.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Builds everything from ``--seed``, warms up (set-up), measures for
``--seconds``, checks what the measured path produced against a plain
reference, and prints one JSON line last on standard output.  Refuses to
run (exit 2, no result) unless JAX's first device is a TPU.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

from bench import harness  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--trace-dir", default=None,
                    help="keep the profiler trace here (default: a "
                         "temporary directory, removed after reading)")
    return harness.main(ap.parse_args(argv), T_START)


if __name__ == "__main__":
    sys.exit(main())
