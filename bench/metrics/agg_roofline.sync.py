"""Share of the HBM roofline that the aggregation kernels reach (%): the
least bytes eqs. 6 (b times) and 10 need per round, over the kernels'
device time, over the HBM peak.  Bandwidth bounds these kernels: their
FLOPs are two per byte read at most."""
from bench.yardstick import counters

ROUND_PROGRAM = "jit_cloud_round"


def read(ctx):
    kernel_s = ctx["trace"].agg_kernel_s.get(ROUND_PROGRAM, 0.0)
    if kernel_s <= 0.0 or not ctx["rounds"]:
        return None
    need = counters.sync_round_agg_min_bytes(ctx["cfg"], ctx["model"])
    return (100.0 * need * ctx["rounds"] / ctx["peaks"]["hbm_bytes_per_s"]
            / kernel_s)
