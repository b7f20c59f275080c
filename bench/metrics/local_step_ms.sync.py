"""Device ms per round of the cloud-round program less its aggregation
kernels: the vmapped local GD steps and their glue."""
ROUND_PROGRAM = "jit_cloud_round"


def read(ctx):
    t = ctx["trace"]
    if ROUND_PROGRAM not in t.module_s or not ctx["rounds"]:
        return None
    step = t.module_s[ROUND_PROGRAM] - t.agg_kernel_s.get(ROUND_PROGRAM, 0.0)
    return 1e3 * step / ctx["rounds"]
