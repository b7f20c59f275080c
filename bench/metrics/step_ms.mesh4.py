"""Device ms per round of the cloud-round program on the slowest chip,
less that chip's collectives and aggregation kernels: the sharded local
GD steps and their glue.  Per chip from the driver (``chip_round_s``,
``chip_collective_s``, ``chip_agg_kernel_s``), never summed over chips.
Left out when no chip shows a collective (the round's psum would then be
counted as step)."""


def read(ctx):
    chips = ctx.get("chip_round_s")
    if not chips or not any(chips) or not ctx["rounds"]:
        return None
    if not any(ctx["chip_collective_s"]):
        return None
    step = [r - c - k for r, c, k in zip(chips, ctx["chip_collective_s"],
                                         ctx["chip_agg_kernel_s"])]
    return 1e3 * max(step) / ctx["rounds"]
