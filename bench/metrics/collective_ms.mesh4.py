"""Device ms per round of the cloud-round program's collective
instructions on the chip that spends most in them (all-reduce,
all-gather, reduce-scatter, collective-permute, all-to-all, with their
``-start``/``-done`` halves).  Per chip from the driver
(``chip_collective_s``), never summed over chips.  The mesh round always
holds the eq. 10 psum, so a trace in which no chip shows a collective
was not read right: the metric is then left out, not read as 0."""


def read(ctx):
    chips = ctx.get("chip_round_s")
    if not chips or not any(chips) or not ctx["rounds"]:
        return None
    if not any(ctx["chip_collective_s"]):
        return None
    return 1e3 * max(ctx["chip_collective_s"]) / ctx["rounds"]
