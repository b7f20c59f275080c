"""Share of the service's own run time spent in checkpoints over the
window (%): the change of ``HFLService.summary()``'s ``ckpt_wall`` over
that of ``run_wall``, the program's host clocks around a synchronous
save."""


def read(ctx):
    if ctx["run_wall"] <= 0.0:
        return None
    return 100.0 * ctx["ckpt_wall"] / ctx["run_wall"]
