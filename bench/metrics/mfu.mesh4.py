"""Share of the cell's chips' bf16 peak that the round's required
training FLOPs reach over the traced window's seconds per round (%): the
whole step's share of a multi-chip cell.  Every real UE row's local steps
count once (``counters.sync_round_train_flops``), padding rows not at
all; the peak is one chip's times the chips (``chips``, from the driver).
The local step runs at the TPU's default matmul precision, one bf16
pass, so the bf16 peak is the ceiling."""
from bench.yardstick import counters


def read(ctx):
    chips = ctx.get("chips")
    if not chips or not ctx.get("round_s"):
        return None
    flops = counters.sync_round_train_flops(ctx["cfg"], ctx["model"])
    peak = chips * ctx["peaks"]["bf16_flops_per_s"]
    return 100.0 * flops / ctx["round_s"] / peak
