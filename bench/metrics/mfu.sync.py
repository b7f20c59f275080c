"""Share of the chip's bf16 peak that the round's required training
FLOPs reach over the traced window's seconds per round (%).  The local
step runs at the TPU's default matmul precision, one bf16 pass, so the
bf16 peak is the ceiling."""
from bench.yardstick import counters


def read(ctx):
    flops = counters.sync_round_train_flops(ctx["cfg"], ctx["model"])
    return 100.0 * flops / ctx["round_s"] / ctx["peaks"]["bf16_flops_per_s"]
