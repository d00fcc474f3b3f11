"""Model FLOP/s utilization of the training window: forward and backward
operations of every step (recomputation not counted, ``chipbench/counts.py``)
over the window's seconds and the chip's bf16 peak."""
from chipbench import counts


def read(ctx):
    if ctx["kind"] != "train" or not ctx["steps"]:
        return None
    flops = ctx["steps"] * counts.train_step_flops(ctx["conf"], ctx["batch"],
                                                   ctx["seq_len"])
    return flops / ctx["window_s"] / ctx["peak"]["bf16_flops_per_s"] * 100
