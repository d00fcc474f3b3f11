"""Model FLOP/s utilization of the whole serving window: the operations of
every prompt prefilled and every token decoded in the window
(``chipbench/counts.py``) over the window's seconds and the chip's bf16
peak."""
from chipbench import counts


def read(ctx):
    if ctx["kind"] != "serve" or not ctx["decode_spans"]:
        return None
    conf = ctx["conf"]
    flops = sum(s["batch"] * counts.prefill_flops(conf, s["plen"])
                for s in ctx["prefill_spans"])
    flops += sum(counts.decode_flops(conf, c)
                 for s in ctx["decode_spans"] for c in s["contexts"])
    return flops / ctx["window_s"] / ctx["peak"]["bf16_flops_per_s"] * 100
