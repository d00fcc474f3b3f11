"""Share of the traced training steps in which no operation ran on the
device (``chipbench/trace_reduce.py``)."""


def read(ctx):
    trace = ctx.get("trace") if ctx["kind"] == "train" else None
    if not trace or not trace["window_s"]:
        return None
    return (1 - trace["busy_s"] / trace["window_s"]) * 100
