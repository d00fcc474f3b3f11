"""Share of the HBM roofline the window's decode steps reach: the bytes each
step needs (every bf16 weight once, and the cached keys and values of each
real row's context, ``chipbench/counts.py``) at the chip's peak bandwidth,
over the steps' measured time.  The host clock around a step's
``block_until_ready`` is at least the device's time, so this cannot pass
100% unless the bytes are counted too high."""
from chipbench import counts


def read(ctx):
    if ctx["kind"] != "serve" or not ctx["stats"]["decode_s"]:
        return None
    need = sum(counts.decode_step_bytes(ctx["conf"], s["contexts"])
               for s in ctx["decode_spans"])
    return need / ctx["peak"]["hbm_bytes_per_s"] / ctx["stats"]["decode_s"] * 100
