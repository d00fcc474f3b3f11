"""Mean host time of one pass of the engine's serve loop in the traced
window: the ``engine.iter`` spans' time outside their
``engine.*.device`` regions (dispatch through ``block_until_ready``), over
the passes (``chipbench/engine_trace.py``)."""
from pathlib import Path

from chipbench import engine_trace


def read(ctx):
    return engine_trace.readings(ctx, Path(__file__).resolve().parents[2])["host_ms_per_iter"]
