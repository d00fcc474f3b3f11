"""Share of the decode rows run that were live requests in the traced
window: the ``batch`` over the pow2 ``bucket`` of every
``engine.decode.device`` span (``chipbench/engine_trace.py``)."""
from pathlib import Path

from chipbench import engine_trace


def read(ctx):
    return engine_trace.readings(ctx, Path(__file__).resolve().parents[2])["decode_bucket_fill"]
