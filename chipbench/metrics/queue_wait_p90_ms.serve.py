"""Nearest-rank 90th percentile of the traced window's queue waits: each
request's ``engine.queue`` span, from its admission to the start of the
prefill that first serves it, on the profiler's clock
(``chipbench/engine_trace.py``)."""
from pathlib import Path

from chipbench import engine_trace


def read(ctx):
    return engine_trace.readings(ctx, Path(__file__).resolve().parents[2])["queue_wait_p90_ms"]
