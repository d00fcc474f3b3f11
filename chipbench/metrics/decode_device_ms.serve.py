"""Mean device time of one run of the decode program in the traced window:
the ``jit_engine_decode`` events of the device's ``XLA Modules`` line
(``chipbench/engine_trace.py``); at most ``decode_step_ms.serve``, which
is the host clock around the same runs."""
from pathlib import Path

from chipbench import engine_trace


def read(ctx):
    return engine_trace.readings(ctx, Path(__file__).resolve().parents[2])["decode_device_ms"]
