"""Reduce a JAX profiler trace to device busy time, top operations and
named idle gaps.

* Device operations are the events of each device plane's ``XLA Ops`` line
  (``/device:TPU:<n>``).  Busy time is the union of their intervals inside
  the traced window, so overlapping operations count once; it is averaged
  over the devices that ran anything.
* The traced window is the host span ``chipbench.window`` that the harness
  opens around its window in a traced run (a
  ``jax.profiler.TraceAnnotation``); without one, the extent of the device
  operations.
* An idle gap is an interval of the window in which no operation ran.  It is
  named by the innermost ``chipbench.*`` host span around its midpoint,
  which says what the host was doing: making traffic, inside the program's
  serve loop, between train steps.
"""
from __future__ import annotations

from pathlib import Path
from typing import Dict, List, Optional, Tuple

HOST_PREFIX = "chipbench."
WINDOW = "chipbench.window"
OPS_LINE = "XLA Ops"

Interval = Tuple[int, int]


def start(directory: Path) -> None:
    """Start the profiler into an emptied ``directory``."""
    import shutil

    import jax

    shutil.rmtree(directory, ignore_errors=True)
    Path(directory).mkdir(parents=True)
    # no Python function tracing: it slows the host loop being measured
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 1
    options.enable_hlo_proto = False
    jax.profiler.start_trace(str(directory), profiler_options=options)


def stop(directory: Path) -> Optional[Dict[str, object]]:
    """Stop the profiler and reduce what it wrote."""
    import time

    import jax

    t0 = time.perf_counter()
    jax.profiler.stop_trace()
    t1 = time.perf_counter()
    out = reduce(load(find_trace(directory)))
    print(f"trace: stopped in {t1 - t0:.1f} s, reduced in "
          f"{time.perf_counter() - t1:.1f} s", flush=True)
    return out


def find_trace(directory: Path) -> Path:
    found = sorted(Path(directory).rglob("*.xplane.pb"))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {directory}")
    return found[-1]


def load(path: Path):
    from jax.profiler import ProfileData

    return ProfileData.from_file(str(path))


def device_ops(pd) -> Dict[str, List[Tuple[int, int, str]]]:
    """Per device plane: (start_ns, end_ns, op name) of every operation."""
    out = {}
    for plane in pd.planes:
        if not plane.name.startswith("/device:TPU:"):
            continue
        for line in plane.lines:
            if line.name == OPS_LINE:
                events = [(e.start_ns, e.end_ns, op_name(e.name))
                          for e in line.events]
                if events:
                    out[plane.name] = events
    return out


def op_name(hlo: str) -> str:
    """``fusion.177`` of ``%fusion.177 = bf16[...] fusion(...)``."""
    return hlo.split(" = ", 1)[0].lstrip("%")


def host_spans(pd) -> List[Tuple[int, int, str]]:
    spans = []
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(HOST_PREFIX):
                    spans.append((e.start_ns, e.end_ns, e.name))
    return spans


def union(intervals: List[Interval]) -> List[Interval]:
    merged: List[List[int]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def gaps(busy: List[Interval], lo: int, hi: int) -> List[Interval]:
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def name_gap(gap: Interval, spans: List[Tuple[int, int, str]]) -> str:
    mid = (gap[0] + gap[1]) / 2
    around = [s for s in spans if s[0] <= mid <= s[1] and s[2] != WINDOW]
    if not around:
        return "host"
    start, end, name = min(around, key=lambda s: s[1] - s[0])
    return name[len(HOST_PREFIX):]


def reduce(pd, top: int = 10) -> Optional[Dict[str, object]]:
    """``busy_s``, ``window_s``, the ``top`` operations by device time and
    the ``top`` longest idle gaps; None when no device operation ran."""
    per_device = device_ops(pd)
    if not per_device:
        return None
    spans = host_spans(pd)
    windows = [(s, e) for s, e, name in spans if name == WINDOW]
    if windows:
        lo, hi = min(s for s, _ in windows), max(e for _, e in windows)
    else:
        lo = min(s for ev in per_device.values() for s, _, _ in ev)
        hi = max(e for ev in per_device.values() for _, e, _ in ev)
    busy_ns, op_ns = [], {}
    idle: List[Tuple[str, int]] = []
    for events in per_device.values():
        inside = [(max(s, lo), min(e, hi), n) for s, e, n in events
                  if e > lo and s < hi]
        for s, e, n in inside:
            op_ns[n] = op_ns.get(n, 0) + (e - s)
        busy = union([(s, e) for s, e, _ in inside])
        busy_ns.append(sum(e - s for s, e in busy))
        idle += [(name_gap(g, spans), g[1] - g[0]) for g in gaps(busy, lo, hi)]
    ops = sorted(op_ns.items(), key=lambda kv: -kv[1])[:top]
    idle = sorted(idle, key=lambda kv: -kv[1])[:top]
    return {
        "busy_s": sum(busy_ns) / len(busy_ns) / 1e9,
        "window_s": (hi - lo) / 1e9,
        "device_ops": [[n, ns / 1e9] for n, ns in ops],
        "idle_gaps": [[n, ns / 1e9] for n, ns in idle],
    }
