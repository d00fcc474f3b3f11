"""The serving engine's own spans and programs in a traced run's profiler
trace.

The engine writes each of its regions (``repro.obs.Region``) into the
profiler's trace as a host ``TraceAnnotation`` with its attributes as
metadata, on the clock of the device operations:

* ``engine.iter`` one pass of the serve loop, holding ``engine.schedule``
  and, per step, ``engine.{prefill,decode}.{prepare,device,commit}``;
  ``engine.decode.device`` carries ``batch`` (live rows) and ``bucket``;
* ``engine.queue`` one request from admission to the start of the prefill
  that first serves it; one that never reached a prefill has no ``iter``.

The device plane's ``XLA Modules`` line names each program run:
``jit_engine_decode(<hash>)``, ``jit_engine_prefill(<hash>)``.

The per-layer readers of the serving cell take :func:`readings` of the
newest trace this process wrote.  A program without these spans or names
yields None for each reading, never an error.  Run as a script on a trace
directory, it prints the readings, device time per program and the device
idle time split by engine span::

    python chipbench/engine_trace.py results/chipbench/trace/<cell>
"""
from __future__ import annotations

import json
import sys
import time
from functools import lru_cache
from pathlib import Path
from typing import Dict, List, Optional, Tuple

if __name__ == "__main__":
    sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from chipbench import common, trace_reduce  # noqa: E402

PREFIX = "engine."
QUEUE = "engine.queue"
MODULES_LINE = "XLA Modules"
TRACE_DIR = ("results", "chipbench", "trace")

#: (start_ns, end_ns, name, metadata)
Span = Tuple[float, float, str, Dict[str, int]]


def engine_spans(pd, lo: float, hi: float) -> List[Span]:
    """Every ``engine.*`` host span that lies inside ``[lo, hi]``."""
    out = []
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if (e.name.startswith(PREFIX) and e.start_ns >= lo
                        and e.end_ns <= hi):
                    out.append((e.start_ns, e.end_ns, e.name, dict(e.stats)))
    return out


def module_times(pd, lo: float, hi: float) -> Dict[str, List[float]]:
    """Per program name (the hash dropped), the device seconds of each run
    on a device plane's ``XLA Modules`` line that starts inside
    ``[lo, hi]``."""
    out: Dict[str, List[float]] = {}
    for plane in pd.planes:
        if not plane.name.startswith("/device:TPU:"):
            continue
        for line in plane.lines:
            if line.name != MODULES_LINE:
                continue
            for e in line.events:
                if lo <= e.start_ns < hi:
                    name = e.name.split("(", 1)[0]
                    out.setdefault(name, []).append(e.duration_ns / 1e9)
    return out


def partition(spans: List[Tuple[float, float, str]], lo: float, hi: float
              ) -> List[Tuple[float, float, Optional[str]]]:
    """``[lo, hi]`` cut into consecutive segments, each named by the
    innermost of ``spans`` over it (None where no span is).  The spans nest
    (regions close LIFO on one thread), so one sweep with a stack does."""
    out: List[Tuple[float, float, Optional[str]]] = []
    stack: List[Tuple[float, float, str]] = []
    t = lo

    def emit(end: float) -> None:
        nonlocal t
        end = min(end, hi)
        if end > t:
            out.append((t, end, stack[-1][2] if stack else None))
            t = end

    for span in sorted(spans, key=lambda s: (s[0], -s[1])):
        while stack and stack[-1][1] <= span[0]:
            emit(stack[-1][1])
            stack.pop()
        emit(span[0])
        stack.append(span)
    while stack:
        emit(stack[-1][1])
        stack.pop()
    emit(hi)
    return out


def idle_by_span(pd, lo: float, hi: float, prefix: str = PREFIX
                 ) -> Dict[str, float]:
    """Device idle seconds inside ``[lo, hi]`` (per device, averaged over
    the devices that ran anything), each gap split among the innermost
    host spans named ``prefix*`` that it overlaps (``engine.queue`` is a
    request's wait, not host work, and is left out).  A part under none of
    them takes the name ``trace_reduce.name_gap`` gives it (``engine_host``,
    ``traffic``, ``host``), as the breakdown's idle gaps have."""
    per_device = trace_reduce.device_ops(pd)
    if not per_device:
        return {}
    bench = trace_reduce.host_spans(pd)
    segments = partition([(s, e, n) for s, e, n, _ in engine_spans(pd, lo, hi)
                          if n.startswith(prefix) and n != QUEUE], lo, hi)
    out: Dict[str, float] = {}
    for events in per_device.values():
        busy = trace_reduce.union([(max(s, lo), min(e, hi))
                                   for s, e, _ in events if e > lo and s < hi])
        i = 0
        for g0, g1 in trace_reduce.gaps(busy, lo, hi):
            while segments[i][1] <= g0:
                i += 1
            j = i
            while j < len(segments) and segments[j][0] < g1:
                s0, s1, name = segments[j]
                part = (max(g0, s0), min(g1, s1))
                if name is None:
                    name = trace_reduce.name_gap(part, bench)
                out[name] = out.get(name, 0.0) + (part[1] - part[0]) / 1e9
                j += 1
    return {k: v / len(per_device) for k, v in
            sorted(out.items(), key=lambda kv: -kv[1])}


def window(pd) -> Optional[Tuple[float, float]]:
    """The traced window: the extent of the ``chipbench.window`` spans."""
    spans = [(s, e) for s, e, n in trace_reduce.host_spans(pd)
             if n == trace_reduce.WINDOW]
    if not spans:
        return None
    return min(s for s, _ in spans), max(e for _, e in spans)


def engine_readings(spans: List[Span], modules: Dict[str, List[float]]
                    ) -> Dict[str, Optional[float]]:
    """The four serving readings, each None where its spans or program are
    missing from the trace."""
    out: Dict[str, Optional[float]] = {
        "queue_wait_p90_ms": None, "host_ms_per_iter": None,
        "decode_bucket_fill": None, "decode_device_ms": None,
    }
    iters = [e - s for s, e, n, _ in spans if n == "engine.iter"]
    device = sum(e - s for s, e, n, _ in spans if n.endswith(".device"))
    if iters:
        out["host_ms_per_iter"] = (sum(iters) - device) / len(iters) / 1e6
    waits = [e - s for s, e, n, m in spans if n == QUEUE and "iter" in m]
    if waits:
        out["queue_wait_p90_ms"] = common.percentile(waits, 90) / 1e6
    steps = [m for _, _, n, m in spans if n == "engine.decode.device"]
    run = sum(m["bucket"] for m in steps)
    if run:
        out["decode_bucket_fill"] = sum(m["batch"] for m in steps) / run * 100
    decode = modules.get("jit_engine_decode")
    if decode:
        out["decode_device_ms"] = sum(decode) / len(decode) * 1e3
    return out


def this_run_trace(root: Path) -> Optional[Path]:
    """The newest trace under the checkout's trace directory that this
    process wrote (readers run in the process that traced), or None."""
    from chipbench.run import process_age_s

    since = time.time() - process_age_s() - 1.0
    found = [(p.stat().st_mtime, p)
             for p in Path(root, *TRACE_DIR).rglob("*.xplane.pb")]
    found = [(t, p) for t, p in found if t >= since]
    return max(found)[1] if found else None


@lru_cache(maxsize=1)
def _readings(path: str, mtime_ns: int) -> Dict[str, Optional[float]]:
    pd = trace_reduce.load(Path(path))
    bounds = window(pd)
    if bounds is None:
        return engine_readings([], {})
    return engine_readings(engine_spans(pd, *bounds), module_times(pd, *bounds))


def readings(ctx, root: Path) -> Dict[str, Optional[float]]:
    """The serving readings of this run's traced window (all None for a
    run that is not serving or wrote no trace)."""
    path = this_run_trace(root) if ctx.get("kind") == "serve" else None
    if path is None:
        return engine_readings([], {})
    return _readings(str(path), path.stat().st_mtime_ns)


def report(directory: Path) -> Dict[str, object]:
    """Everything this module reads from the newest trace under
    ``directory``, with the host time split into its parts."""
    pd = trace_reduce.load(trace_reduce.find_trace(directory))
    bounds = window(pd)
    if bounds is None:
        raise SystemExit(f"no {trace_reduce.WINDOW} span in the trace")
    lo, hi = bounds
    spans = engine_spans(pd, lo, hi)
    modules = module_times(pd, lo, hi)
    by_name: Dict[str, float] = {}
    count: Dict[str, int] = {}
    for s, e, n, m in spans:
        if n == QUEUE and "iter" not in m:
            n = QUEUE + " (never served)"
        by_name[n] = by_name.get(n, 0.0) + (e - s) / 1e9
        count[n] = count.get(n, 0) + 1
    reduced = trace_reduce.reduce(pd)
    return {
        "window_s": (hi - lo) / 1e9,
        "busy_s": reduced["busy_s"] if reduced else None,
        "readings": engine_readings(spans, modules),
        "span_s": {n: [count[n], v] for n, v in sorted(by_name.items())},
        "module_s": {n: [len(v), sum(v)] for n, v in sorted(modules.items())},
        "idle_by_span_s": idle_by_span(pd, lo, hi),
    }


if __name__ == "__main__":
    if len(sys.argv) != 2:
        raise SystemExit(__doc__.strip().splitlines()[-1].strip())
    print(json.dumps(report(Path(sys.argv[1])), indent=1))
