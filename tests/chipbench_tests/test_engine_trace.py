"""CPU tests of the serving engine's spans and programs in the profiler's
trace (``chipbench/engine_trace.py``) and of the per-layer readers that
read them.

    JAX_PLATFORMS=cpu PYTHONPATH=src python -m pytest -q tests/chipbench_tests
"""
from __future__ import annotations

import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from chipbench import engine_trace  # noqa: E402
from test_chipbench import SERVE, run_cell, tiny_root  # noqa: E402,F401


def _ev(meta, start, dur, stats=()):
    st = "".join(f"stats {{ metadata_id: {k} int64_value: {v} }} "
                 for k, v in stats)
    return (f"events {{ metadata_id: {meta} offset_ps: {start * 1000} "
            f"duration_ps: {dur * 1000} {st}}}")


def _plane(pid, name, lines, metas, stat_metas=None):
    body = "".join(
        f'lines {{ id: {i} name: "{ln}" timestamp_ns: 0 {" ".join(evs)} }} '
        for i, (ln, evs) in enumerate(lines))
    md = "".join(f'event_metadata {{ key: {k} value {{ id: {k} name: "{v}" }} }} '
                 for k, v in metas.items())
    sm = "".join(f'stat_metadata {{ key: {k} value {{ id: {k} name: "{v}" }} }} '
                 for k, v in (stat_metas or {}).items())
    return f'planes {{ id: {pid} name: "{name}" {body} {md} {sm} }}'


def _hand_trace():
    """A window [0, 200) ns.  Two passes of the serve loop, [10, 90) and
    [100, 190), each a schedule, a decode device region and a commit;
    two programs per pass on the device, one ``jit_engine_decode`` inside
    each device region; two queue spans, one never served."""
    from jax.profiler import ProfileData

    dev = _plane(1, "/device:TPU:0", [
        ("XLA Modules", [_ev(1, 42, 30), _ev(2, 80, 5), _ev(1, 132, 40),
                         _ev(2, 250, 5)]),
        ("XLA Ops", [_ev(3, 42, 30), _ev(3, 80, 5), _ev(3, 132, 40)]),
    ], {1: "jit_engine_decode(123)", 2: "jit__insert_rows(9)", 3: "fusion"})
    host = _plane(2, "/host:CPU", [("python", [
        _ev(1, 0, 200),
        _ev(2, 4, 192),
        _ev(3, 10, 80, [(1, 0)]), _ev(4, 10, 20, [(1, 0)]),
        _ev(5, 30, 50, [(1, 0), (2, 3), (3, 4)]), _ev(6, 80, 10, [(1, 0)]),
        _ev(3, 100, 90, [(1, 1)]), _ev(4, 100, 25, [(1, 1)]),
        _ev(5, 125, 50, [(1, 1), (2, 2), (3, 2)]), _ev(6, 175, 15, [(1, 1)]),
        _ev(7, 12, 16, [(4, 7), (1, 0)]), _ev(7, 105, 40, [(4, 8)]),
    ])], {1: "chipbench.window", 2: "chipbench.engine_host", 3: "engine.iter",
          4: "engine.schedule", 5: "engine.decode.device",
          6: "engine.decode.commit", 7: "engine.queue"},
        {1: "iter", 2: "batch", 3: "bucket", 4: "rid"})
    return ProfileData.from_text_proto(dev + " " + host)


def test_engine_readings_by_hand():
    pd = _hand_trace()
    lo, hi = engine_trace.window(pd)
    assert (lo, hi) == (0, 200)
    spans = engine_trace.engine_spans(pd, lo, hi)
    assert len(spans) == 10
    modules = engine_trace.module_times(pd, lo, hi)
    # the run starting at 250 ns lies outside the window
    assert modules == {"jit_engine_decode": [pytest.approx(30e-9),
                                             pytest.approx(40e-9)],
                       "jit__insert_rows": [pytest.approx(5e-9)]}
    r = engine_trace.engine_readings(spans, modules)
    # passes 80 + 90 ns, device regions 50 + 50 ns: (170 - 100) / 2 ns
    assert r["host_ms_per_iter"] == pytest.approx(35e-6)
    # one served queue span (16 ns); the one with no iter never reached a prefill
    assert r["queue_wait_p90_ms"] == pytest.approx(16e-6)
    assert r["decode_bucket_fill"] == pytest.approx((3 + 2) / (4 + 2) * 100)
    assert r["decode_device_ms"] == pytest.approx(35e-6)


def test_idle_by_span_by_hand():
    """Busy [42,72) [80,85) [132,172) of [0,200): 125 ns idle, split among
    the innermost engine spans each gap overlaps (the queue span is not
    host work): [0,42) is 10 outside the passes (inside the harness's
    ``engine_host`` [4,196)), 20 schedule, 12 device region; [72,80) 8
    device; [85,132) 5 commit, 10 between passes, 25 schedule, 7 device;
    [172,200) 3 device, 15 commit, 10 after the passes."""
    pd = _hand_trace()
    split = engine_trace.idle_by_span(pd, 0, 200)
    assert split == pytest.approx({"engine.schedule": 45e-9,
                                   "engine.decode.device": 30e-9,
                                   "engine_host": 30e-9,
                                   "engine.decode.commit": 20e-9})
    assert sum(split.values()) == pytest.approx(125e-9)


def test_idle_outside_engine_spans_takes_the_harness_name():
    """A gap under no engine span is named as the harness names it."""
    pd = _hand_trace()
    split = engine_trace.idle_by_span(pd, 0, 200, prefix="nothing.")
    assert split == pytest.approx({"engine_host": 125e-9})


def test_partition_matches_a_scan():
    """Each segment is named by the shortest span over it, as a scan of
    every span at the segment's midpoint finds, and the segments tile the
    interval."""
    spans = [(0, 100, "a"), (10, 40, "b"), (15, 20, "c"), (50, 90, "d"),
             (120, 130, "e"), (130, 135, "f")]
    segments = engine_trace.partition(spans, -5, 150)

    def scan(p):
        around = [s for s in spans if s[0] <= p <= s[1]]
        return min(around, key=lambda s: s[1] - s[0])[2] if around else None

    assert [n for _, _, n in segments] == [scan((a + b) / 2) for a, b, _ in segments]
    assert segments[0][0] == -5 and segments[-1][1] == 150
    assert all(a[1] == b[0] for a, b in zip(segments, segments[1:]))
    assert [n for _, _, n in segments] == [
        None, "a", "b", "c", "b", "a", "d", "a", None, "e", "f", None]


def test_serve_cell_reads_engine_metrics(tiny_root):
    """The tiny serving cell's traced run reads the three span metrics on
    the CPU; the device metric needs a TPU plane and reads nothing."""
    out = run_cell(tiny_root, SERVE, trace=1)
    m = {k: v["value"] for k, v in out["metrics"].items()}
    assert m["queue_wait_p90_ms.serve"] > 0
    assert m["host_ms_per_iter.serve"] > 0
    assert 0 < m["decode_bucket_fill.serve"] <= 100
    assert "decode_device_ms.serve" not in m
    assert out["metrics"]["decode_bucket_fill.serve"]["unit"] == "%"


def test_readings_of_no_serving_trace_are_none(tmp_path):
    """A run that is not serving, or wrote no trace, reads nothing."""
    for ctx in ({"kind": "train"}, {"kind": "serve"}):
        assert set(engine_trace.readings(ctx, tmp_path).values()) == {None}
