"""Observability-layer tests (ISSUE 10 acceptance, docs/observability.md).

Covers: the tracer primitives and deterministic Perfetto export, the
event-log truncation tombstone (local trim + lattice merge laws), the
metrics registry / Prometheus text round-trip, the ``as_metrics()``
adapters, byte-identical engine traces across two seeded-chaos runs on
the ``TickTimer`` clock, span-nesting laminarity under the background
tuner's worker thread, the retire-uniqueness timeline property (one
terminal ``engine.retire`` instant per admitted rid, matching its
``RequestResult.status``), and the explain report's decision chain.
"""
import json
import threading

import jax
import jax.numpy as jnp
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ModuleNotFoundError:  # property section skips, unit tests still run
    given = None

from repro.configs import get_config
from repro.core import (
    ATRegion,
    AutotunedOp,
    BasicParams,
    KernelSpec,
    ParamSpace,
    PerfParam,
    TrafficClass,
    TuningDB,
)
from repro.core.db import EVENT_LIMIT, TOMBSTONE_KIND
from repro.data import synthetic_requests
from repro.models import init_params, param_specs
from repro.obs import (
    MetricsRegistry,
    TickTimer,
    Tracer,
    current_tracer,
    parse_prometheus,
    snapshot_stats,
    use_tracer,
)
from repro.obs.explain import db_summary, explain_fingerprint, render_report
from repro.runtime import BackgroundTuner, ChaosInjector, StreamingEngine
from repro.runtime.engine import REQUEST_STATUSES

KEY = jax.random.PRNGKey(0)
SMOKE = get_config("tinyllama-1.1b", smoke=True)
MAX_LEN = 16


@pytest.fixture(scope="module")
def smoke_params():
    return init_params(KEY, param_specs(SMOKE))


# ---------------------------------------------------------------------------
# Tracer primitives + deterministic export
# ---------------------------------------------------------------------------


def test_tick_timer_is_deterministic_and_thread_safe():
    t = TickTimer(0.5)
    assert [t() for _ in range(3)] == [0.5, 1.0, 1.5]
    t2 = TickTimer(0.5)
    out = []
    threads = [
        threading.Thread(target=lambda: out.append(t2())) for _ in range(8)
    ]
    for th in threads:
        th.start()
    for th in threads:
        th.join()
    # every call got a distinct tick regardless of interleaving
    assert sorted(out) == [pytest.approx(0.5 * i) for i in range(1, 9)]


def test_span_nesting_and_attrs():
    tr = Tracer(clock=TickTimer(1.0))
    with tr.span("outer", cat="t", track="main") as attrs:
        with tr.span("inner", cat="t", track="main"):
            pass
        attrs["verdict"] = "ok"  # body can attach results before close
    evs = tr.events()
    by_name = {e["name"]: e for e in evs}
    # inner closes first (LIFO) and sits inside outer's [ts, ts+dur]
    o, i = by_name["outer"], by_name["inner"]
    assert o["ts"] <= i["ts"] and i["ts"] + i["dur"] <= o["ts"] + o["dur"]
    assert o["args"]["verdict"] == "ok"


def test_ring_buffer_bounds_memory_and_counts_drops():
    tr = Tracer(capacity=4)
    for k in range(10):
        tr.instant("e", t=float(k))
    assert len(tr.events()) == 4
    assert tr.emitted == 10 and tr.dropped == 6


def test_trace_export_is_a_pure_function_of_the_event_set():
    """Same events captured in different arrival order -> same bytes."""

    def _fill(tr, order):
        for k in order:
            if k % 2:
                tr.complete("step", k * 1e-3, (k + 1) * 1e-3,
                            track=f"w{k % 3}", idx=k)
            else:
                tr.instant("mark", t=k * 1e-3, track=f"w{k % 3}", idx=k)

    a, b = Tracer(), Tracer()
    _fill(a, range(12))
    _fill(b, reversed(range(12)))
    assert a.to_json() == b.to_json()
    # and the export is well-formed for the observe CLI's validator
    doc = json.loads(a.to_json())
    for ev in doc["traceEvents"]:
        assert ev["ph"] in ("X", "i", "M")
        assert isinstance(ev["pid"], int) and isinstance(ev["tid"], int)
        if ev["ph"] != "M":
            assert isinstance(ev["ts"], int)
        if ev["ph"] == "X":
            assert isinstance(ev["dur"], int)
    # one thread_name meta event per track, tids dense from 1
    meta = [e for e in doc["traceEvents"] if e["ph"] == "M"]
    assert sorted(e["tid"] for e in meta) == [1, 2, 3]


def test_use_tracer_restores_previous():
    assert current_tracer() is None
    outer = Tracer()
    with use_tracer(outer):
        assert current_tracer() is outer
        with use_tracer(None):
            assert current_tracer() is None
        assert current_tracer() is outer
    assert current_tracer() is None


def test_nonfinite_and_exotic_attrs_stay_jsonable():
    tr = Tracer()
    tr.instant("e", t=0.0, bad=float("nan"), obj=object(), seq=(1, 2))
    ev = tr.events()[0]
    json.dumps(ev)  # must not raise
    assert ev["args"]["bad"] == "nan" and ev["args"]["seq"] == [1, 2]


# ---------------------------------------------------------------------------
# Event-log truncation tombstone (satellite: db.record_event)
# ---------------------------------------------------------------------------


def _bp(kernel="tomb"):
    return BasicParams.make(kernel=kernel)


def test_event_overflow_folds_into_tombstone():
    db = TuningDB()
    bp = _bp()
    extra = 10
    for k in range(EVENT_LIMIT + extra):
        db.record_event(bp, "noise", k=k)
    events = db.events(bp)
    assert len(events) == EVENT_LIMIT
    tomb = events[0]
    assert tomb["kind"] == TOMBSTONE_KIND
    # tombstone + survivors account for every event ever recorded
    assert tomb["count"] + (len(events) - 1) == EVENT_LIMIT + extra
    assert tomb["oldest_t"] <= tomb["newest_t"]
    # newest events survive, oldest were folded
    assert events[-1]["k"] == EVENT_LIMIT + extra - 1


def test_tombstone_accumulates_across_repeated_trims():
    db = TuningDB()
    bp = _bp()
    for k in range(EVENT_LIMIT * 3):
        db.record_event(bp, "noise", k=k)
    events = db.events(bp)
    assert len(events) == EVENT_LIMIT
    assert events[0]["kind"] == TOMBSTONE_KIND
    assert events[0]["count"] + (len(events) - 1) == EVENT_LIMIT * 3


def _overflowed_db(seed, n):
    db = TuningDB()
    bp = _bp()
    for k in range(n):
        db.record_event(bp, "noise", host=seed, k=k)
    return db, bp


def test_tombstone_merge_is_commutative_and_idempotent():
    a, bp = _overflowed_db("a", EVENT_LIMIT + 7)
    b, _ = _overflowed_db("b", EVENT_LIMIT + 3)

    def _merged(x, y):
        out = TuningDB()
        out.merge(x)
        out.merge(y)
        return out.events(bp)

    ab, ba = _merged(a, b), _merged(b, a)
    assert ab == ba  # commutative
    twice = TuningDB()
    twice.merge(a)
    twice.merge(b)
    twice.merge(b)  # idempotent: re-delivery changes nothing
    assert twice.events(bp) == ab
    # exactly one joined tombstone, pinned first; the merged union re-trims
    # so the joined count covers at least what either host had folded
    tombs = [e for e in ab if e["kind"] == TOMBSTONE_KIND]
    assert len(tombs) == 1 and ab[0]["kind"] == TOMBSTONE_KIND
    assert len(ab) <= EVENT_LIMIT
    assert tombs[0]["count"] >= max(
        a.events(bp)[0]["count"], b.events(bp)[0]["count"]
    )
    # join of *identical* logs takes max, not sum (no double-counting)
    same = TuningDB()
    same.merge(a)
    same.merge(a)
    assert same.events(bp) == a.events(bp)


# ---------------------------------------------------------------------------
# Metrics registry + Prometheus round-trip
# ---------------------------------------------------------------------------


def test_registry_prometheus_round_trip():
    reg = MetricsRegistry()
    reg.counter("req_total", help="requests").inc(3, status="ok")
    reg.counter("req_total").inc(1, status="error")
    reg.gauge("queue_depth").set(7)
    h = reg.histogram("lat_s", buckets=(0.1, 1.0))
    h.observe(0.05)
    h.observe(0.5)
    h.observe(5.0)
    text = reg.prometheus_text()
    fams = parse_prometheus(text)
    assert fams["req_total"] == [
        ({"status": "error"}, 1.0), ({"status": "ok"}, 3.0),
    ]
    assert fams["queue_depth"] == [({}, 7.0)]
    assert fams["lat_s_count"] == [({}, 3.0)]
    assert fams["lat_s_sum"] == [({}, pytest.approx(5.55))]
    buckets = {lab["le"]: v for lab, v in fams["lat_s_bucket"]}
    assert buckets == {"0.1": 1.0, "1": 2.0, "+Inf": 3.0}
    # deterministic: a second exposition is byte-identical
    assert reg.prometheus_text() == text


def test_registry_rejects_kind_clash_and_negative_counter():
    reg = MetricsRegistry()
    reg.counter("x")
    with pytest.raises(ValueError):
        reg.gauge("x")
    with pytest.raises(ValueError):
        reg.counter("x").inc(-1)


def test_register_stats_pulls_live_values():
    class Stats:
        def __init__(self):
            self.n = 0

        def as_metrics(self):
            return {"n": self.n, "flag": True}

    s = Stats()
    reg = MetricsRegistry()
    reg.register_stats("toy", s, worker="w0")
    first = parse_prometheus(reg.prometheus_text())
    s.n = 5  # mutate after registration: pull model must observe it
    second = parse_prometheus(reg.prometheus_text())
    assert first["toy_n"] == [({"worker": "w0"}, 0.0)]
    assert second["toy_n"] == [({"worker": "w0"}, 5.0)]
    assert second["toy_flag"] == [({"worker": "w0"}, 1.0)]


def test_parse_prometheus_rejects_malformed():
    for bad in ("metric{ 1", "# BOGUS comment\nm 1\nnot a line", ""):
        with pytest.raises(ValueError):
            parse_prometheus(bad)


def test_snapshot_stats_fallbacks():
    assert snapshot_stats({"a": 1, "b": "skip"}) == {"a": 1.0}

    class Plain:
        def __init__(self):
            self.x = 2
            self.name = "not-numeric"
            self._hidden = 9

    assert snapshot_stats(Plain()) == {"x": 2.0}


def test_ad_hoc_stats_all_speak_as_metrics():
    """Every stats class named in docs/observability.md flows through the
    one ``as_metrics()`` pipe with numeric-only fields."""
    from repro.fleet.coordinator import WorkerReport
    from repro.fleet.service import ClientStats
    from repro.runtime.chaos import ChaosStats
    from repro.runtime.engine import StreamStats

    for stats in (
        StreamStats(),
        ChaosStats(),
        ClientStats(),
        WorkerReport(worker=0, points=3, evaluations=3, best_cost=1.0,
                     best_point={"i": 0}, wall_s=0.1),
    ):
        snap = snapshot_stats(stats)
        assert snap, f"{type(stats).__name__} produced an empty snapshot"
        assert all(isinstance(v, float) for v in snap.values())


# ---------------------------------------------------------------------------
# Engine timelines: deterministic bytes + retire uniqueness
# ---------------------------------------------------------------------------


def _traced_run(smoke_params, reqs_seed=5, n=4, chaos_seed=11):
    """One seeded-chaos engine run with a pinned tracer on the TickTimer
    measurement clock; returns (engine, tracer, requests)."""
    reqs = synthetic_requests(
        SMOKE, n, prompt_len=3, max_new_tokens=4, seed=reqs_seed
    )
    if n >= 2:  # one malformed straggler exercises the error-retire path
        reqs[-1].max_new_tokens = MAX_LEN + 8
    tracer = Tracer(clock=TickTimer(1e-3))
    eng = StreamingEngine(
        SMOKE, smoke_params, n_blocks=3, max_len=MAX_LEN,
        queue_limit=3, default_ttl_s=30.0,
        chaos=ChaosInjector(seed=chaos_seed, step_fault_rate=0.2),
        timer=TickTimer(1e-3), tracer=tracer,
    )
    eng.serve(reqs)
    return eng, tracer, reqs


def test_engine_trace_is_byte_identical_across_runs(smoke_params):
    """ISSUE 10 acceptance: two runs of the same seeded-chaos trace on the
    virtual clock produce byte-identical Perfetto files."""
    _, tr1, _ = _traced_run(smoke_params)
    _, tr2, _ = _traced_run(smoke_params)
    assert tr1.to_json() == tr2.to_json()
    assert tr1.emitted > 0 and tr1.dropped == 0


def _retire_check(eng, tracer, reqs):
    """Exactly one terminal ``engine.retire`` instant per admitted rid,
    matching the recorded RequestResult status."""
    retires = [e for e in tracer.events() if e["name"] == "engine.retire"]
    by_rid = {}
    for e in retires:
        by_rid.setdefault(e["args"]["rid"], []).append(e["args"]["status"])
    assert set(by_rid) == set(eng.results)
    for rid, statuses in by_rid.items():
        assert len(statuses) == 1, f"rid {rid} retired {len(statuses)} times"
        assert statuses[0] == eng.results[rid].status
        assert statuses[0] in REQUEST_STATUSES
    # every admit instant has a matching terminal retire (admits that shed
    # or error later still retire exactly once — checked above)
    admits = {e["args"]["rid"] for e in tracer.events()
              if e["name"] == "engine.admit"}
    assert admits <= set(by_rid)


def test_engine_timeline_retire_uniqueness(smoke_params):
    eng, tracer, reqs = _traced_run(smoke_params)
    _retire_check(eng, tracer, reqs)


def test_engine_events_carry_timer_timestamps(smoke_params):
    """prefill/decode complete-events sit inside the serve span and never
    run backwards, and each is stamped with its device region's two timer
    reads: the engine's events are on its measurement timer."""
    _, tracer, _ = _traced_run(smoke_params)
    evs = tracer.events()
    serve = [e for e in evs if e["name"] == "engine.serve"]
    assert len(serve) == 1
    lo, hi = serve[0]["ts"], serve[0]["ts"] + serve[0]["dur"]
    steps = [e for e in evs if e["name"] in ("engine.prefill", "engine.decode")]
    assert steps
    device = {(e["ts"], e["dur"]) for e in evs if e["name"].endswith(".device")}
    for e in steps:
        assert lo <= e["ts"] and e["ts"] + e["dur"] <= hi
        assert e["dur"] >= 0
        assert (e["ts"], e["dur"]) in device
    stamped = [e for e in evs if e["name"] in
               ("engine.admit", "engine.retire", "engine.preempt")]
    assert stamped and all(lo <= e["ts"] <= hi for e in stamped)
    assert all("queue_wait_s" not in e["args"] for e in evs
               if e["name"] == "engine.admit")


def _by_iter(evs):
    iters = {e["args"]["iter"]: e for e in evs if e["name"] == "engine.iter"}
    children = {}
    for e in evs:
        if e["name"].startswith("engine.") and e["name"] not in (
                "engine.iter", "engine.queue", "engine.serve") and \
                e["ph"] == "X" and "iter" in e["args"]:
            children.setdefault(e["args"]["iter"], []).append(e)
    return iters, children


def test_engine_iter_regions_nest_lifo(smoke_params):
    """On the TickTimer clock every ``engine.iter`` holds its
    ``engine.schedule`` and, per step, ``prepare`` -> ``device`` ->
    ``commit``; the children of a pass follow one another inside it (LIFO
    on one thread: none overlaps another)."""
    eng, tracer, _ = _traced_run(smoke_params)
    iters, children = _by_iter(tracer.events())
    assert sorted(iters) == list(range(eng.stats.iterations))
    assert set(children) <= set(iters)
    kinds = set()
    for n, it in iters.items():
        kids = sorted(children.get(n, []), key=lambda e: e["ts"])
        assert kids and kids[0]["name"] == "engine.schedule"
        end = it["ts"]
        for prev, e in zip([None] + kids, kids):
            assert e["ts"] >= end, (prev, e)
            end = e["ts"] + e["dur"]
            assert end <= it["ts"] + it["dur"]
            if e["name"].endswith(".device"):
                step = e["name"].rsplit(".", 1)[0]
                assert prev["name"] == step + ".prepare"
            if e["name"].endswith(".commit"):
                assert prev["name"] == e["name"][:-len("commit")] + "device"
            kinds.add(e["name"])
    assert {"engine.prefill.prepare", "engine.prefill.device",
            "engine.prefill.commit", "engine.decode.prepare",
            "engine.decode.device", "engine.decode.commit"} <= kinds


def test_engine_queue_span_per_prefilled_request(smoke_params):
    """One ``engine.queue`` per request that reached a prefill, ending as
    the first prefill that serves it starts."""
    eng, tracer, _ = _traced_run(smoke_params)
    evs = tracer.events()
    first = {}
    for e in sorted(evs, key=lambda e: e["ts"]):
        if e["name"] == "engine.prefill":
            for rid in e["args"]["rids"]:
                first.setdefault(rid, e["ts"])
    queues = [e for e in evs if e["name"] == "engine.queue"]
    assert first and sorted(e["args"]["rid"] for e in queues) == sorted(first)
    for e in queues:
        assert e["dur"] >= 0
        assert e["ts"] + e["dur"] <= first[e["args"]["rid"]]
    assert eng.stats.queue_waits == len(queues)


def test_engine_counters_equal_span_sums(smoke_params):
    """``StreamStats``' loop counters are the sums over the engine's spans
    (TickTimer: every stamp is a whole millisecond, exact in µs)."""
    eng, tracer, _ = _traced_run(smoke_params)
    evs = tracer.events()

    def total(pred):
        return sum(e["dur"] for e in evs if e["ph"] == "X" and pred(e["name"])) / 1e6

    s = eng.stats
    device = total(lambda n: n.endswith(".device"))
    assert s.host_s == pytest.approx(total(lambda n: n == "engine.iter") - device)
    assert s.schedule_s == pytest.approx(total(lambda n: n == "engine.schedule"))
    assert s.prepare_s == pytest.approx(total(lambda n: n.endswith(".prepare")))
    assert s.commit_s == pytest.approx(total(lambda n: n.endswith(".commit")))
    assert s.queue_wait_s == pytest.approx(total(lambda n: n == "engine.queue"))
    assert s.prefill_s + s.decode_s == pytest.approx(device)
    decode = [e["args"] for e in evs if e["name"] == "engine.decode.device"]
    assert s.decode_rows_live == sum(a["batch"] for a in decode) > 0
    assert s.decode_rows_run == sum(a["bucket"] for a in decode)
    assert s.host_s > 0 and s.iterations > 0
    metrics = s.as_metrics()
    assert not {"tok_per_s", "ttft_p50_s", "ttft_p99_s", "makespan_s",
                "idle_s"} & set(metrics)
    for key in ("iterations", "host_s", "schedule_s", "prepare_s",
                "commit_s", "queue_wait_s", "queue_waits",
                "decode_rows_live", "decode_rows_run"):
        assert metrics[key] == getattr(s, key)


def test_region_writes_both_traces(tmp_path):
    """A Region emits one complete event to its tracer (or the installed
    one) with the attrs given at open and at close, and lands in a
    ``jax.profiler`` capture as a host annotation with those attrs."""
    from jax.profiler import ProfileData

    from repro.obs import Region

    tracer = Tracer(clock=TickTimer(1.0))
    jax.profiler.start_trace(str(tmp_path))
    try:
        with Region("t.outer", tracer, TickTimer(1.0), a=1) as outer:
            pass
        with use_tracer(tracer):
            Region("t.open", clock=TickTimer(1.0), b=2).close(c=3)
        Region("t.untraced").close()
    finally:
        jax.profiler.stop_trace()
    evs = {e["name"]: e for e in tracer.events()}
    assert set(evs) == {"t.outer", "t.open"}
    assert (outer.t0, outer.t1) == (1.0, 2.0)
    assert evs["t.outer"]["ts"] == 1_000_000 and evs["t.outer"]["dur"] == 1_000_000
    assert evs["t.outer"]["args"] == {"a": 1}
    assert evs["t.open"]["args"] == {"b": 2, "c": 3}
    pd = ProfileData.from_file(str(next(tmp_path.rglob("*.xplane.pb"))))
    host = {e.name: dict(e.stats) for p in pd.planes for ln in p.lines
            for e in ln.events if e.name.startswith("t.")}
    assert host == {"t.outer": {"a": 1}, "t.open": {"b": 2, "c": 3},
                    "t.untraced": {}}


def test_deferred_region_emits_when_asked():
    """A DeferredRegion ends with its block (its clock read, its attrs) but
    writes its tracer event only at ``emit``; a Region's ``close`` is
    ``end`` then ``emit``."""
    from repro.obs import DeferredRegion, Region

    tracer = Tracer(clock=TickTimer(1.0))
    with DeferredRegion("t.later", tracer, TickTimer(1.0), a=1) as later:
        pass
    assert (later.t0, later.t1) == (1.0, 2.0)
    assert tracer.events() == []
    now = Region("t.now", tracer, TickTimer(1.0))
    assert now.end(b=2) == 1.0 and tracer.events() == []
    now.emit()
    later.emit()
    evs = tracer.events()
    assert [e["name"] for e in evs] == ["t.now", "t.later"]
    assert evs[0]["args"] == {"b": 2} and evs[1]["args"] == {"a": 1}
    assert all(e["ts"] == 1_000_000 and e["dur"] == 1_000_000 for e in evs)


def test_engine_regions_in_profiler_trace(smoke_params, tmp_path):
    """A ``jax.profiler`` capture of a tiny engine run: its host plane
    carries every engine region, with the decode step's rows as metadata,
    and names the programs after the engine's jitted functions."""
    from jax.profiler import ProfileData

    reqs = synthetic_requests(SMOKE, 3, prompt_len=3, max_new_tokens=3, seed=2)
    eng = StreamingEngine(SMOKE, smoke_params, n_blocks=2, max_len=MAX_LEN)
    eng.serve(reqs)  # compile outside the capture
    jax.profiler.start_trace(str(tmp_path))
    try:
        eng.serve(synthetic_requests(SMOKE, 3, prompt_len=3, max_new_tokens=3,
                                     seed=3))
    finally:
        jax.profiler.stop_trace()
    pd = ProfileData.from_file(str(next(tmp_path.rglob("*.xplane.pb"))))
    host = [(e.name, dict(e.stats)) for p in pd.planes for ln in p.lines
            for e in ln.events]
    names = {n for n, _ in host}
    assert {"engine.serve", "engine.iter", "engine.schedule", "engine.queue",
            "engine.prefill.prepare", "engine.prefill.device",
            "engine.prefill.commit", "engine.decode.prepare",
            "engine.decode.device", "engine.decode.commit"} <= names
    decode = [m for n, m in host if n == "engine.decode.device"]
    assert decode and all(1 <= m["batch"] <= m["bucket"] <= 2 for m in decode)
    assert len([m for n, m in host if n == "engine.queue" and "iter" in m]) == 3
    programs = " ".join(names)
    assert "engine_prefill" in programs and "engine_decode" in programs


if given is not None:

    @settings(max_examples=5, deadline=None)
    @given(
        reqs_seed=st.integers(0, 50),
        chaos_seed=st.integers(0, 50),
        n=st.integers(1, 5),
    )
    def test_retire_uniqueness_property(smoke_params, reqs_seed, chaos_seed, n):
        """Under arbitrary seeded traces + chaos, every admitted request's
        timeline carries exactly one terminal retire instant whose status
        matches the engine's recorded RequestResult."""
        eng, tracer, reqs = _traced_run(
            smoke_params, reqs_seed=reqs_seed, n=n, chaos_seed=chaos_seed
        )
        _retire_check(eng, tracer, reqs)


# ---------------------------------------------------------------------------
# Span nesting under the background tuner's worker thread
# ---------------------------------------------------------------------------


def _toy_spec(costs, name="obs_toy"):
    space = ParamSpace([PerfParam("i", tuple(range(len(costs))))])

    def cost_factory(region, bp, args, kwargs):
        return lambda point: float(costs[point["i"]])

    return KernelSpec(
        name,
        make_region=lambda bp: ATRegion(
            name, space, lambda p: (lambda x: x * (p["i"] + 1))
        ),
        shape_class=lambda x: BasicParams.make(kernel=name),
        cost_factory=cost_factory,
        traffic_class=lambda x: TrafficClass.of(
            "prefill", int(x.shape[0]), int(x.shape[1])
        ),
    )


def _laminar(spans):
    """Complete spans on one track must be properly nested: any two either
    disjoint or one inside the other (the flame-graph invariant)."""
    for a in spans:
        for b in spans:
            a0, a1 = a["ts"], a["ts"] + a["dur"]
            b0, b1 = b["ts"], b["ts"] + b["dur"]
            disjoint = a1 <= b0 or b1 <= a0
            nested = (a0 <= b0 and b1 <= a1) or (b0 <= a0 and a1 <= b1)
            if not (disjoint or nested):
                return False, (a, b)
    return True, None


def test_background_tuner_spans_nest_on_worker_track():
    tracer = Tracer()
    op = AutotunedOp(_toy_spec([3.0, 1.0, 2.0]), db=TuningDB(), tune=False)
    x = jnp.ones((2, 8))
    with use_tracer(tracer):
        with BackgroundTuner() as tuner:
            state = tuner.submit(op, x)
            assert tuner.drain(timeout=60)
    assert state.tuned
    evs = tracer.events()
    worker_tracks = {e["track"] for e in evs if e["name"] == "bgtuner.job"}
    assert len(worker_tracks) == 1  # all tune work on the one worker thread
    track = worker_tracks.pop()
    spans = [e for e in evs if e["ph"] == "X" and e["track"] == track]
    names = {e["name"] for e in spans}
    assert {"bgtuner.job", "tuner.tune", "tuner.trial"} <= names
    ok, pair = _laminar(spans)
    assert ok, f"overlapping spans on worker track: {pair}"
    # tuner.tune nests inside bgtuner.job; every trial inside tuner.tune
    job = next(e for e in spans if e["name"] == "bgtuner.job")
    tune = next(e for e in spans if e["name"] == "tuner.tune")
    assert job["ts"] <= tune["ts"] <= tune["ts"] + tune["dur"] <= job["ts"] + job["dur"]
    for trial in (e for e in spans if e["name"] == "tuner.trial"):
        assert tune["ts"] <= trial["ts"]
        assert trial["ts"] + trial["dur"] <= tune["ts"] + tune["dur"]
    # thread interleaving cannot perturb the export (determinism contract)
    assert tracer.to_json() == tracer.to_json()


def test_disabled_tracer_emits_nothing():
    """With no tracer installed the instrumented paths run silently — the
    zero-cost-when-disabled contract's functional half."""
    assert current_tracer() is None
    op = AutotunedOp(_toy_spec([2.0, 1.0], name="obs_off"), db=TuningDB(),
                     tune=False)
    x = jnp.ones((2, 8))
    with BackgroundTuner() as tuner:
        tuner.submit(op, x)
        assert tuner.drain(timeout=60)
    # nothing to assert on a tracer — the assertion is that this ran with
    # current_tracer() None throughout and no error surfaced


# ---------------------------------------------------------------------------
# Explainability
# ---------------------------------------------------------------------------


def test_explain_reconstructs_decision_chain():
    db = TuningDB()
    op = AutotunedOp(_toy_spec([3.0, 1.0, 2.0], name="obs_explain"), db=db)
    x = jnp.ones((2, 8))
    op(x)  # tunes inline, recording trials + search_completed
    fp = next(iter(db.fingerprints()))
    report = explain_fingerprint(db, fp)
    assert report["kernel"] == "obs_explain"
    assert report["final"]["point"] == {"i": 1}
    assert report["final"]["final"] and report["final"]["source"] == "local_search"
    assert report["search"]["evaluations"] >= 3
    trials = report["measured_trials"]
    assert trials[0]["cost"] <= trials[-1]["cost"]  # ranked best-first
    text = render_report(report)
    assert "obs_explain" in text and "<- winner" in text
    assert "decision:" in text and "local_search" in text


def test_explain_unknown_fingerprint_raises():
    with pytest.raises(KeyError):
        explain_fingerprint(TuningDB(), "no-such-entry")


def test_db_summary_counts():
    db = TuningDB()
    op = AutotunedOp(_toy_spec([2.0, 1.0], name="obs_summary"), db=db)
    op(jnp.ones((2, 8)))
    s = db_summary(db)
    assert s["entries"] == 1 and s["finals"] == 1
    assert s["trials"] >= 2 and s["events"] >= 1
    reg = MetricsRegistry()
    reg.register_stats("tuning_db", s)
    assert "tuning_db_entries 1" in reg.prometheus_text()
