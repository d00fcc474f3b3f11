"""Tuner measurement guardrail + crash-safe TuningDB tests (ISSUE 8).

Covers: NaN/inf trial costs are quarantined and can never win an argmin
(locally or after a merge), raising cost functions quarantine the candidate
instead of aborting the sweep (control-flow exceptions still propagate),
``tuned_point`` refuses a quarantined final, quarantine markers survive the
CRDT merge in both directions, the all-candidates-quarantined search fails
loudly, the BackgroundTuner surfaces quarantined classes, and the
crash-safe two-step flush: a corrupted (or mid-rename vanished) main DB
file salvages from the ``.bak`` of the last good flush with the recovery
recorded in ``db_events``.
"""
import json
import math
import os

import pytest

from repro.core import (
    ATRegion,
    BasicParams,
    ParamSpace,
    PerfParam,
    Tuner,
    TuningDB,
    pp_key,
)
from repro.core.autotuned import TrialBudgetExhausted

BP = BasicParams.make(kernel="guard", n=8)
SPACE = ParamSpace([PerfParam("i", (0, 1, 2))])


def _region():
    return ATRegion("guard", SPACE, lambda p: (lambda: p["i"]))


# ---------------------------------------------------------------------------
# Measurement guardrail
# ---------------------------------------------------------------------------


def test_nan_cost_is_quarantined_and_never_wins():
    db = TuningDB()
    costs = {0: 3.0, 1: float("nan"), 2: 2.0}
    result = Tuner(db=db).tune(_region(), BP, lambda p: costs[p["i"]])
    assert result.best.point == {"i": 2}  # NaN survived no comparison
    assert db.tuned_point(BP) == {"i": 2}
    assert db.is_quarantined(BP, {"i": 1})
    assert not db.is_quarantined(BP, {"i": 2})
    assert pp_key({"i": 1}) not in db.trials(BP)  # never recorded as a trial
    assert "non-finite" in db.quarantined(BP)[pp_key({"i": 1})]["reason"]


def test_raising_cost_is_quarantined_not_fatal():
    db = TuningDB()

    def cost(p):
        if p["i"] == 0:
            raise ZeroDivisionError("broken candidate")
        return float(p["i"])

    result = Tuner(db=db).tune(_region(), BP, cost)
    assert result.best.point == {"i": 1}
    reason = db.quarantined(BP)[pp_key({"i": 0})]["reason"]
    assert "ZeroDivisionError" in reason and "broken candidate" in reason


def test_quarantined_candidate_is_never_remeasured():
    db = TuningDB()
    calls = []

    def cost(p):
        calls.append(p["i"])
        return float("inf") if p["i"] == 0 else float(p["i"])

    tuner = Tuner(db=db)
    tuner.tune(_region(), BP, cost)
    n = calls.count(0)
    tuner.tune(_region(), BP, cost, fresh=True)
    assert calls.count(0) == n  # known-broken: short-circuited to +inf


def test_all_candidates_quarantined_fails_loudly():
    db = TuningDB()
    with pytest.raises(RuntimeError, match="every candidate quarantined"):
        Tuner(db=db).tune(_region(), BP, lambda p: float("nan"))
    assert db.tuned_point(BP) is None  # nothing finalized
    assert len(db.quarantined(BP)) == SPACE.size()


def test_control_flow_exceptions_still_propagate():
    db = TuningDB()

    def cost(p):
        raise TrialBudgetExhausted("budget spent")

    assert TrialBudgetExhausted.tuning_control
    with pytest.raises(TrialBudgetExhausted):
        Tuner(db=db).tune(_region(), BP, cost)
    assert db.quarantined(BP) == {}  # control flow, not a broken candidate


@pytest.mark.parametrize("workers", [1, 2])
def test_prescreen_failure_scores_inf(workers):
    from repro.core.cost import score_points_concurrently

    def score(p):
        if p["i"] == 1:
            raise ValueError("refused by the compiler")
        return float(p["i"])

    points = [{"i": i} for i in range(3)]
    assert score_points_concurrently(score, points, max_workers=workers) == [
        0.0, math.inf, 2.0,
    ]


def test_search_completed_counts_prescreen_exclusions():
    """A candidate whose prescreen raises is excluded and counted in the
    search_completed event, so a caller that demands a space of compilable
    candidates only (chip_smoke.py) can refuse the search."""
    import jax.numpy as jnp

    from repro.core import AutotunedOp, KernelSpec

    space = ParamSpace([PerfParam("i", (0, 1, 2, 3))])

    def prescreen_factory(region, bp, args, kwargs):
        def score(p):
            if p["i"] == 1:
                raise ValueError("refused by the compiler")
            return float(p["i"])

        return score

    spec = KernelSpec(
        "excluded_toy",
        make_region=lambda bp: ATRegion("excluded_toy", space,
                                        lambda p: (lambda x: x)),
        shape_class=lambda x: BasicParams.make(kernel="excluded_toy", n=4),
        cost_factory=lambda r, b, a, k: (lambda p: float(p["i"])),
        prescreen_factory=prescreen_factory,
    )
    db = TuningDB()
    state = AutotunedOp(spec, db=db, prescreen_k=2).resolve(jnp.ones(4))
    (done,) = [e for e in db.events(state.bp) if e["kind"] == "search_completed"]
    assert done["prescreen_excluded"] == 1
    assert state.region.selected == {"i": 0}
    assert db.quarantined(state.bp) == {}  # excluded before any measurement


class _FailsToBlock:
    """An output whose device work fails when it is waited on."""

    def block_until_ready(self):
        raise RuntimeError("device fault")


@pytest.mark.parametrize("cls", ["WallClockCost", "AdaptiveWallClockCost"])
def test_wallclock_cost_propagates_errors_from_blocking(cls):
    import repro.core.cost as cost_mod

    cost = getattr(cost_mod, cls)(lambda p: _FailsToBlock)
    with pytest.raises(RuntimeError, match="device fault"):
        cost({"i": 0})


def test_record_best_refuses_non_finite():
    db = TuningDB()
    with pytest.raises(ValueError, match="never become a final best"):
        db.record_best(BP, {"i": 0}, float("nan"), "before_execution")


def test_quarantine_survives_merge_both_directions():
    ours, theirs = TuningDB(), TuningDB()
    # theirs tuned {"i": 0} as a legitimate final; ours quarantined it
    theirs.record_trial(BP, {"i": 0}, 1.0, "before_execution")
    theirs.record_best(BP, {"i": 0}, 1.0, "before_execution")
    ours.record_quarantine(BP, {"i": 0}, "non-finite trial cost nan")
    assert theirs.tuned_point(BP) == {"i": 0}
    merged_a = TuningDB().merge(ours).merge(theirs)
    merged_b = TuningDB().merge(theirs).merge(ours)
    for m in (merged_a, merged_b):
        # the sticky distrust wins: the quarantined final is refused
        assert m.is_quarantined(BP, {"i": 0})
        assert m.tuned_point(BP) is None
    fp = BP.fingerprint()
    assert merged_a.export_entries()[fp]["quarantined"] \
        == merged_b.export_entries()[fp]["quarantined"]


def test_nearest_tuned_skips_quarantined_final():
    db = TuningDB()
    near = BasicParams.make(kernel="guard", n=9)
    db.record_trial(near, {"i": 0}, 1.0, "before_execution")
    db.record_best(near, {"i": 0}, 1.0, "before_execution")
    assert db.nearest_tuned(BP) is not None
    db.record_quarantine(near, {"i": 0}, "drifted to nan")
    assert db.nearest_tuned(BP) is None


def test_background_tuner_surfaces_quarantined_labels():
    import jax.numpy as jnp

    from repro.core import AutotunedOp, KernelSpec, TrafficClass
    from repro.runtime import BackgroundTuner

    space = ParamSpace([PerfParam("i", (0, 1))])

    def cost_factory(region, bp, args, kwargs):
        return lambda p: float("nan") if p["i"] == 0 else 1.0

    spec = KernelSpec(
        "half_broken",
        make_region=lambda bp: ATRegion(
            "half_broken", space, lambda p: (lambda x: x)
        ),
        shape_class=lambda x: BasicParams.make(kernel="half_broken"),
        cost_factory=cost_factory,
        traffic_class=lambda x: TrafficClass.of("prefill", 1, int(x.shape[1])),
    )
    op = AutotunedOp(spec, db=TuningDB(), tune=False)
    with BackgroundTuner() as tuner:
        state = tuner.submit(op, jnp.ones((1, 8)))
        assert tuner.drain(timeout=60)
    assert tuner.quarantined_labels == ["prefill/b1/s8"]
    assert tuner.failed_labels == []  # the class still tuned on the survivor
    assert state.region.selected == {"i": 1}


# ---------------------------------------------------------------------------
# Crash-safe flush + salvage-on-load
# ---------------------------------------------------------------------------


def _seeded_db(path):
    db = TuningDB(path)
    db.record_trial(BP, {"i": 0}, 2.0, "before_execution")
    db.record_best(BP, {"i": 0}, 2.0, "before_execution")
    # one more flush so the .bak (always the last-but-one flush) holds the
    # finalized state the salvage tests expect to recover
    db.record_trial(BP, {"i": 2}, 3.0, "before_execution")
    return db


def test_flush_keeps_bak_of_last_good_flush(tmp_path):
    path = str(tmp_path / "db.json")
    _seeded_db(path)
    assert os.path.exists(path + ".bak")  # second flush demoted the first
    with open(path + ".bak") as f:
        json.load(f)  # the backup is itself valid JSON


def test_corrupted_main_salvages_from_bak(tmp_path):
    path = str(tmp_path / "db.json")
    _seeded_db(path)
    with open(path, "w") as f:
        f.write('{"schema_version": 2, "entries": {TRUNCATED')  # torn write
    db = TuningDB(path)
    assert db.tuned_point(BP) == {"i": 0}  # the last good flush survived
    events = db.db_events()
    assert events and events[-1]["kind"] == "db_salvaged"
    assert events[-1]["source"].endswith(".bak")
    # the salvage event itself persists through the next flush
    db.record_trial(BP, {"i": 1}, 1.0, "before_execution")
    assert TuningDB(path).db_events()[-1]["kind"] != "db_salvage_failed"
    assert any(e["kind"] == "db_salvaged" for e in TuningDB(path).db_events())


def test_kill_between_renames_salvages_from_bak(tmp_path):
    """Simulate a crash after demoting main to .bak but before promoting the
    tmp file: main is gone, .bak holds the last good flush."""
    path = str(tmp_path / "db.json")
    _seeded_db(path)
    os.replace(path, path + ".bak")  # the mid-_flush crash window
    db = TuningDB(path)
    assert db.tuned_point(BP) == {"i": 0}
    assert db.db_events()[-1]["kind"] == "db_salvaged"


def test_both_files_unreadable_starts_empty_and_logs(tmp_path):
    path = str(tmp_path / "db.json")
    _seeded_db(path)
    for p in (path, path + ".bak"):
        with open(p, "w") as f:
            f.write("not json at all")
    db = TuningDB(path)
    assert db.tuned_point(BP) is None and db.fingerprints() == []
    assert db.db_events()[-1]["kind"] == "db_salvage_failed"


def test_schema_too_new_still_raises_through_salvage(tmp_path):
    path = str(tmp_path / "db.json")
    with open(path, "w") as f:
        json.dump({"schema_version": 99, "entries": {}}, f)
    with pytest.raises(ValueError, match="schema"):
        TuningDB(path)
