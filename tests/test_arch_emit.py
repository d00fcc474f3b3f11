"""Architecture model + emit layer (docs/arch.md).

Covers the tentpole contracts: ArchSpec/DeviceFingerprint BP round-trips,
deterministic emitted-space signatures, the signature-gated DB recall
(changed arch invalidates stale finals; unchanged arch recalls with zero
evals), the EmptySpace constructor guard, and the pinned-point escape hatch.
"""
import dataclasses

import pytest

from repro.core import BasicParams, EmptySpace, ParamSpace, PerfParam, pp_key
from repro.core.arch import ArchSpec, default_interpret, detect, local_arch
from repro.core.db import TuningDB
from repro.core.emit import TileDim, TilePolicy, hint_prescreen
from repro.fleet.fingerprint import DeviceFingerprint, _pow2_bucket, local_device


# ---------------------------------------------------------------------------
# fingerprint round-trips
# ---------------------------------------------------------------------------


def test_device_fingerprint_bp_roundtrip_identity():
    fp = local_device()
    assert DeviceFingerprint.from_bp_entries(fp.bp_entries()) == fp


def test_device_fingerprint_roundtrip_synthetic():
    fp = DeviceFingerprint(
        backend="tpu", platform="TPU v5e", device_count=4,
        host_cores=8, memory_gib=16, schema=2,
    )
    assert DeviceFingerprint.from_bp_entries(fp.bp_entries()) == fp


@pytest.mark.parametrize(
    "gib,bucket",
    [(0.1, 1), (1.0, 1), (1.0001, 2), (1.5, 2), (2.0, 2), (2.1, 4),
     (4.0, 4), (63.9, 64), (64.0, 64), (64.1, 128)],
)
def test_pow2_bucket_boundaries(gib, bucket):
    assert _pow2_bucket(gib) == bucket


def test_arch_spec_bp_roundtrip_identity():
    arch = local_arch()
    assert ArchSpec.from_bp_entries(arch.bp_entries()) == arch
    assert all(k.startswith("arch_") for k in arch.bp_entries())


def test_fingerprint_hangs_arch_spec():
    fp = local_device()
    arch = fp.arch_spec()
    assert isinstance(arch, ArchSpec)
    assert arch.backend == fp.backend
    assert arch == detect(fp.backend)


def test_default_interpret_matches_backend():
    import jax

    assert default_interpret() == (jax.default_backend() == "cpu")


class _FakeDevice:
    def __init__(self, kind):
        self.device_kind = kind


def test_detect_tpu_by_exact_device_kind(monkeypatch):
    import jax

    monkeypatch.setattr(jax, "devices", lambda: [_FakeDevice("TPU v5 lite")] * 4)
    arch = detect("tpu")
    assert (arch.name, arch.core_count) == ("tpu_v5e", 4)
    assert arch.vmem_budget() * 2 <= arch.vmem_limit_bytes() < arch.vmem_bytes


@pytest.mark.parametrize("kind", ["TPU v4", "TPU v6 lite", "TPU v5e", ""])
def test_detect_unknown_tpu_kind_raises(monkeypatch, kind):
    """A TPU missing from the table is an error, never another chip's VMEM."""
    import jax

    monkeypatch.setattr(jax, "devices", lambda: [_FakeDevice(kind)])
    with pytest.raises(ValueError, match="no ArchSpec for TPU device_kind"):
        detect("tpu")


# ---------------------------------------------------------------------------
# emitted spaces
# ---------------------------------------------------------------------------


def _toy_policy(**kw):
    return TilePolicy(
        kernel="toy",
        dims=lambda bp: (
            TileDim("block", bp["n"], semantic="lane"),
            TileDim("chunk", bp["s"], semantic="sequential"),
        ),
        vmem_model=lambda bp, p: p["block"] * p["chunk"] * 4,
        traffic_model=lambda bp, p: (bp["n"] * bp["s"] * 8.0,
                                     bp["n"] * bp["s"] * 4.0),
        **kw,
    )


def test_same_arch_same_signature_property():
    """Same ArchSpec → byte-identical signature, across shapes and repeats."""
    arch = detect("cpu")
    policy = _toy_policy()
    for n in (128, 256, 1024):
        for s in (64, 512):
            sigs = {
                policy.emit(arch, {"n": n, "s": s}).signature
                for _ in range(3)
            }
            assert len(sigs) == 1
            sig = sigs.pop()
            assert isinstance(sig, str) and len(sig) == 16
            # a fresh policy object emits the identical signature too
            assert _toy_policy().emit(arch, {"n": n, "s": s}).signature == sig


def test_changed_arch_changes_signature():
    arch = detect("cpu")
    policy = _toy_policy()
    bp = {"n": 1024, "s": 512}
    base = policy.emit(arch, bp).signature
    smaller = dataclasses.replace(arch, vmem_bytes=arch.vmem_bytes // 8)
    assert policy.emit(smaller, bp).signature != base
    # a pure metadata change (bandwidth) also re-signs: the model changed
    faster = dataclasses.replace(arch, hbm_bandwidth=arch.hbm_bandwidth * 2)
    assert policy.emit(faster, bp).signature != base


def test_emitted_space_respects_vmem_budget():
    arch = detect("cpu")
    emitted = _toy_policy().emit(arch, {"n": 2048, "s": 2048},
                                 vmem_budget=256 * 1024)
    for p in emitted.space.points():
        assert p["block"] * p["chunk"] * 4 <= 256 * 1024
        h = emitted.hints[pp_key(p)]
        assert h["vmem_bytes"] <= 256 * 1024
        assert h["memory_space"] == "vmem"
        assert h["stages"] in (1, 2)
        assert h["programs"] >= 1


def test_emitted_points_are_hint_ordered():
    arch = detect("cpu")
    emitted = _toy_policy().emit(arch, {"n": 1024, "s": 512})
    ests = [emitted.hints[pp_key(p)]["est_s"] for p in emitted.space.points()]
    assert ests == sorted(ests)
    # the space default (untuned baseline) is the model's best guess
    assert pp_key(emitted.space.default()) == pp_key(
        min(emitted.space.points(),
            key=lambda p: emitted.hints[pp_key(p)]["est_s"])
    )


def test_ladder_respects_semantics():
    arch = detect("cpu")
    emitted = _toy_policy().emit(arch, {"n": 1024, "s": 512})
    blocks = {p["block"] for p in emitted.space.points()}
    chunks = {p["chunk"] for p in emitted.space.points()}
    assert min(blocks) >= arch.lane_width          # lane dim floor
    assert min(chunks) >= arch.sublane_width * 4   # sequential dim floor
    for b in blocks:
        assert 1024 % b == 0                       # no padding unless allowed


def test_padding_dim_emits_nondividing_candidates():
    arch = detect("cpu")
    policy = TilePolicy(
        kernel="toy_pad",
        dims=lambda bp: (
            TileDim("block", bp["n"], semantic="lane", allow_padding=True),
        ),
        vmem_model=lambda bp, p: p["block"] * 4,
    )
    emitted = policy.emit(arch, {"n": 200})
    blocks = sorted(p["block"] for p in emitted.space.points())
    assert blocks == [128, 200]  # padded pow2 + the exact extent
    assert emitted.hints[pp_key({"block": 128})]["pad_factor"] > 1.0


def test_pinned_escape_hatch_unions_points():
    """Hand-pinned points survive even outside ladder and budget."""
    arch = detect("cpu")
    pinned = [{"block": 384, "chunk": 512}]  # 384 is not a pow2 ladder value
    emitted = _toy_policy().emit(
        arch, {"n": 1024, "s": 512}, pinned=pinned, vmem_budget=64 * 1024
    )
    keys = {pp_key(p) for p in emitted.space.points()}
    assert pp_key(pinned[0]) in keys
    # and pinning changes the signature (the space genuinely differs)
    base = _toy_policy().emit(arch, {"n": 1024, "s": 512},
                              vmem_budget=64 * 1024)
    assert emitted.signature != base.signature


def test_empty_space_raises_typed_error_naming_arch():
    arch = detect("cpu")
    with pytest.raises(EmptySpace) as exc:
        _toy_policy().emit(arch, {"n": 1024, "s": 512}, vmem_budget=16)
    msg = str(exc.value)
    assert "toy" in msg and "cpu_host" in msg and "16" in msg
    assert exc.value.context["vmem_budget"] == 16


def test_param_space_empty_constraint_raises_at_construction():
    with pytest.raises(EmptySpace):
        ParamSpace(
            [PerfParam("x", (1, 2, 3))],
            constraint=lambda p: False,
            label="always_empty",
        )


def test_hint_prescreen_ranks_without_example_args():
    from repro.kernels.flash_attention.ops import flash_region

    region = flash_region(1024, 64)
    score = hint_prescreen(region, None, (), {})
    assert score is not None  # emitted regions always have a prescreen
    pts = list(region.space.points())
    scores = [score(p) for p in pts]
    assert all(s >= 0 for s in scores)
    assert scores == sorted(scores)  # points() is already hint-ordered


# ---------------------------------------------------------------------------
# signature-gated DB recall
# ---------------------------------------------------------------------------


def _bp():
    return BasicParams.make(kernel="toy", n=1024)


def test_unchanged_signature_recalls_final(tmp_path):
    db = TuningDB(str(tmp_path / "db.json"))
    bp = _bp()
    db.record_best(bp, {"block": 128}, 1.0, "install", space_signature="sigA")
    assert db.tuned_point(bp, space_signature="sigA") == {"block": 128}
    assert db.space_signature(bp) == "sigA"
    assert db.invalidate_stale_final(bp, "sigA") is False  # nothing stale


def test_changed_signature_blocks_recall_and_invalidates(tmp_path):
    db = TuningDB(str(tmp_path / "db.json"))
    bp = _bp()
    db.record_trial(bp, {"block": 128}, 1.0, "install")
    db.record_best(bp, {"block": 128}, 1.0, "install", space_signature="sigA")
    # a region emitted under a different arch model must not recall it
    assert db.tuned_point(bp, space_signature="sigB") is None
    assert db.invalidate_stale_final(bp, "sigB") is True
    assert db.tuned_point(bp) is None          # final flag stripped
    assert db.trials(bp) == {}                 # stale trials dropped
    kinds = [e["kind"] for e in db.events(bp)]
    assert "space_invalidated" in kinds
    ev = [e for e in db.events(bp) if e["kind"] == "space_invalidated"][0]
    assert ev["old_sig"] == "sigA" and ev["new_sig"] == "sigB"


def test_legacy_final_without_signature_is_stale_for_emitted_region(tmp_path):
    db = TuningDB(str(tmp_path / "db.json"))
    bp = _bp()
    db.record_best(bp, {"block": 128}, 1.0, "install")  # pre-emit final
    assert db.tuned_point(bp) == {"block": 128}          # legacy callers OK
    assert db.tuned_point(bp, space_signature="sigA") is None
    assert db.invalidate_stale_final(bp, "sigA") is True


def test_signature_survives_merge(tmp_path):
    a = TuningDB(str(tmp_path / "a.json"))
    b = TuningDB(str(tmp_path / "b.json"))
    bp = _bp()
    a.record_best(bp, {"block": 128}, 1.0, "install", space_signature="sigA")
    b.merge(a.export_entries())
    assert b.tuned_point(bp, space_signature="sigA") == {"block": 128}
    assert b.space_signature(bp) == "sigA"


def test_autotuned_op_invalidates_on_arch_change(tmp_path):
    """End to end: tune once, re-resolve with a changed emitted space →
    the stale final is demoted and the op re-tunes; unchanged space →
    zero-eval recall (the hot path stays hot)."""
    from repro.core import ATRegion, AutotunedOp, KernelSpec

    def make_spec(signature):
        def make_region(bp):
            space = ParamSpace([PerfParam("block", (128, 256))])
            return ATRegion(
                "toy", space, lambda pt: (lambda x: x * pt["block"]),
                space_signature=signature,
            )

        return KernelSpec(
            "toy_sig", make_region=make_region,
            shape_class=lambda x: BasicParams.make(kernel="toy_sig", n=int(x)),
        )

    db = TuningDB(str(tmp_path / "db.json"))
    evals = []

    def cost_factory(region, bp, args, kwargs):
        return lambda point: (evals.append(dict(point)) or 0.1)

    op = AutotunedOp(make_spec("sigA"), db=db, cost_factory=cost_factory,
                     warm=False, device_key=False)
    first = op.resolve(7)
    assert evals  # searched
    assert db.space_signature(first.bp) == "sigA"

    # same arch model: a fresh op recalls with zero evaluations
    evals.clear()
    op2 = AutotunedOp(make_spec("sigA"), db=db, cost_factory=cost_factory,
                      warm=False, device_key=False)
    state = op2.resolve(7)
    assert state.from_cache and not evals

    # changed arch model: stale final demoted, search re-runs
    op3 = AutotunedOp(make_spec("sigB"), db=db, cost_factory=cost_factory,
                      warm=False, device_key=False)
    state = op3.resolve(7)
    assert not state.from_cache and evals
    bp = state.bp
    assert db.space_signature(bp) == "sigB"
    assert any(e["kind"] == "space_invalidated" for e in db.events(bp))
