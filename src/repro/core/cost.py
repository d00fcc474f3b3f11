"""FIBER cost-definition functions (paper §II.A).

The cost definition function maps a PP assignment to a scalar cost with BP
fixed.  The paper uses measured execution time on the FX100.  We provide:

* :class:`WallClockCost` — measured wall time of a compiled candidate.  Used
  for the paper-reproduction experiments (GKV / Seism3D run on this host) and
  for the FIBER *run-time* layer.
* :class:`CompiledRooflineCost` — the TPU-targeted analytic cost: lower +
  compile the candidate (no execution, no allocation), read
  ``cost_analysis()`` FLOPs/bytes and parse collective bytes out of the HLO,
  and return ``max(compute, memory, collective)`` seconds under the roofline
  model.  Used for the *before-execution* layer where the target hardware is
  not the host (this container is CPU; the target is TPU v5e).
* :class:`MemoryCost` — peak bytes/device from ``memory_analysis()``; FIBER
  explicitly names memory as an admissible cost.
"""
from __future__ import annotations

import math
import os
import re
import time
from concurrent.futures import ThreadPoolExecutor
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Mapping, Optional, Sequence

import jax


# ---------------------------------------------------------------------------
# Target-hardware model (TPU v5e, per assignment)
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class HardwareSpec:
    name: str
    peak_flops: float        # FLOP/s per chip (bf16)
    hbm_bandwidth: float     # bytes/s per chip
    ici_bandwidth: float     # bytes/s per link
    hbm_bytes: float         # HBM capacity per chip
    vmem_bytes: float        # VMEM per core


TPU_V5E = HardwareSpec(
    name="tpu_v5e",
    peak_flops=197e12,
    hbm_bandwidth=819e9,
    ici_bandwidth=50e9,
    hbm_bytes=16 * 1024**3,
    vmem_bytes=128 * 1024 * 1024,  # v5e VMEM is ~128MiB/core budgeted conservatively
)

# The paper's machine, for the reproduction benchmarks' narrative only.
FX100 = HardwareSpec(
    name="fujitsu_fx100",
    peak_flops=1.1264e12,
    hbm_bandwidth=480e9 / 2,
    ici_bandwidth=12.5e9,
    hbm_bytes=32 * 1024**3,
    vmem_bytes=24 * 1024**2,
)


# ---------------------------------------------------------------------------
# Roofline terms
# ---------------------------------------------------------------------------


_COLLECTIVE_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%?[\w.\-]+\s*=\s*((?:\([^)]*\)|[\w\[\],<>{}: ])+?)\s*"
    r"(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start|-done)?\(",
)

_SHAPE_RE = re.compile(r"(pred|[usbf]\d+(?:e\d+m\d+)?)\[([\d,]*)\]")

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "s32": 4, "u32": 4,
    "s64": 8, "u64": 8, "f16": 2, "bf16": 2, "f32": 4, "f64": 8,
    "f8e4m3": 1, "f8e5m2": 1, "f8e4m3fn": 1,
}


def _shape_bytes(shape_text: str) -> int:
    """Sum byte sizes of every typed array shape in an HLO type string."""
    total = 0
    for dtype, dims in _SHAPE_RE.findall(shape_text):
        base = _DTYPE_BYTES.get(dtype)
        if base is None:
            m = re.match(r"[usbf]?f?(\d+)", dtype)
            base = int(m.group(1)) // 8 if m else 4
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += base * n
    return total


def collective_bytes_from_hlo(hlo_text: str) -> Dict[str, int]:
    """Parse an HLO dump and sum result sizes of every collective op.

    ``cost_analysis()`` does not report collective traffic, so we walk the
    HLO text.  Returns per-op-kind byte totals; ``sum(result.values())`` is
    the collective_bytes roofline numerator.  ``-start``/``-done`` pairs are
    counted once (we match the ``-start`` form or the plain form; ``-done``
    lines do not re-list operand shapes in the same way but are filtered by
    only counting lines that declare a result type).
    """
    out: Dict[str, int] = {}
    for line in hlo_text.splitlines():
        m = _COLLECTIVE_RE.search(line)
        if not m:
            continue
        kind = m.group(2)
        if "-done(" in line:
            continue  # counted at -start
        nbytes = _shape_bytes(m.group(1))
        if nbytes == 0:
            continue
        out[kind] = out.get(kind, 0) + nbytes
    return out


@dataclass
class RooflineTerms:
    """The three roofline terms, in seconds, for one compiled candidate."""

    compute_s: float
    memory_s: float
    collective_s: float
    hlo_flops: float
    hlo_bytes: float
    collective_bytes: float
    per_device_hbm_bytes: float = 0.0
    collective_breakdown: Dict[str, int] = field(default_factory=dict)

    @property
    def total_s(self) -> float:
        """Roofline lower bound: terms overlap perfectly, so cost = max."""
        return max(self.compute_s, self.memory_s, self.collective_s)

    @property
    def bottleneck(self) -> str:
        terms = {
            "compute": self.compute_s,
            "memory": self.memory_s,
            "collective": self.collective_s,
        }
        return max(terms, key=terms.get)

    def asdict(self) -> Dict[str, Any]:
        return {
            "compute_s": self.compute_s,
            "memory_s": self.memory_s,
            "collective_s": self.collective_s,
            "total_s": self.total_s,
            "bottleneck": self.bottleneck,
            "hlo_flops": self.hlo_flops,
            "hlo_bytes": self.hlo_bytes,
            "collective_bytes": self.collective_bytes,
            "per_device_hbm_bytes": self.per_device_hbm_bytes,
            "collective_breakdown": dict(self.collective_breakdown),
        }


# Ring-model execution factors: an all-reduce moves ~2× its payload per
# device ((k-1)/k reduce-scatter + (k-1)/k all-gather); others ~1×.
_COLLECTIVE_EXEC_FACTOR = {
    "all-reduce": 2.0,
    "all-gather": 1.0,
    "reduce-scatter": 1.0,
    "all-to-all": 1.0,
    "collective-permute": 1.0,
}


def roofline_from_compiled(
    lowered: Any,
    compiled: Any,
    n_chips: int,
    hw: HardwareSpec = TPU_V5E,
) -> RooflineTerms:
    """Derive the three roofline terms from a lowered+compiled jit artifact.

    * compute    = HLO_FLOPs / (chips × peak_FLOP/s)
    * memory     = HLO_bytes / (chips × HBM_bw)
    * collective = collective_bytes / (chips × link_bw), all-reduce weighted
      2× (ring model).

    The SPMD module is per-device, so per-device cost × n_chips = the global
    HLO_* numerators; the division by chips then cancels back to per-device
    time — i.e. the assignment's formula evaluated exactly, reported with
    global numerators.

    FLOPs/bytes/collectives come from :mod:`repro.core.hlo_analysis`, which
    multiplies ``while`` bodies by their known trip counts —
    ``compiled.cost_analysis()`` counts scan bodies once and is wrong by the
    layer count on scan-over-layers models (measured 6× on a 6-layer toy).
    """
    try:
        hlo = compiled.as_text()
    except Exception:
        hlo = lowered.as_text()

    from .hlo_analysis import analyze_hlo_text

    per_dev = analyze_hlo_text(hlo)
    flops_dev = per_dev.flops
    bytes_dev = per_dev.bytes
    coll = {k: float(v) for k, v in per_dev.collectives.items()}
    coll_bytes_dev = float(sum(coll.values()))
    coll_exec_dev = float(
        sum(_COLLECTIVE_EXEC_FACTOR.get(k, 1.0) * v for k, v in coll.items())
    )

    mem_per_dev = 0.0
    try:
        ma = compiled.memory_analysis()
        mem_per_dev = float(
            getattr(ma, "temp_size_in_bytes", 0)
            + getattr(ma, "argument_size_in_bytes", 0)
            + getattr(ma, "output_size_in_bytes", 0)
            - getattr(ma, "alias_size_in_bytes", 0)
        )
    except Exception:
        pass

    return RooflineTerms(
        compute_s=flops_dev / hw.peak_flops,
        memory_s=bytes_dev / hw.hbm_bandwidth,
        collective_s=coll_exec_dev / hw.ici_bandwidth,
        hlo_flops=flops_dev * n_chips,
        hlo_bytes=bytes_dev * n_chips,
        collective_bytes=coll_bytes_dev * n_chips,
        per_device_hbm_bytes=mem_per_dev,
        collective_breakdown={k: int(v * n_chips) for k, v in coll.items()},
    )


# ---------------------------------------------------------------------------
# Cost functions
# ---------------------------------------------------------------------------


def score_points_concurrently(
    score_one: Callable[[Mapping[str, Any]], float],
    points: Sequence[Mapping[str, Any]],
    max_workers: Optional[int] = None,
) -> List[float]:
    """Score candidates on a bounded thread pool; failures score ``inf``.

    The single shared policy for prescreen fan-out (XLA lowering/compilation
    release the GIL): `CompiledRooflineCost.score_many` and
    `StagedSearch`'s generic prescreen both delegate here, so the worker
    bound and the exclude-don't-fail error handling cannot diverge.  The
    exclusions are counted: ``search_completed`` events record them as
    ``prescreen_excluded``.
    """
    workers = max_workers or min(8, os.cpu_count() or 2)

    def score(p: Mapping[str, Any]) -> float:
        try:
            return float(score_one(p))
        except Exception:
            return math.inf

    if workers <= 1 or len(points) <= 1:
        return [score(p) for p in points]
    with ThreadPoolExecutor(max_workers=workers) as pool:
        return list(pool.map(score, points))


class CostFunction:
    """cost(PP point) -> float seconds (lower is better)."""

    def __call__(self, point: Mapping[str, Any]) -> float:  # pragma: no cover
        raise NotImplementedError


class WallClockCost(CostFunction):
    """Measured wall time of ``build(point)() `` — the paper's cost function.

    ``build`` maps a PP point to a zero-arg callable that runs the candidate
    once (already closed over its inputs, already jitted if appropriate).
    Measures ``repeats`` timed runs after ``warmup`` untimed ones and returns
    the minimum (standard practice to suppress OS noise; the paper runs 1000
    iterations for the same reason).
    """

    def __init__(
        self,
        build: Callable[[Mapping[str, Any]], Callable[[], Any]],
        warmup: int = 2,
        repeats: int = 5,
        inner_iters: int = 1,
    ) -> None:
        self.build = build
        self.warmup = warmup
        self.repeats = repeats
        self.inner_iters = inner_iters

    def __call__(self, point: Mapping[str, Any]) -> float:
        fn = self.build(point)
        for _ in range(self.warmup):
            jax.block_until_ready(fn())
        best = math.inf
        for _ in range(self.repeats):
            t0 = time.perf_counter()
            for _ in range(self.inner_iters):
                out = fn()
            jax.block_until_ready(out)
            best = min(best, (time.perf_counter() - t0) / self.inner_iters)
        return best


class AdaptiveWallClockCost(CostFunction):
    """Measured wall time with variance-aware adaptive repeats.

    Fixed-repeat timing spends the same budget on a candidate that is 10×
    off the incumbent as on one within noise of it.  This cost times each
    point until its confidence interval separates from the best cost seen so
    far (the *incumbent*), then stops:

    * after ``min_repeats`` timed runs, a point whose best time is already
      ``rel_margin`` above the incumbent is abandoned immediately;
    * otherwise timing continues until ``best ± halfwidth`` (a
      ``confidence``-sigma standard-error interval) no longer straddles the
      incumbent, or ``max_repeats`` is reached.

    ``supports_budget`` lets :class:`~repro.core.search.SuccessiveHalving`
    pass its rung budget through: ``cost(point, budget)`` scales the repeat
    cap.  ``timed_runs`` / ``measured_points`` expose the totals the
    tuning-throughput benchmark reports.
    """

    supports_budget = True

    def __init__(
        self,
        build: Callable[[Mapping[str, Any]], Callable[[], Any]],
        warmup: int = 1,
        min_repeats: int = 1,
        max_repeats: int = 4,
        rel_margin: float = 0.25,
        confidence: float = 2.0,
    ) -> None:
        self.build = build
        self.warmup = warmup
        self.min_repeats = max(1, min_repeats)
        self.max_repeats = max(self.min_repeats, max_repeats)
        self.rel_margin = rel_margin
        self.confidence = confidence
        self.incumbent = math.inf
        self.timed_runs = 0
        self.measured_points = 0

    def __call__(
        self, point: Mapping[str, Any], budget: Optional[int] = None
    ) -> float:
        fn = self.build(point)
        for _ in range(self.warmup):
            jax.block_until_ready(fn())
        cap = self.max_repeats * max(1, int(budget or 1))
        times: List[float] = []
        while len(times) < cap:
            t0 = time.perf_counter()
            out = fn()
            jax.block_until_ready(out)
            times.append(time.perf_counter() - t0)
            self.timed_runs += 1
            if len(times) < self.min_repeats:
                continue
            best = min(times)
            if not math.isfinite(self.incumbent):
                if len(times) >= self.min_repeats + 1:
                    break  # first point: just establish the incumbent
                continue
            if best > self.incumbent * (1.0 + self.rel_margin):
                break  # clearly worse: stop paying for precision
            if len(times) >= 2:
                mean = sum(times) / len(times)
                var = sum((t - mean) ** 2 for t in times) / (len(times) - 1)
                halfwidth = self.confidence * math.sqrt(var / len(times))
                if (best + halfwidth < self.incumbent
                        or best - halfwidth > self.incumbent):
                    break  # CI separated from the incumbent either way
        cost = min(times)
        self.measured_points += 1
        self.incumbent = min(self.incumbent, cost)
        return cost


class CompiledRooflineCost(CostFunction):
    """Lower+compile the candidate and score it with the roofline model.

    ``lower`` maps a PP point to a ``jax.stages.Lowered`` (the caller does
    ``jax.jit(step, in_shardings=...).lower(*specs)`` with whatever shardings
    the point dictates).  No device execution ever happens: this is FIBER
    before-execution AT with the hardware absent.
    """

    def __init__(
        self,
        lower: Callable[[Mapping[str, Any]], Any],
        n_chips: int,
        hw: HardwareSpec = TPU_V5E,
        keep_compiled: bool = False,
    ) -> None:
        self.lower = lower
        self.n_chips = n_chips
        self.hw = hw
        self.last_terms: Optional[RooflineTerms] = None
        self.terms_by_point: Dict[str, RooflineTerms] = {}
        # keep_compiled retains each candidate's compiled executable so a
        # downstream measured stage can execute it instead of recompiling
        # (the staged pipeline's prescreen already paid the compile cost).
        # The executables are argument-shape-specialized, so they are valid
        # only for the example arguments the prescreen lowered against.
        self.keep_compiled = keep_compiled
        self.compiled_by_point: Dict[str, Any] = {}

    def __call__(self, point: Mapping[str, Any]) -> float:
        from .params import pp_key

        lowered = self.lower(point)
        compiled = lowered.compile()
        terms = roofline_from_compiled(lowered, compiled, self.n_chips, self.hw)
        self.last_terms = terms
        key = pp_key(point)
        self.terms_by_point[key] = terms
        if self.keep_compiled:
            self.compiled_by_point[key] = compiled
        return terms.total_s

    def score_many(
        self,
        points: Sequence[Mapping[str, Any]],
        max_workers: Optional[int] = None,
    ) -> List[float]:
        """Score candidates concurrently on a bounded thread pool.

        Lowering and XLA compilation release the GIL, so independent
        candidates compile in parallel — this is the staged pipeline's
        prescreen fan-out (docs/tuning.md).  Per-point failures score
        ``inf`` rather than aborting the batch.
        """
        return score_points_concurrently(self, points, max_workers)


class MemoryCost(CostFunction):
    """Peak per-device bytes of the compiled candidate (FIBER's memory cost)."""

    def __init__(self, lower: Callable[[Mapping[str, Any]], Any]) -> None:
        self.lower = lower

    def __call__(self, point: Mapping[str, Any]) -> float:
        compiled = self.lower(point).compile()
        ma = compiled.memory_analysis()
        return float(
            getattr(ma, "temp_size_in_bytes", 0)
            + getattr(ma, "argument_size_in_bytes", 0)
            + getattr(ma, "output_size_in_bytes", 0)
        )


def roofline_prescreen(
    region: Any, bp: Any, args: tuple, kwargs: dict,
) -> Optional[CompiledRooflineCost]:
    """The generic staged-pipeline prescreen for any AT region.

    Matches the ``KernelSpec.prescreen_factory`` signature: lowers + compiles
    each candidate against the call's example arguments (no execution, no
    allocation) and scores it with the roofline model — FIBER's
    before-execution layer as stage 1 of the staged pipeline
    (docs/tuning.md).  Returns ``None`` when there are no example arguments
    to lower against (nothing to compile — the op falls back to single-stage
    search).

    The compiled executables are retained (``keep_compiled``): the measured
    finals run on the same example arguments, so survivors execute the
    prescreen's artifact instead of paying a second compilation — the eval
    reduction becomes a wall-clock reduction too.
    """
    if not args and not kwargs:
        return None

    def lower(point: Mapping[str, Any]) -> Any:
        return jax.jit(region.instantiate(point)).lower(*args, **kwargs)

    return CompiledRooflineCost(lower, n_chips=1, keep_compiled=True)
