"""Emit policies — candidate spaces *generated* from the architecture model.

ppOpen-AT enumerates every directive variant ahead of time from a fixed,
hand-written list.  This module replaces the hand-written part: a kernel
describes its tunable dimensions (:class:`TileDim` — extent plus a semantic
role), and an :class:`EmitPolicy` derives the candidate :class:`ParamSpace`
from an :class:`~repro.core.arch.ArchSpec` — pow2 tile ladders clipped to
divisibility and the arch's actual VMEM budget, pipeline-stage counts,
memory-space placement, and a per-point roofline estimate the staged
prescreen consumes for ranking.

Every emitted space carries a ``signature``: a content hash over the policy,
the arch, the dims, and the resulting point list.  The TuningDB records the
signature with each final so a changed arch model *invalidates* stale
winners instead of silently recalling them (docs/arch.md).
"""
from __future__ import annotations

import hashlib
import json
import math
from dataclasses import dataclass, field
from typing import (
    Any, Callable, Dict, List, Mapping, Optional, Protocol, Sequence, Tuple,
)

from .arch import ArchSpec, local_arch
from .params import EmptySpace, ParamSpace, PerfParam, pp_key


# Dimension semantics → the smallest tile worth emitting.  "lane" dims map
# to the VPU minor axis (tiles below lane width waste the vector unit);
# "sublane" dims are the second-minor block axis, which Mosaic tiles in
# whole sublanes; "sequential" dims are loop-carried chunks (a few
# sublanes deep is the floor); "grid" dims are pure program-count splits
# (any size works).
_SEMANTICS = ("lane", "sublane", "sequential", "grid")


@dataclass(frozen=True)
class TileDim:
    """One tunable dimension of a kernel, as the emit layer sees it.

    ``allow_padding`` marks dims the kernel can tile past the array edge
    (masking the tail), so non-dividing pow2 tiles stay candidates —
    without it a prime extent collapses to the single full-extent tile.
    """

    name: str
    extent: int
    semantic: str = "lane"
    min_tile: Optional[int] = None
    allow_padding: bool = False

    def __post_init__(self) -> None:
        if self.semantic not in _SEMANTICS:
            raise ValueError(
                f"TileDim {self.name!r}: unknown semantic {self.semantic!r}; "
                f"expected one of {_SEMANTICS}"
            )
        if self.extent < 1:
            raise ValueError(f"TileDim {self.name!r}: extent must be >= 1")

    def resolved_min(self, arch: ArchSpec) -> int:
        if self.min_tile is not None:
            return max(1, self.min_tile)
        if self.semantic == "lane":
            return arch.lane_width
        if self.semantic == "sublane":
            return arch.sublane_width
        if self.semantic == "sequential":
            return arch.sublane_width * 4
        return 1


@dataclass
class EmittedSpace:
    """What an emit policy returns: the space plus everything derived from it.

    ``hints`` maps ``pp_key(point)`` to the per-point model estimates
    (``est_s``, ``vmem_bytes``, ``programs``, ``stages``, ``memory_space``,
    ``pad_factor``) that :func:`hint_prescreen` folds into ranking.
    """

    space: ParamSpace
    signature: str
    arch: ArchSpec
    policy: str
    hints: Dict[str, Dict[str, Any]] = field(default_factory=dict)
    dims: Tuple[TileDim, ...] = ()


class EmitPolicy(Protocol):
    """Anything that can turn (arch, shape BP) into an EmittedSpace."""

    name: str
    version: int

    def emit(
        self, arch: ArchSpec, bp: Mapping[str, Any],
        pinned: Sequence[Mapping[str, Any]] = (),
        vmem_budget: Optional[int] = None,
    ) -> EmittedSpace:
        ...  # pragma: no cover - protocol


def pow2_ladder(dim: TileDim, arch: ArchSpec, cap: int = 8) -> Tuple[int, ...]:
    """Candidate tile sizes for one dim: pow2 multiples of the semantic
    minimum up to the extent, clipped to divisibility (unless the dim
    allows padded tails), plus the full extent itself.  At most ``cap``
    values survive — the largest ones, since the VMEM constraint prunes
    from above anyway."""
    lo = min(dim.resolved_min(arch), dim.extent)
    out = []
    v = lo
    while v < dim.extent:
        if dim.extent % v == 0 or dim.allow_padding:
            out.append(v)
        v *= 2
    out.append(dim.extent)
    out = sorted(set(out))
    return tuple(out[-cap:])


def _pad_factor(dims: Sequence[TileDim], point: Mapping[str, Any]) -> float:
    """Compute/traffic inflation from tiling past the array edge."""
    factor = 1.0
    for d in dims:
        if d.name not in point:
            continue
        tile = int(point[d.name])
        padded = -(-d.extent // tile) * tile
        factor *= padded / d.extent
    return factor


def _programs(dims: Sequence[TileDim], point: Mapping[str, Any]) -> int:
    n = 1
    for d in dims:
        if d.name in point:
            n *= -(-d.extent // int(point[d.name]))
    return n


class TilePolicy:
    """The default emit policy: arch-derived pow2 tile ladders.

    * ``dims(bp)`` returns the kernel's :class:`TileDim` list for a shape BP.
    * ``vmem_model(bp, point)`` returns the candidate's working-set bytes —
      the constraint is ``vmem_model <= arch.vmem_budget()``.
    * ``traffic_model(bp, point)`` (optional) returns ``(flops, bytes)`` of
      one whole call, used for the roofline part of the per-point hint.
    """

    def __init__(
        self,
        kernel: str,
        dims: Callable[[Mapping[str, Any]], Sequence[TileDim]],
        vmem_model: Callable[[Mapping[str, Any], Mapping[str, Any]], int],
        traffic_model: Optional[
            Callable[[Mapping[str, Any], Mapping[str, Any]], Tuple[float, float]]
        ] = None,
        max_per_dim: int = 8,
        version: int = 1,
    ) -> None:
        self.kernel = kernel
        self.name = "tile_pow2"
        self.version = version
        self.dims = dims
        self.vmem_model = vmem_model
        self.traffic_model = traffic_model
        self.max_per_dim = max_per_dim

    # -- hints -----------------------------------------------------------

    def _hint(
        self,
        arch: ArchSpec,
        bp: Mapping[str, Any],
        dims: Sequence[TileDim],
        point: Mapping[str, Any],
        budget: int,
    ) -> Dict[str, Any]:
        vmem = int(self.vmem_model(bp, point))
        stages = 2 if 2 * vmem <= budget else 1
        programs = _programs(dims, point)
        pad = _pad_factor(dims, point)
        est = programs * arch.grid_overhead_s
        flops = bytes_ = 0.0
        if self.traffic_model is not None:
            flops, bytes_ = self.traffic_model(bp, point)
            flops *= pad
            bytes_ *= pad
            # single-stage candidates cannot overlap copy-in with compute
            mem_penalty = 1.0 if stages >= 2 else 1.5
            est += max(
                flops / arch.peak_flops,
                bytes_ * mem_penalty / arch.hbm_bandwidth,
            )
        return {
            "est_s": est,
            "vmem_bytes": vmem,
            "stages": stages,
            "programs": programs,
            "pad_factor": pad,
            "memory_space": "vmem" if vmem <= budget else "hbm",
            "flops": flops,
            "bytes": bytes_,
        }

    # -- emit ------------------------------------------------------------

    def emit(
        self,
        arch: Optional[ArchSpec] = None,
        bp: Mapping[str, Any] = (),
        pinned: Sequence[Mapping[str, Any]] = (),
        vmem_budget: Optional[int] = None,
    ) -> EmittedSpace:
        arch = arch or local_arch()
        bp = dict(bp)
        budget = int(vmem_budget if vmem_budget is not None
                     else arch.vmem_budget())
        dims = tuple(self.dims(bp))
        pinned_pts = [dict(p) for p in pinned]
        pinned_keys = {pp_key(p) for p in pinned_pts}

        domains: Dict[str, List[Any]] = {
            d.name: list(pow2_ladder(d, arch, self.max_per_dim)) for d in dims
        }
        # escape hatch: hand-pinned points are always candidates, even when
        # their values fall outside the ladder or past the VMEM budget — a
        # known winner must never be lost to a model change
        for p in pinned_pts:
            for name, value in p.items():
                if name in domains and value not in domains[name]:
                    domains[name].append(value)
        params = [PerfParam(d.name, tuple(sorted(domains[d.name]))) for d in dims]

        def fits(point: Mapping[str, Any]) -> bool:
            if pp_key(point) in pinned_keys:
                return True
            return int(self.vmem_model(bp, point)) <= budget

        context = {
            "kernel": self.kernel,
            "arch": arch.name,
            "vmem_budget": budget,
            **{f"extent_{d.name}": d.extent for d in dims},
        }
        base = ParamSpace(
            params, constraint=fits,
            label=f"emitted:{self.kernel}", context=context,
        )
        feasible = list(base.points())
        if not feasible:  # pragma: no cover - base construction raises first
            raise EmptySpace(
                f"emitted:{self.kernel}: no candidate fits", context=context
            )

        hints = {
            pp_key(p): self._hint(arch, bp, dims, p, budget) for p in feasible
        }
        ordered = sorted(
            feasible, key=lambda p: (hints[pp_key(p)]["est_s"], pp_key(p))
        )
        space = base.subset(ordered)
        space.label, space.context = base.label, base.context

        signature = space_signature(
            policy=self.name, version=self.version, kernel=self.kernel,
            arch=arch, dims=dims, budget=budget,
            point_keys=[pp_key(p) for p in ordered],
        )
        return EmittedSpace(
            space=space, signature=signature, arch=arch,
            policy=self.name, hints=hints, dims=dims,
        )


def space_signature(
    policy: str,
    version: int,
    kernel: str,
    arch: ArchSpec,
    dims: Sequence[TileDim],
    budget: int,
    point_keys: Sequence[str],
) -> str:
    """Content hash of an emitted space — byte-identical iff the policy,
    the arch model, the shape dims, the budget, and the resulting ordered
    candidate list are all identical."""
    payload = {
        "policy": policy,
        "version": version,
        "kernel": kernel,
        "arch": arch.bp_entries(),
        "dims": [
            {
                "name": d.name, "extent": d.extent, "semantic": d.semantic,
                "min_tile": d.min_tile, "allow_padding": d.allow_padding,
            }
            for d in dims
        ],
        "vmem_budget": budget,
        "points": list(point_keys),
    }
    blob = json.dumps(payload, sort_keys=True, separators=(",", ":"))
    return hashlib.sha256(blob.encode()).hexdigest()[:16]


class HintedRooflineCost:
    """Compiled roofline prescreen, re-ranked with the emit-layer hints.

    Wraps :class:`~repro.core.cost.CompiledRooflineCost`: the HLO roofline
    gives flops/bytes truth, while the hint contributes what the HLO cannot
    see — the per-program grid overhead and the single-stage pipeline
    penalty.  Exposes the same ``score_many`` / ``compiled_by_point``
    surface so the measured stage still reuses the prescreen's executables.
    """

    def __init__(self, inner: Any, hints: Mapping[str, Mapping[str, Any]],
                 arch: ArchSpec) -> None:
        self.inner = inner
        self.hints = hints
        self.arch = arch

    @property
    def compiled_by_point(self) -> Dict[str, Any]:
        return self.inner.compiled_by_point

    @property
    def terms_by_point(self) -> Dict[str, Any]:
        return self.inner.terms_by_point

    def __call__(self, point: Mapping[str, Any]) -> float:
        base = float(self.inner(point))
        h = self.hints.get(pp_key(point))
        if h:
            penalty = 1.0 if h.get("stages", 2) >= 2 else 1.5
            base = base * penalty + h["programs"] * self.arch.grid_overhead_s
        return base

    def score_many(
        self,
        points: Sequence[Mapping[str, Any]],
        max_workers: Optional[int] = None,
    ) -> List[float]:
        from .cost import score_points_concurrently

        return score_points_concurrently(self, points, max_workers)


def hint_prescreen(
    region: Any, bp: Any, args: tuple, kwargs: dict
) -> Optional[Any]:
    """Staged-pipeline prescreen for emitted regions.

    With example arguments, compiles candidates like
    :func:`~repro.core.cost.roofline_prescreen` and folds the emit hints
    into the score.  Without example arguments (where the compiled
    prescreen must return ``None``), falls back to ranking purely on the
    hint estimates — an emitted region always has *some* prescreen.
    """
    from .cost import roofline_prescreen

    hints = getattr(region, "hints", None) or {}
    arch = getattr(region, "arch", None) or local_arch()
    compiled = roofline_prescreen(region, bp, args, kwargs)
    if compiled is not None:
        return HintedRooflineCost(compiled, hints, arch) if hints else compiled
    if not hints:
        return None

    def score(point: Mapping[str, Any]) -> float:
        h = hints.get(pp_key(point))
        return float(h["est_s"]) if h else math.inf

    return score
