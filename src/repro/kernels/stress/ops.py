"""Jitted wrapper + AT region for the stress Pallas kernel."""
from __future__ import annotations

import functools
from typing import Any, Mapping, Optional, Sequence

import jax

from repro.core import ATRegion, BasicParams, KernelSpec, register_kernel
from repro.core.arch import ArchSpec, default_interpret, local_arch
from repro.core.emit import TileDim, TilePolicy, hint_prescreen

from .ref import stress_ref
from .stress import stress_pallas, vmem_bytes


@functools.partial(jax.jit, static_argnames=("block_k", "block_j", "interpret"))
def stress(inp, block_k: int = 8, block_j: int = 64,
           interpret: Optional[bool] = None):
    if interpret is None:
        interpret = default_interpret()
    return stress_pallas(inp, block_k=block_k, block_j=block_j, interpret=interpret)


def _traffic(bp: Mapping[str, Any], point: Mapping[str, Any]):
    nk, nj, ni = bp["nk"], bp["nj"], bp["ni"]
    cells = float(nk * nj * ni)
    return 30.0 * cells, 2.0 * cells * 4 * 9   # 9 stress/strain fields


STRESS_POLICY = TilePolicy(
    kernel="stress",
    # both block dims split the outer loops (the paper's Seism3D
    # update_stress nest); the inner ni stays whole per program.  block_j
    # is the second-minor block axis, so it comes in whole sublanes
    dims=lambda bp: (
        TileDim("block_k", bp["nk"], semantic="grid"),
        TileDim("block_j", bp["nj"], semantic="sublane"),
    ),
    vmem_model=lambda bp, p: vmem_bytes(p["block_k"], p["block_j"], bp["ni"]),
    traffic_model=_traffic,
)


def stress_region(
    dims=(64, 64, 64), vmem_budget: Optional[int] = None,
    arch: Optional[ArchSpec] = None,
    pinned: Sequence[Mapping[str, Any]] = (),
) -> ATRegion:
    nk, nj, ni = dims
    arch = arch or local_arch()
    emitted = STRESS_POLICY.emit(
        arch, {"nk": nk, "nj": nj, "ni": ni},
        pinned=pinned, vmem_budget=vmem_budget,
    )

    def instantiate(point: Mapping[str, Any]):
        bk, bj = point["block_k"], point["block_j"]
        return lambda inp: stress(inp, block_k=bk, block_j=bj)

    return ATRegion(
        "stress_pallas", emitted.space, instantiate, oracle=stress_ref,
        space_signature=emitted.signature, hints=emitted.hints, arch=arch,
    )


def shape_class(inp) -> BasicParams:
    nk, nj, ni = next(iter(inp.values())).shape
    return BasicParams.make(
        kernel="stress",
        nk=int(nk),
        nj=int(nj),
        ni=int(ni),
        dtype=str(next(iter(inp.values())).dtype),
        backend=jax.default_backend(),
    )


register_kernel(
    KernelSpec(
        "stress",
        make_region=lambda bp: stress_region(dims=(bp["nk"], bp["nj"], bp["ni"])),
        shape_class=shape_class,
        prescreen_factory=hint_prescreen,
        tags=("pallas",),
    ),
    replace=True,
)
