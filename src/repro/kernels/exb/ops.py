"""Jitted wrapper + AT region for the exb Pallas kernel.

``exb_region()`` brackets the kernel's (block_iv, block_iz) family exactly
like the paper brackets the Fortran loop nest — the candidate family is
emitted from the architecture model (core/emit.py), with a VMEM-feasibility
constraint standing in for "enough iterations per thread" (docs/design.md
§2), and an analytic cost model for install-time AT on a host without the
target hardware.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Mapping, Optional, Sequence

import jax
import jax.numpy as jnp

from repro.core import ATRegion, BasicParams, KernelSpec, register_kernel
from repro.core.arch import ArchSpec, default_interpret, local_arch
from repro.core.cost import TPU_V5E, HardwareSpec
from repro.core.emit import TileDim, TilePolicy

from .exb import exb_pallas, vmem_bytes
from .ref import exb_ref


@functools.partial(jax.jit, static_argnames=("block_iv", "block_iz", "interpret"))
def exb(inp: Dict[str, jnp.ndarray], block_iv: int = 1, block_iz: int = 16,
        interpret: Optional[bool] = None):
    if interpret is None:
        interpret = default_interpret()
    return exb_pallas(inp, block_iv=block_iv, block_iz=block_iz, interpret=interpret)


def _traffic(bp: Mapping[str, Any], point: Mapping[str, Any]):
    iv, iz, mx, my = bp["iv"], bp["iz"], bp["mx"], bp["my"]
    flops = 24.0 * iv * iz * mx * my
    # 3-D fields are re-streamed once per iv-block row (index_map reuse)
    bytes_ = 6.0 * iv * iz * mx * my * 4 \
        + 8.0 * iz * mx * my * 4 * (iv // point["block_iv"])
    return flops, bytes_


EXB_POLICY = TilePolicy(
    kernel="exb",
    dims=lambda bp: (
        TileDim("block_iv", bp["iv"], semantic="grid"),
        TileDim("block_iz", bp["iz"], semantic="grid"),
    ),
    vmem_model=lambda bp, p: vmem_bytes(
        p["block_iv"], p["block_iz"], bp["mx"], bp["my"]
    ),
    traffic_model=_traffic,
)


def exb_region(
    dims=(16, 16, 128, 65), vmem_budget: Optional[int] = None,
    arch: Optional[ArchSpec] = None,
    pinned: Sequence[Mapping[str, Any]] = (),
) -> ATRegion:
    iv, iz, mx, my = dims
    arch = arch or local_arch()
    emitted = EXB_POLICY.emit(
        arch, {"iv": iv, "iz": iz, "mx": mx, "my": my},
        pinned=pinned, vmem_budget=vmem_budget,
    )

    def instantiate(point: Mapping[str, Any]):
        biv, biz = point["block_iv"], point["block_iz"]
        return lambda inp: exb(inp, block_iv=biv, block_iz=biz)

    return ATRegion(
        "exb_pallas", emitted.space, instantiate, oracle=exb_ref,
        space_signature=emitted.signature, hints=emitted.hints, arch=arch,
    )


def analytic_cost(
    point: Mapping[str, Any],
    dims=(16, 16, 128, 65),
    hw: HardwareSpec = TPU_V5E,
    grid_overhead_s: float = 1.5e-6,
) -> float:
    """Install-time cost model: HBM-stream time + per-program overhead.

    The kernel is memory-bound (arithmetic intensity ≈ 24 flops / 56 bytes),
    so cost ≈ bytes/BW + n_programs × launch overhead; finer grids pipeline
    better but pay overhead — the same trade the FX100 thread count makes.
    """
    iv, iz, mx, my = dims
    biv, biz = point["block_iv"], point["block_iz"]
    n_programs = (iv // biv) * (iz // biz)
    bytes_hbm = 6 * iv * iz * mx * my * 4 + 8 * iz * mx * my * 4 * (iv // biv)
    # 3-D fields are re-streamed once per iv-block row (index_map reuse)
    return bytes_hbm / hw.hbm_bandwidth + n_programs * grid_overhead_s


def shape_class(inp) -> BasicParams:
    iz, mx, my = inp["ex_re"].shape
    return BasicParams.make(
        kernel="exb",
        iv=int(inp["vl"].shape[0]),
        iz=int(iz),
        mx=int(mx),
        my=int(my),
        dtype=str(inp["ex_re"].dtype),
        backend=jax.default_backend(),
    )


def _bp_dims(bp: BasicParams):
    return (bp["iv"], bp["iz"], bp["mx"], bp["my"])


def _analytic_factory(region, bp, args, kwargs):
    return lambda point: analytic_cost(point, dims=_bp_dims(bp))


def _cost_factory(region, bp, args, kwargs):
    """Measured wall clock (``None``) where the target chip is present;
    the analytic model on any other host."""
    if jax.default_backend() == "tpu":
        return None
    return _analytic_factory(region, bp, args, kwargs)


register_kernel(
    KernelSpec(
        "exb",
        make_region=lambda bp: exb_region(dims=_bp_dims(bp)),
        shape_class=shape_class,
        # install-layer AT on a host without the target hardware: the
        # memory-bound analytic model replaces wall-clock measurement, and
        # doubles as the staged prescreen — stage 1 ranks exactly, so the
        # finals stage only confirms the top-k (measured on a TPU)
        cost_factory=_cost_factory,
        prescreen_factory=_analytic_factory,
        tags=("pallas",),
    ),
    replace=True,
)
