"""Pallas TPU kernel for Seism3D ``update_stress``.

Grid over (k-blocks, j-blocks); the contiguous i dimension stays inside the
block as the VPU lane axis (the Fortran innermost loop — never split, per
the paper's Fig-14 lesson).  Tunables (block_k, block_j) are the directive
position / grain: one program instance per (bk × bj × i) tile.
"""
from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.arch import local_arch

from .ref import DT, INPUT_NAMES


def _stress_kernel(*refs):
    i = {name: r for name, r in zip(INPUT_NAMES, refs[: len(INPUT_NAMES)])}
    o = refs[len(INPUT_NAMES):]
    rl = i["lam"][...]
    rm = i["rig"][...]
    rm2 = 2.0 * rm
    rlrm2 = rl + rm2
    dxVx, dyVy, dzVz = i["dxVx"][...], i["dyVy"][...], i["dzVz"][...]
    d3 = dxVx + dyVy + dzVz
    o[0][...] = i["Sxx"][...] + DT * (rlrm2 * d3 - rm2 * (dyVy + dzVz))
    o[1][...] = i["Syy"][...] + DT * (rlrm2 * d3 - rm2 * (dxVx + dzVz))
    o[2][...] = i["Szz"][...] + DT * (rlrm2 * d3 - rm2 * (dxVx + dyVy))
    o[3][...] = i["Sxy"][...] + DT * rm * (i["dxVy"][...] + i["dyVx"][...])
    o[4][...] = i["Sxz"][...] + DT * rm * (i["dxVz"][...] + i["dzVx"][...])
    o[5][...] = i["Syz"][...] + DT * rm * (i["dyVz"][...] + i["dzVy"][...])


def stress_pallas(
    inp: Dict[str, jnp.ndarray],
    block_k: int = 8,
    block_j: int = 64,
    interpret: bool = True,
) -> Dict[str, jnp.ndarray]:
    nk, nj, ni = inp["Sxx"].shape
    if nk % block_k or nj % block_j:
        raise ValueError(f"blocks ({block_k},{block_j}) must divide ({nk},{nj})")
    grid = (nk // block_k, nj // block_j)
    spec = pl.BlockSpec((block_k, block_j, ni), lambda a, b: (a, b, 0))
    out_shape = [jax.ShapeDtypeStruct((nk, nj, ni), jnp.float32)] * 6
    fn = pl.pallas_call(
        _stress_kernel,
        grid=grid,
        in_specs=[spec] * len(INPUT_NAMES),
        out_specs=[spec] * 6,
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=local_arch().vmem_limit_bytes()
        ),
        interpret=interpret,
    )
    outs = fn(*[inp[n] for n in INPUT_NAMES])
    return dict(zip(("Sxx", "Syy", "Szz", "Sxy", "Sxz", "Syz"), outs))


def vmem_bytes(block_k: int, block_j: int, ni: int) -> int:
    """Single-buffered working set: the 17 input and 6 output blocks plus
    the five shared temporaries the body keeps live (rm2, rlrm2, d3 and
    two partial sums)."""
    pad_i = -(-ni // 128) * 128
    return (len(INPUT_NAMES) + 6 + 5) * block_k * block_j * pad_i * 4
