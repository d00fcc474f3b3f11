"""Pallas TPU kernel for GKV ``exb_realspcal`` with an Exchange-style
(grid × block) candidate family.

The paper's directive-position transform maps onto Pallas as: loop levels
OUTSIDE the kernel become grid dimensions (one program instance per tile,
pipelined HBM→VMEM), loop levels INSIDE the block are VPU-vectorized.  The
tunable pair (block_iv, block_iz) plays (directive position × thread count):

* block_iv=1,  block_iz=1  → grid (16,16): directive on iz, max grain count
  (the paper's Fig-1 structure);
* block_iv=1,  block_iz=16 → grid (16,1): directive on iv (Fig 4 — the
  paper's winner on FX100);
* block_iv=16, block_iz=16 → grid (1,1): single fused block (Fig 7).

The (mx, my) inner loops always stay inside the block — my=65 is the short
loop whose 32-way splitting destroyed FX100 pipelining (Fig 14); on TPU it
maps to the VPU lane dimension and must never be split across grid.

3-D field blocks drop the iv grid index in their index_map — the physical
realization of the Fortran broadcast, with zero memory amplification.
"""
from __future__ import annotations

import functools
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.arch import local_arch

from .ref import CEF, CS1


def _exb_kernel(
    vl_ref,  # (iv,) in SMEM: the whole velocity grid, read as scalars
    df1_re_ref, df1_im_ref, df2_re_ref, df2_im_ref,
    ex_re_ref, ex_im_ref, ey_re_ref, ey_im_ref,
    bx_re_ref, bx_im_ref, by_re_ref, by_im_ref,
    out_re_ref, out_im_ref,
    *,
    block_iv: int,
):
    iv0 = pl.program_id(0) * block_iv
    for b in range(block_iv):  # static: one (biz, mx, my) slab per iv
        cs1vl = CS1 * vl_ref[iv0 + b]
        ey_re = ey_re_ref[...] - cs1vl * by_re_ref[...]
        ey_im = ey_im_ref[...] - cs1vl * by_im_ref[...]
        ex_re = ex_re_ref[...] - cs1vl * bx_re_ref[...]
        ex_im = ex_im_ref[...] - cs1vl * bx_im_ref[...]
        out_re_ref[b] = (df1_re_ref[b] * ey_re - df2_re_ref[b] * ex_re) * CEF
        out_im_ref[b] = (df1_im_ref[b] * ey_im - df2_im_ref[b] * ex_im) * CEF


def exb_pallas(
    inp: Dict[str, jnp.ndarray],
    block_iv: int = 1,
    block_iz: int = 16,
    interpret: bool = True,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    iv, iz, mx, my = inp["df1_re"].shape
    if iv % block_iv or iz % block_iz:
        raise ValueError(f"blocks ({block_iv},{block_iz}) must divide ({iv},{iz})")
    grid = (iv // block_iv, iz // block_iz)

    b4 = pl.BlockSpec(
        (block_iv, block_iz, mx, my), lambda i, j: (i, j, 0, 0)
    )
    b3 = pl.BlockSpec((block_iz, mx, my), lambda i, j: (j, 0, 0))  # drops iv
    bvl = pl.BlockSpec(memory_space=pltpu.SMEM)

    out_shape = [
        jax.ShapeDtypeStruct((iv, iz, mx, my), jnp.float32),
        jax.ShapeDtypeStruct((iv, iz, mx, my), jnp.float32),
    ]
    fn = pl.pallas_call(
        functools.partial(_exb_kernel, block_iv=block_iv),
        grid=grid,
        in_specs=[bvl] + [b4] * 4 + [b3] * 8,
        out_specs=[b4, b4],
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=local_arch().vmem_limit_bytes()
        ),
        interpret=interpret,
    )
    args = [
        inp["vl"],
        inp["df1_re"], inp["df1_im"], inp["df2_re"], inp["df2_im"],
        inp["ex_re"], inp["ex_im"], inp["ey_re"], inp["ey_im"],
        inp["bx_re"], inp["bx_im"], inp["by_re"], inp["by_im"],
    ]
    out_re, out_im = fn(*args)
    return out_re, out_im


def vmem_bytes(block_iv: int, block_iz: int, mx: int = 128, my: int = 65) -> int:
    """VMEM working set of one program instance (feasibility constraint):
    the 4-D and 3-D blocks plus the four (block_iz, mx, my) field
    temporaries of one iv slab (vl lives in SMEM)."""
    pad_my = -(-my // 128) * 128  # lane padding on real TPU
    b4 = block_iv * block_iz * mx * pad_my * 4
    b3 = block_iz * mx * pad_my * 4
    return 6 * b4 + 12 * b3  # 4 in + 2 out 4-D, 8 3-D in + 4 temporaries
