"""Pallas TPU kernel for the RG-LRU recurrence — sequential-grid carry.

Same chunked idiom as the selective scan (grid (B, w-blocks, chunks), h in
VMEM scratch across chunk steps) but with a diagonal state (no N dim), so
each fori step is pure VPU elementwise on a (1, block_w) lane row.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.arch import local_arch

C_FACTOR = 8.0


def _rglru_kernel(
    x_ref, r_ref, i_ref,  # (chunk, bw)
    lam_ref,              # (1, bw)
    y_ref,                # (chunk, bw)
    h_ref,                # scratch (1, bw) fp32
    *,
    chunk: int,
):
    cj = pl.program_id(2)

    @pl.when(cj == 0)
    def _init():
        h_ref[...] = jnp.zeros_like(h_ref)

    splam = jax.nn.softplus(-lam_ref[...].astype(jnp.float32))  # (1, bw)

    def step(t, h):
        row = pl.ds(t, 1)
        x_t = x_ref[row, :].astype(jnp.float32)
        r_t = r_ref[row, :].astype(jnp.float32)
        i_t = i_ref[row, :].astype(jnp.float32)
        a = jnp.exp(-C_FACTOR * r_t * splam)
        h = a * h + jnp.sqrt(jnp.maximum(1.0 - a * a, 1e-12)) * (i_t * x_t)
        y_ref[row, :] = h.astype(y_ref.dtype)
        return h

    h_ref[...] = jax.lax.fori_loop(0, chunk, step, h_ref[...])


def rglru_scan(
    x: jnp.ndarray,   # (B, S, W)
    r: jnp.ndarray,
    i: jnp.ndarray,
    lam: jnp.ndarray,  # (W,)
    block_w: int = 512,
    chunk: int = 128,
    interpret: bool = True,
) -> jnp.ndarray:
    B, S, W = x.shape
    bw = min(block_w, W)
    ck = min(chunk, S)
    if W % bw or S % ck:
        raise ValueError(f"blocks ({bw},{ck}) must divide (W={W}, S={S})")
    grid = (B, W // bw, S // ck)

    spec = pl.BlockSpec((None, ck, bw), lambda b, w, c: (b, c, w))
    lam_spec = pl.BlockSpec((1, bw), lambda b, w, c: (0, w))

    kernel = functools.partial(_rglru_kernel, chunk=ck)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[spec, spec, spec, lam_spec],
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct((B, S, W), x.dtype),
        scratch_shapes=[pltpu.VMEM((1, bw), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=local_arch().vmem_limit_bytes()
        ),
        interpret=interpret,
    )(x, r, i, lam.reshape(1, W))


def vmem_bytes(block_w: int, chunk: int) -> int:
    """Single-buffered working set: x/r/i/y chunks plus the (1, block_w)
    rows of lam and h, each padded to a full (8, 128) tile."""
    pad = lambda n: -(-n // 128) * 128
    return 4 * chunk * pad(block_w) * 4 + 2 * 8 * pad(block_w) * 4
