"""Pallas TPU kernel for the Mamba selective scan — chunked recurrence.

TPU adaptation of the CUDA selective-scan kernel: instead of one thread
block per (batch, channel-tile) with warp-level parallel prefix (a GPU
shared-memory pattern), we use the *sequential-grid carry* idiom: grid
(B, d-blocks, chunks), the h-state lives in VMEM scratch and persists
across the chunk dimension (the fastest-varying one).  Inside a chunk a
``fori_loop`` steps the recurrence with everything VMEM-resident — the
(S, D, N) decay tensor never exists anywhere, in any memory.

The state is kept as ``(N, block_d)``: channels on the lanes, the small
state dim on the sublanes, so each step's ``(1, block_d)`` input rows
broadcast over it without a relayout.  ``B_t`` and ``C_t`` arrive as rows
and are turned into ``(N, 1)`` columns by :func:`_column`.

Tunables: (block_d, chunk) — channel tile width and temporal chunk length.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.arch import local_arch


def _column(row: jnp.ndarray) -> jnp.ndarray:
    """(1, n) -> (n, 1): keep the diagonal of the row broadcast over n
    sublanes, then reduce the lanes — a transpose built from operations
    Mosaic lays out natively."""
    n = row.shape[1]
    eye = (jax.lax.broadcasted_iota(jnp.int32, (n, n), 0)
           == jax.lax.broadcasted_iota(jnp.int32, (n, n), 1))
    return jnp.sum(jnp.where(eye, row, 0.0), axis=1, keepdims=True)


def _ssm_kernel(
    x_ref,   # (chunk, bd)
    dt_ref,  # (chunk, bd)
    b_ref,   # (chunk, N)
    c_ref,   # (chunk, N)
    a_ref,   # (N, bd)   A transposed
    d_ref,   # (1, bd)
    y_ref,   # (chunk, bd)
    h_ref,   # scratch (N, bd) fp32
    *,
    chunk: int,
):
    cj = pl.program_id(2)

    @pl.when(cj == 0)
    def _init():
        h_ref[...] = jnp.zeros_like(h_ref)

    A = a_ref[...].astype(jnp.float32)  # (N, bd)
    Dp = d_ref[...].astype(jnp.float32)  # (1, bd)

    def step(t, h):
        row = pl.ds(t, 1)
        x_t = x_ref[row, :].astype(jnp.float32)    # (1, bd)
        dt_t = dt_ref[row, :].astype(jnp.float32)  # (1, bd)
        B_t = _column(b_ref[row, :].astype(jnp.float32))  # (N, 1)
        C_t = _column(c_ref[row, :].astype(jnp.float32))  # (N, 1)
        h = jnp.exp(dt_t * A) * h + B_t * (dt_t * x_t)
        y = jnp.sum(h * C_t, axis=0, keepdims=True) + x_t * Dp
        y_ref[row, :] = y.astype(y_ref.dtype)
        return h

    h_ref[...] = jax.lax.fori_loop(0, chunk, step, h_ref[...])


def ssm_scan(
    x: jnp.ndarray,   # (B, S, D)
    dt: jnp.ndarray,  # (B, S, D)
    A: jnp.ndarray,   # (D, N)
    Bc: jnp.ndarray,  # (B, S, N)
    Cc: jnp.ndarray,  # (B, S, N)
    D: jnp.ndarray,   # (D,)
    block_d: int = 512,
    chunk: int = 128,
    interpret: bool = True,
) -> jnp.ndarray:
    Bsz, S, Dd = x.shape
    N = A.shape[1]
    bd = min(block_d, Dd)
    ck = min(chunk, S)
    if Dd % bd or S % ck:
        raise ValueError(f"blocks ({bd},{ck}) must divide (D={Dd}, S={S})")
    grid = (Bsz, Dd // bd, S // ck)

    xd_spec = pl.BlockSpec((None, ck, bd), lambda b, d, c: (b, c, d))
    bn_spec = pl.BlockSpec((None, ck, N), lambda b, d, c: (b, c, 0))
    a_spec = pl.BlockSpec((N, bd), lambda b, d, c: (0, d))
    dd_spec = pl.BlockSpec((1, bd), lambda b, d, c: (0, d))

    kernel = functools.partial(_ssm_kernel, chunk=ck)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[xd_spec, xd_spec, bn_spec, bn_spec, a_spec, dd_spec],
        out_specs=xd_spec,
        out_shape=jax.ShapeDtypeStruct((Bsz, S, Dd), x.dtype),
        scratch_shapes=[pltpu.VMEM((N, bd), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=local_arch().vmem_limit_bytes()
        ),
        interpret=interpret,
    )(x, dt, Bc, Cc, A.T, D.reshape(1, Dd))


def vmem_bytes(block_d: int, chunk: int, n_state: int) -> int:
    """Single-buffered working set: the x/dt/y and B/C chunks, A and D
    tiles, the h scratch, and the (N, block_d) step temporaries."""
    pad = lambda n: -(-n // 128) * 128
    sub = lambda n: -(-n // 8) * 8
    io = 3 * chunk * pad(block_d) * 4  # x, dt, y
    bn = 2 * chunk * pad(n_state) * 4
    state = 4 * sub(n_state) * pad(block_d) * 4  # A, h scratch, 2 temps
    return io + bn + state + 8 * pad(block_d) * 4
