"""Pallas TPU flash attention (forward) with tunable VMEM block shapes.

Grid (B, H, nq, nkv) — the last (fastest) grid dim walks KV blocks so the
online-softmax state lives in VMEM scratch across those steps (the standard
TPU flash layout: sequential grid = free accumulator carry).  Block shapes
(block_q, block_kv) are the AT knobs: q/k/v tiles must fit VMEM and the
MXU wants both ≥ 128.

Heads are fused into the minor axis: ``(B, S, H, hd)`` is viewed as
``(B, S, H*hd)`` (a free reshape) and a block is one head's
``(block, hd)`` slab, so the two minor block dims are the sequence tile and
the head dim — the layout Mosaic tiles in (8, 128) units.  A head dim that
is not a lane multiple is zero-padded up to one (scores and outputs are
unchanged; the pad columns are sliced off).

GQA is handled in the index maps: the KV block index ignores the query-head
grid coordinate beyond h // G — no KV replication in HBM.

Compared to the XLA path (models.attention.flash_attention_xla), the score
block never leaves VMEM.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.arch import local_arch

NEG_INF = -0.7 * float(jnp.finfo(jnp.float32).max)
LANES = 128


def _flash_kernel(
    q_ref,   # (block_q, hd)
    k_ref,   # (block_kv, hd)
    v_ref,   # (block_kv, hd)
    o_ref,   # (block_q, hd)
    m_ref,   # scratch (block_q, 1)
    l_ref,   # scratch (block_q, 1)
    acc_ref,  # scratch (block_q, hd)
    *,
    causal: bool,
    scale: float,
    block_q: int,
    block_kv: int,
    nkv: int,
    seq_len: int,
):
    qi = pl.program_id(2)
    kj = pl.program_id(3)

    @pl.when(kj == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[...]
    k = k_ref[...]
    v = v_ref[...]
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ) * scale
    if nkv * block_kv > seq_len or causal:
        col = jax.lax.broadcasted_iota(jnp.int32, (block_q, block_kv), 1) \
            + kj * block_kv
        # padded tail block: keys past the real sequence must not score
        keep = col < seq_len
        if causal:
            row = jax.lax.broadcasted_iota(jnp.int32, (block_q, block_kv), 0) \
                + qi * block_q
            keep = jnp.logical_and(keep, row >= col)
        s = jnp.where(keep, s, NEG_INF)

    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)
    alpha = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha + jnp.dot(
        p.astype(v.dtype), v, preferred_element_type=jnp.float32
    )
    m_ref[...] = m_new

    @pl.when(kj == nkv - 1)
    def _finish():
        o_ref[...] = (acc_ref[...] / l_ref[...]).astype(o_ref.dtype)


def flash_attention(
    q: jnp.ndarray,  # (B, S, H, hd)
    k: jnp.ndarray,  # (B, S, KV, hd)
    v: jnp.ndarray,
    block_q: int = 512,
    block_kv: int = 512,
    causal: bool = True,
    interpret: bool = True,
) -> jnp.ndarray:
    B, S, H, hd = q.shape
    KV = k.shape[2]
    G = H // KV
    bq, bkv = min(block_q, S), min(block_kv, S)
    # non-dividing blocks tile past the sequence edge: pad q rows and kv
    # columns up to whole blocks (the kernel masks tail keys to NEG_INF;
    # tail query rows are garbage and sliced off below)
    nq, nkv = -(-S // bq), -(-S // bkv)
    Sq, Skv = nq * bq, nkv * bkv
    hdp = -(-hd // LANES) * LANES
    q = jnp.pad(q, ((0, 0), (0, Sq - S), (0, 0), (0, hdp - hd)))
    k, v = (
        jnp.pad(t, ((0, 0), (0, Skv - S), (0, 0), (0, hdp - hd))) for t in (k, v)
    )
    q = q.reshape(B, Sq, H * hdp)
    k = k.reshape(B, Skv, KV * hdp)
    v = v.reshape(B, Skv, KV * hdp)
    grid = (B, H, nq, nkv)

    q_spec = pl.BlockSpec((None, bq, hdp), lambda b, h, i, j: (b, i, h))
    kv_spec = pl.BlockSpec((None, bkv, hdp), lambda b, h, i, j: (b, j, h // G))

    kernel = functools.partial(
        _flash_kernel,
        causal=causal,
        scale=1.0 / math.sqrt(hd),
        block_q=bq,
        block_kv=bkv,
        nkv=nkv,
        seq_len=S,
    )
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[q_spec, kv_spec, kv_spec],
        out_specs=q_spec,
        out_shape=jax.ShapeDtypeStruct((B, Sq, H * hdp), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, hdp), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=local_arch().vmem_limit_bytes()
        ),
        interpret=interpret,
    )(q, k, v)
    return out.reshape(B, Sq, H, hdp)[:, :S, :, :hd]


def vmem_bytes(block_q: int, block_kv: int, hd: int) -> int:
    """Single-buffered working set of one program: the q/k/v/o blocks, the
    f32 scratch, and the (block_q, block_kv) f32 temporaries the body keeps
    live at once (scores, probabilities, mask)."""
    pad = lambda n: -(-n // LANES) * LANES
    blocks = (2 * block_q + 2 * block_kv) * pad(hd) * 2
    scratch = 2 * block_q * LANES * 4 + block_q * pad(hd) * 4
    temps = 3 * block_q * pad(block_kv) * 4
    return blocks + scratch + temps
