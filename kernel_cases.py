"""The five registered kernels at the widths of the workloads they serve.

One table for ``chip_smoke.py`` (tune, run and check each kernel on the
chip) and ``tests/test_tpu_compile.py`` (compile every emitted candidate for
a described TPU); it sits beside them, outside the library, because it
knows the model zoo and the apps the kernels serve.  ``src`` must be on
the import path.  A case names the kernel, its jitted ops wrapper (whose
keyword arguments are the candidate point's keys), its emitted region for a
given arch, a seeded input builder, the jnp oracle and the tolerance.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Callable, Tuple

import jax
import jax.numpy as jnp

from repro.core.arch import ArchSpec

SEQ = 2048  # prefill / scan length of every sequence kernel


@dataclass(frozen=True)
class KernelCase:
    name: str
    source: str                               # where the widths come from
    fn: Callable[..., Any]                    # ops wrapper: fn(*args, **point)
    region: Callable[[ArchSpec], Any]         # emitted ATRegion for an arch
    make_args: Callable[[jax.Array], Tuple[Any, ...]]
    oracle: Callable[..., Any]
    tol: float                                # rtol = atol against the oracle

    def arg_shapes(self) -> Tuple[Any, ...]:
        return jax.eval_shape(self.make_args, jax.random.PRNGKey(0))


def _flash_args(key: jax.Array, H: int, KV: int, hd: int):
    kq, kk, kv = jax.random.split(key, 3)
    return tuple(
        jax.random.normal(k, (1, SEQ, n, hd), jnp.float32).astype(jnp.bfloat16)
        for k, n in ((kq, H), (kk, KV), (kv, KV))
    )


def kernel_cases() -> Tuple[KernelCase, ...]:
    from repro.apps.gkv import GKV_DIMS
    from repro.apps.seism3d import SEISM_DIMS
    from repro.configs import get_config

    from repro.kernels.exb import ops as exb_ops, ref as exb_ref
    from repro.kernels.flash_attention import ops as fa_ops, ref as fa_ref
    from repro.kernels.rglru_scan import ops as rg_ops, ref as rg_ref
    from repro.kernels.ssm_scan import ops as ssm_ops, ref as ssm_ref
    from repro.kernels.stress import ops as st_ops, ref as st_ref

    qwen = get_config("qwen3-0.6b")
    H, KV, hd = qwen.n_heads, qwen.n_kv_heads, qwen.head_dim_
    mamba = get_config("falcon-mamba-7b")
    d_inner, n_state = mamba.d_inner, mamba.ssm_state
    width = get_config("recurrentgemma-2b").lru_width_
    gkv = tuple(n for _, n in GKV_DIMS)
    seism = tuple(n for _, n in SEISM_DIMS)
    return (
        KernelCase(
            "flash_attention", f"qwen3-0.6b prefill B=1 S={SEQ} H={H} KV={KV} "
            f"hd={hd} bf16", fa_ops.attention,
            lambda arch: fa_ops.flash_region(SEQ, hd, arch=arch),
            lambda key: _flash_args(key, H, KV, hd),
            lambda q, k, v: fa_ref.attention_ref(q, k, v, causal=True),
            tol=2e-2,
        ),
        KernelCase(
            "ssm_scan", f"falcon-mamba-7b d_inner={d_inner} N={n_state} "
            f"S={SEQ} f32", ssm_ops.scan,
            lambda arch: ssm_ops.ssm_region(d_inner, SEQ, n_state, arch=arch),
            lambda key: ssm_ref.make_inputs(key, B=1, S=SEQ, D=d_inner,
                                            N=n_state),
            ssm_ref.ssm_scan_ref, tol=1e-3,
        ),
        KernelCase(
            "rglru_scan", f"recurrentgemma-2b width={width} S={SEQ} f32",
            rg_ops.scan,
            lambda arch: rg_ops.rglru_region(width, SEQ, arch=arch),
            lambda key: rg_ref.make_inputs(key, B=1, S=SEQ, W=width),
            rg_ref.rglru_scan_ref, tol=1e-3,
        ),
        KernelCase(
            "exb", f"GKV exb_realspcal (iv, iz, mx, my)={gkv} f32", exb_ops.exb,
            lambda arch: exb_ops.exb_region(dims=gkv, arch=arch),
            lambda key: (exb_ref.make_inputs(key, dims=gkv),),
            exb_ref.exb_ref, tol=1e-5,
        ),
        KernelCase(
            "stress", f"Seism3D update_stress (k, j, i)={seism} f32",
            st_ops.stress,
            lambda arch: st_ops.stress_region(dims=seism, arch=arch),
            lambda key: (st_ref.make_inputs(key, dims=seism),),
            st_ref.stress_ref, tol=1e-5,
        ),
    )
