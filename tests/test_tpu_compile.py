"""Compile the main path for a described TPU v5e chip, with no chip attached.

Every candidate the emit layer produces for the v5e arch, for each of the
five kernels at the widths of the workloads they serve, must pass the TPU
compiler as a native Mosaic kernel (``tpu_custom_call``), and the
qwen3-0.6b engine decode step (8 KV blocks x 2048, 16 x 1024) and train
step (batch 1 x 2048) must each fit one chip's HBM, updating the KV pool
and the training state in place.
Nothing runs, so this checks neither results nor times.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, so every xdist worker must
collect the same tests and only the one that runs them loads it.
"""
import os
import sys

import jax
import jax.numpy as jnp
import pytest

from repro.core import arch as arch_mod

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
from kernel_cases import kernel_cases  # noqa: E402  (beside chip_smoke.py)

HBM_BYTES = 16 * 2**30
CASES = {c.name: c for c in kernel_cases()}


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else it logs to /tmp
    try:
        desc = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without the chip: keep the cache out of it
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def v5e(topo):
    """The described chip's ArchSpec, pinned as the local arch while this
    module runs, so the kernels ask the compiler for its VMEM limit."""
    arch = arch_mod.tpu_arch(topo.devices[0].device_kind)
    backend = jax.default_backend()
    saved = arch_mod._LOCAL.get(backend)
    arch_mod._LOCAL[backend] = arch
    yield arch
    if saved is None:
        arch_mod._LOCAL.pop(backend, None)
    else:
        arch_mod._LOCAL[backend] = saved


def _on(sharding, shapes):
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sharding),
        shapes,
    )


@pytest.mark.parametrize("name", sorted(CASES))
def test_every_emitted_candidate_compiles_natively(name, one_chip, v5e):
    case = CASES[name]
    args = _on(one_chip, case.arg_shapes())
    points = [dict(p) for p in case.region(v5e).space.points()]
    assert points
    for point in points:
        hlo = jax.jit(
            lambda *a, point=point: case.fn(*a, **point, interpret=False)
        ).lower(*args).compile().as_text()
        assert "tpu_custom_call" in hlo, (name, point)


def _pool(blocks, capacity):
    from repro.configs import get_config
    from repro.models import init_cache

    cfg = get_config("qwen3-0.6b")
    row = jax.eval_shape(lambda: init_cache(cfg, 1, capacity))
    return cfg, {
        k: jax.ShapeDtypeStruct((blocks,) + v.shape, v.dtype)
        for k, v in row.items()
    }


def _bytes(tree) -> int:
    return sum(s.size * s.dtype.itemsize for s in jax.tree.leaves(tree))


@pytest.mark.parametrize("blocks,capacity,bucket",
                         [(8, 2048, 8), (16, 1024, 16)])
def test_qwen3_engine_decode_step_fits_one_chip(blocks, capacity, bucket,
                                                one_chip):
    """The engine's decode step donates the pool (its output aliases its
    input) and writes one slot per row and layer, so it holds no copy of
    the pool's rows: 8 x 2048, and the serving benchmark's 16 x 1024 at
    its largest bucket."""
    from repro.models import param_specs
    from repro.models.spec import as_shape_dtype_structs
    from repro.runtime.engine import decode_program

    cfg, pool = _pool(blocks, capacity)
    idx = jax.ShapeDtypeStruct((bucket,), jnp.int32)
    params = as_shape_dtype_structs(param_specs(cfg))
    compiled = decode_program(cfg).lower(
        *_on(one_chip, (params, pool, idx, idx))
    ).compile()
    assert 0 < _device_bytes(compiled) <= HBM_BYTES
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= 0.99 * _bytes(pool)
    assert mem.temp_size_in_bytes <= 0.25 * _bytes(pool)


def test_qwen3_insert_rows_donates_pool(one_chip):
    """The row insert after a prefill writes its rows into the donated
    pool: the program's pool output aliases its input."""
    from repro.models import init_cache
    from repro.runtime.engine import _INSERT_ROWS

    cfg, pool = _pool(16, 1024)
    cache = jax.eval_shape(lambda: init_cache(cfg, 2, 1024))
    slots = jax.ShapeDtypeStruct((2,), jnp.int32)
    compiled = _INSERT_ROWS.lower(
        *_on(one_chip, (pool, cache, slots))
    ).compile()
    assert compiled.memory_analysis().alias_size_in_bytes >= 0.99 * _bytes(pool)


def test_qwen3_train_step_fits_one_chip(one_chip):
    """The Trainer's step at batch 1 x 2048, as ``repro.launch.train``
    builds it, fits because it donates params and optimizer state (without
    donation the same step needs about 14.4 GB)."""
    from repro.launch import train

    trainer, ds = train.make_trainer(train.build_parser().parse_args([
        "--arch", "qwen3-0.6b", "--full", "--steps", "3", "--batch", "1",
        "--seq", "2048",
    ]))
    state = jax.eval_shape(trainer.init_state, jax.random.PRNGKey(0))
    batch = {k: jax.ShapeDtypeStruct(v.shape, v.dtype)
             for k, v in ds.batch(0).items()}
    step = trainer.region.candidate({"n_micro": 1})
    compiled = step.lower(*_on(one_chip, state), _on(one_chip, batch)).compile()
    assert 0 < _device_bytes(compiled) <= HBM_BYTES
    assert compiled.memory_analysis().alias_size_in_bytes >= 0.99 * _bytes(state)


def _device_bytes(compiled) -> int:
    mem = compiled.memory_analysis()
    return (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
