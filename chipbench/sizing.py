"""Size the cells for a described TPU v5e, without the chip.

    JAX_PLATFORMS=cpu PYTHONPATH=src python chipbench/sizing.py serve|train

Compiles the programs a cell's window runs, for one chip of a described
``v5e:2x2`` topology, and prints ``memory_analysis()`` of each: the
arguments, outputs and temporaries the compiler plans for one program.
``serve``: the engine's decode step (every pow2 row bucket up to the pool)
and its largest prefill group, at each candidate KV pool size.  ``train``:
the train step at each candidate batch.  A program fits when its total
stays under the chip's 16 GB with room for what the process keeps beside
it (the weights, and for serving the pool itself).
"""
import os
import sys
from pathlib import Path

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("TPU_LOG_DIR", "disabled")
ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.experimental import topologies  # noqa: E402
from jax.sharding import SingleDeviceSharding  # noqa: E402

from chipbench import common  # noqa: E402

GB = 1e9


def _total(ma) -> float:
    return (ma.argument_size_in_bytes + ma.output_size_in_bytes
            + ma.temp_size_in_bytes - ma.alias_size_in_bytes) / GB


def _report(label: str, compiled) -> None:
    ma = compiled.memory_analysis()
    print(f"{label}: args {ma.argument_size_in_bytes / GB:.2f} GB, "
          f"out {ma.output_size_in_bytes / GB:.2f}, "
          f"temp {ma.temp_size_in_bytes / GB:.2f}, "
          f"alias {ma.alias_size_in_bytes / GB:.2f}, "
          f"total {_total(ma):.2f} GB", flush=True)


def _sds(tree, sharding):
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sharding), tree
    )


def serve(one_chip) -> None:
    from repro.models import init_cache, param_specs, prefill_fn
    from repro.models.spec import as_shape_dtype_structs
    from repro.runtime.engine import _make_decode_rows

    conf = common.load_config(ROOT, "qwen3-0.6b")
    cfg = common.program_config(conf)
    traffic = common.load_traffic(ROOT, "serve-decode")
    cap = traffic["max_len"]
    params = _sds(as_shape_dtype_structs(param_specs(cfg)), one_chip)
    row = jax.eval_shape(lambda: init_cache(cfg, 1, cap))
    plen = max(traffic["prompt_ladder"])
    for n_blocks in (16, 32):
        pool = _sds({k: jax.ShapeDtypeStruct((n_blocks,) + v.shape, v.dtype)
                     for k, v in row.items()}, one_chip)
        b = 1
        while b <= n_blocks:
            idx = jax.ShapeDtypeStruct((b,), jnp.int32, sharding=one_chip)
            compiled = jax.jit(_make_decode_rows(cfg)).lower(
                params, pool, idx, idx).compile()
            _report(f"decode n_blocks={n_blocks} rows={b}", compiled)
            b *= 2
    for group in (1, 2, 4):
        batch = {"tokens": jax.ShapeDtypeStruct((group, plen), jnp.int32,
                                                sharding=one_chip)}
        compiled = jax.jit(
            lambda p, bt: prefill_fn(p, bt, cfg, capacity=cap)
        ).lower(params, batch).compile()
        _report(f"prefill group={group} plen={plen}", compiled)


def train(one_chip) -> None:
    from repro.models import param_specs
    from repro.models.spec import as_shape_dtype_structs
    from repro.optim import adamw_init
    from repro.runtime.train import make_train_step

    conf = common.load_config(ROOT, "granite-moe-1b-a400m")
    cfg = common.program_config(conf)
    traffic = common.load_traffic(ROOT, "train-4k")
    opt_cfg = common.optimizer_config(traffic)
    seq = traffic["seq_len"]
    params = as_shape_dtype_structs(param_specs(cfg))
    opt = jax.eval_shape(lambda p: adamw_init(p, opt_cfg), params)
    params, opt = _sds(params, one_chip), _sds(opt, one_chip)
    step = jax.jit(make_train_step(cfg, opt_cfg, 1), donate_argnums=(0, 1))
    for batch in (1, 2, 3, 4):
        bt = {
            "tokens": jax.ShapeDtypeStruct((batch, seq), jnp.int32, sharding=one_chip),
            "targets": jax.ShapeDtypeStruct((batch, seq), jnp.int32, sharding=one_chip),
            "loss_mask": jax.ShapeDtypeStruct((batch, seq), jnp.float32,
                                              sharding=one_chip),
        }
        try:
            compiled = step.lower(params, opt, bt).compile()
        except Exception as e:  # the compiler refuses what does not fit
            print(f"train batch={batch}: refused: {str(e).splitlines()[0]}",
                  flush=True)
            continue
        _report(f"train batch={batch} seq={seq}", compiled)


def main() -> None:
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    one_chip = SingleDeviceSharding(topo.devices[0])
    {"serve": serve, "train": train}[sys.argv[1]](one_chip)


if __name__ == "__main__":
    main()
