import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
os.environ["JAX_PLATFORMS"] = "cpu"  # placeholder devices only: never the chip

"""Multi-pod dry-run: lower + compile every (arch × shape × mesh) cell.

This is FIBER before-execution AT with the hardware absent: the candidate
(sharding rule, remat policy, microbatch degree, ...) is lowered with
``jax.jit(step, in_shardings=...).lower(**input_specs)``, compiled (no
allocation — all inputs are ShapeDtypeStructs), and scored by
``memory_analysis()`` + the trip-count-aware HLO cost walk.

The XLA_FLAGS line above MUST run before any other import (jax locks the
device count at first init), which is why it is the first statement of the
module.  Nothing else in the repo sets it — smoke tests and benches see the
host's real single device.

Usage:
    PYTHONPATH=src python -m repro.launch.dryrun --arch tinyllama-1.1b --shape train_4k
    PYTHONPATH=src python -m repro.launch.dryrun --all --out results/dryrun.json
    PYTHONPATH=src python -m repro.launch.dryrun --all --multi-pod-only
"""
import argparse
import json
import time
import traceback
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from repro.configs import ARCH_IDS, SHAPES, ShapeCell, all_cells, get_config, skipped_cells
from repro.core.cost import TPU_V5E, roofline_from_compiled
from repro.distributed.sharding import (
    RULES,
    activation_sharding,
    logical_to_spec,
    opt_state_sharding,
    param_sharding,
)
from repro.launch.mesh import make_production_mesh, n_chips
from repro.models import (
    analytic_param_count,
    analytic_step_flops,
    decode_fn,
    input_logical_axes,
    input_specs,
    param_specs,
    prefill_fn,
    train_loss,
)
from repro.models.spec import as_shape_dtype_structs
from repro.optim import AdamWConfig, adamw_init_specs, adamw_update
from jax.sharding import NamedSharding


def _shard_tree(tree_specs, axes_tree, rule, mesh):
    def one(spec, axes):
        return NamedSharding(mesh, logical_to_spec(rule, spec.shape, axes, mesh))

    return jax.tree.map(one, tree_specs, axes_tree, is_leaf=lambda x: hasattr(x, "shape") and not isinstance(x, dict))


def lower_cell(
    arch: str,
    cell: ShapeCell,
    mesh,
    rule_name: str = "tp",
    opt_cfg: Optional[AdamWConfig] = None,
    cfg_overrides: Optional[Dict[str, Any]] = None,
    n_micro: int = 1,
):
    """Build and lower the step function for one cell.  Returns Lowered."""
    cfg = get_config(arch)
    if cfg_overrides:
        cfg = cfg.with_(**cfg_overrides)
    rule = RULES[rule_name]
    specs = param_specs(cfg)
    p_shard = param_sharding(rule, specs, mesh)
    p_sds = as_shape_dtype_structs(specs)
    ins = input_specs(cfg, cell.kind, cell.global_batch, cell.seq_len)
    in_axes = input_logical_axes(cfg, cell.kind, ins)
    batch_shard = _shard_tree(ins["batch"], in_axes["batch"], rule, mesh)

    if cell.kind == "train":
        opt_cfg = opt_cfg or AdamWConfig()
        o_specs = adamw_init_specs(specs, opt_cfg)
        o_shard = opt_state_sharding(rule, o_specs, mesh)
        o_sds = as_shape_dtype_structs(o_specs)

        def train_step(params, opt_state, batch):
            if n_micro == 1:
                loss, grads = jax.value_and_grad(
                    lambda p: train_loss(p, batch, cfg)
                )(params)
            else:  # gradient-accumulation degree (the paper's thread-count PP)
                micro = jax.tree.map(
                    lambda x: x.reshape(
                        (x.shape[0], n_micro, x.shape[1] // n_micro) + x.shape[2:]
                    ).swapaxes(0, 1)
                    if x.ndim >= 2 and x.shape[0] == 3  # mrope positions
                    else x.reshape((n_micro, x.shape[0] // n_micro) + x.shape[1:]),
                    batch,
                )
                zeros = jax.tree.map(
                    lambda q: jnp.zeros(q.shape, jnp.float32), params
                )

                def body(carry, mb):
                    g_acc, l_acc = carry
                    l, g = jax.value_and_grad(
                        lambda p: train_loss(p, mb, cfg)
                    )(params)
                    g_acc = jax.tree.map(
                        lambda a, x: a + x.astype(jnp.float32), g_acc, g
                    )
                    return (g_acc, l_acc + l), None

                (gs, ls), _ = jax.lax.scan(body, (zeros, jnp.float32(0.0)), micro)
                grads = jax.tree.map(lambda g: g / n_micro, gs)
                loss = ls / n_micro
            params, opt_state, metrics = adamw_update(grads, opt_state, params, opt_cfg)
            return params, opt_state, loss

        jitted = jax.jit(
            train_step,
            in_shardings=(p_shard, o_shard, batch_shard),
            out_shardings=(p_shard, o_shard, NamedSharding(mesh, jax.sharding.PartitionSpec())),
        )
        with activation_sharding(mesh, rule):
            return jitted.lower(p_sds, o_sds, ins["batch"]), cfg

    if cell.kind == "prefill":
        def prefill_step(params, batch):
            return prefill_fn(params, batch, cfg)

        jitted = jax.jit(prefill_step, in_shardings=(p_shard, batch_shard))
        with activation_sharding(mesh, rule):
            return jitted.lower(p_sds, ins["batch"]), cfg

    if cell.kind == "decode":
        cache_shard = _shard_tree(ins["cache"], in_axes["cache"], rule, mesh)

        def serve_step(params, batch, cache):
            return decode_fn(params, batch, cache, cfg)

        jitted = jax.jit(
            serve_step, in_shardings=(p_shard, batch_shard, cache_shard)
        )
        with activation_sharding(mesh, rule):
            return jitted.lower(p_sds, ins["batch"], ins["cache"]), cfg

    raise ValueError(cell.kind)


def model_flops(cfg, cell: ShapeCell) -> float:
    """MODEL_FLOPS: 6·N·D / 2·N·D weight flops plus the attention/scan
    sequence terms (dominant at 32k+) — see models.analytic_step_flops."""
    return analytic_step_flops(cfg, cell.kind, cell.global_batch, cell.seq_len)


def run_cell(
    arch: str,
    cell: ShapeCell,
    multi_pod: bool,
    rule_name: str = "tp",
    verbose: bool = True,
    cfg_overrides: Optional[Dict[str, Any]] = None,
    opt_cfg: Optional[AdamWConfig] = None,
    n_micro: int = 1,
    label: str = "",
    mesh_shape=None,
) -> Dict[str, Any]:
    mesh = make_production_mesh(multi_pod=multi_pod, shape=mesh_shape)
    chips = n_chips(mesh)
    t0 = time.time()
    lowered, cfg = lower_cell(arch, cell, mesh, rule_name, cfg_overrides=cfg_overrides, opt_cfg=opt_cfg, n_micro=n_micro)
    t_lower = time.time() - t0
    t0 = time.time()
    compiled = lowered.compile()
    t_compile = time.time() - t0

    ma = compiled.memory_analysis()
    terms = roofline_from_compiled(lowered, compiled, chips, TPU_V5E)
    mf = model_flops(cfg, cell)
    rec: Dict[str, Any] = {
        "arch": arch,
        "shape": cell.name,
        "kind": cell.kind,
        "mesh": (
            "pod" + "x".join(map(str, mesh_shape))
            if mesh_shape
            else ("pod2x16x16" if multi_pod else "pod16x16")
        ),
        "chips": chips,
        "rule": rule_name,
        "n_micro": n_micro,
        "label": label,
        "overrides": cfg_overrides or {},
        "lower_s": round(t_lower, 2),
        "compile_s": round(t_compile, 2),
        "memory": {
            "argument_bytes": int(ma.argument_size_in_bytes),
            "output_bytes": int(ma.output_size_in_bytes),
            "temp_bytes": int(ma.temp_size_in_bytes),
            "alias_bytes": int(ma.alias_size_in_bytes),
            "per_device_total": int(
                ma.argument_size_in_bytes
                + ma.output_size_in_bytes
                + ma.temp_size_in_bytes
                - ma.alias_size_in_bytes
            ),
        },
        "roofline": terms.asdict(),
        "model_flops": mf,
        "useful_flops_ratio": mf / terms.hlo_flops if terms.hlo_flops else None,
        "status": "ok",
    }
    if verbose:
        hbm_gib = rec["memory"]["per_device_total"] / 2**30
        print(
            f"[dryrun] {arch:22s} {cell.name:12s} {rec['mesh']:11s} rule={rule_name:8s} "
            f"lower={t_lower:6.1f}s compile={t_compile:6.1f}s "
            f"mem/dev={hbm_gib:7.2f}GiB "
            f"roofline: C={terms.compute_s:.3e}s M={terms.memory_s:.3e}s "
            f"X={terms.collective_s:.3e}s -> {terms.bottleneck} "
            f"useful={rec['useful_flops_ratio'] and round(rec['useful_flops_ratio'],3)}"
        )
    return rec


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", choices=list(ARCH_IDS))
    ap.add_argument("--shape", choices=list(SHAPES))
    ap.add_argument("--rule", default="tp", choices=list(RULES))
    ap.add_argument("--all", action="store_true", help="run every assigned cell")
    ap.add_argument("--multi-pod", action="store_true", help="2x16x16 mesh")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--out", default=None, help="append JSONL results here")
    ap.add_argument("--skip-existing", action="store_true")
    args = ap.parse_args()

    done = set()
    if args.out and args.skip_existing and os.path.exists(args.out):
        with open(args.out) as f:
            for line in f:
                try:
                    r = json.loads(line)
                    done.add((r["arch"], r["shape"], r["mesh"], r.get("rule", "tp")))
                except Exception:
                    pass

    if args.all:
        cells = all_cells()
    else:
        if not args.arch or not args.shape:
            ap.error("--arch and --shape required unless --all")
        cells = [(args.arch, SHAPES[args.shape])]

    meshes = [args.multi_pod] if not args.both_meshes else [False, True]
    results = []
    for arch, cell in cells:
        for multi_pod in meshes:
            mesh_name = "pod2x16x16" if multi_pod else "pod16x16"
            if (arch, cell.name, mesh_name, args.rule) in done:
                print(f"[dryrun] skip existing {arch} {cell.name} {mesh_name}")
                continue
            try:
                rec = run_cell(arch, cell, multi_pod, args.rule)
            except Exception as e:
                rec = {
                    "arch": arch,
                    "shape": cell.name,
                    "mesh": mesh_name,
                    "rule": args.rule,
                    "status": "error",
                    "error": f"{type(e).__name__}: {e}",
                    "traceback": traceback.format_exc()[-2000:],
                }
                print(f"[dryrun] FAIL {arch} {cell.name} {mesh_name}: {e}")
            results.append(rec)
            if args.out:
                with open(args.out, "a") as f:
                    f.write(json.dumps(rec) + "\n")

    for arch, shape, reason in skipped_cells():
        print(f"[dryrun] skipped-by-rule {arch} {shape}: {reason}")

    n_ok = sum(1 for r in results if r.get("status") == "ok")
    print(f"[dryrun] {n_ok}/{len(results)} cells ok")
    if n_ok < len(results):
        raise SystemExit(1)


if __name__ == "__main__":
    main()
