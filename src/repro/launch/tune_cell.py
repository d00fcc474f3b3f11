import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
os.environ["JAX_PLATFORMS"] = "cpu"  # placeholder devices only: never the chip

"""Before-execution AT of a full training/serving cell through the FIBER
tuner — the paper's §IV procedure ("user fixes BP; measure all candidates;
persist; select") executed at 256-chip scale with the hardware absent.

BP  = (arch, shape, mesh)
PP  = (sharding rule, remat policy, microbatch degree, attention blocks)
cost = CompiledRooflineCost: lower + compile each candidate, score with the
       trip-count-aware three-term roofline (max of C/M/X), with an HBM
       feasibility penalty.

    PYTHONPATH=src python -m repro.launch.tune_cell --arch qwen2.5-32b \
        --shape prefill_32k --db results/cell_tuning.json
"""
import argparse
import json
from typing import Any, Dict, Mapping

from repro.configs import SHAPES, ARCH_IDS, get_config
from repro.core import (
    ATRegion,
    BasicParams,
    ParamSpace,
    PerfParam,
    Tuner,
    TuningDB,
)
from repro.core.cost import TPU_V5E, roofline_from_compiled
from repro.launch.dryrun import lower_cell
from repro.launch.mesh import make_production_mesh, n_chips

HBM_BYTES = 16 * 2**30


def tune_cell(
    arch: str,
    shape: str,
    db_path: str,
    multi_pod: bool = False,
    hbm_penalty: float = 10.0,
) -> Dict[str, Any]:
    cell = SHAPES[shape]
    mesh = make_production_mesh(multi_pod=multi_pod)
    chips = n_chips(mesh)
    cfg = get_config(arch)

    params = [
        PerfParam("rule", ("tp",) + (("tp_ep",) if cfg.family == "moe" else ())
                  + (("tp_kvseq",) if cell.kind == "decode" else ())),
        PerfParam("attn_block_q", (512, 1024)),
        PerfParam("attn_block_kv", (1024, 4096)),
    ]
    if cell.kind == "train":
        params.append(PerfParam("remat", ("full", "dots")))
        params.append(PerfParam("n_micro", (1, 4)))
    space = ParamSpace(params)

    results: Dict[str, Any] = {}

    def cost(point: Mapping[str, Any]) -> float:
        overrides = {
            "attn_block_q": point["attn_block_q"],
            "attn_block_kv": point["attn_block_kv"],
        }
        if "remat" in point:
            overrides["remat"] = point["remat"]
        if cfg.family == "moe" and point["rule"] == "tp_ep":
            overrides["moe_groups"] = mesh.shape.get("data", 16)
        lowered, _ = lower_cell(
            arch, cell, mesh, point["rule"],
            cfg_overrides=overrides, n_micro=point.get("n_micro", 1),
        )
        compiled = lowered.compile()
        terms = roofline_from_compiled(lowered, compiled, chips, TPU_V5E)
        ma = compiled.memory_analysis()
        mem = (
            ma.argument_size_in_bytes + ma.output_size_in_bytes
            + ma.temp_size_in_bytes - ma.alias_size_in_bytes
        )
        c = terms.total_s * (hbm_penalty if mem > HBM_BYTES else 1.0)
        results[json.dumps(dict(point), sort_keys=True)] = {
            "terms": terms.asdict(), "mem_per_dev": int(mem), "cost": c,
        }
        print(
            f"[tune] {dict(point)} -> C={terms.compute_s:.2e} M={terms.memory_s:.2e} "
            f"X={terms.collective_s:.2e} mem={mem / 2**30:.1f}GiB cost={c:.2e}"
        )
        return c

    region = ATRegion(f"{arch}/{shape}", space, instantiate=lambda p: (lambda: p))
    bp = BasicParams.make(arch=arch, shape=shape, chips=chips)
    tuner = Tuner(TuningDB(db_path))
    res = tuner.tune(region, bp, cost)
    print(f"\n[tune] best PP for BP({arch}, {shape}, {chips} chips): "
          f"{res.best.point}  cost={res.best.cost:.3e}s "
          f"({res.evaluations} candidates compiled)")
    return {"best": res.best.point, "cost": res.best.cost, "all": results}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True, choices=list(ARCH_IDS))
    ap.add_argument("--shape", required=True, choices=list(SHAPES))
    ap.add_argument("--db", default="results/cell_tuning.json")
    ap.add_argument("--multi-pod", action="store_true")
    args = ap.parse_args()
    tune_cell(args.arch, args.shape, args.db, args.multi_pod)


if __name__ == "__main__":
    main()
