"""The chip benchmark's one command.

    python chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Runs one cell of ``BENCHMARK.json`` on the chips of the machine it is
started on and prints, as the last line of standard output, one JSON object:
``correct``, ``attempted``, ``failed``, ``metrics``, ``device`` (and with
``--trace 1`` a ``breakdown``), then ``checks``: each number compared with
the plain reference, beside its limit.  ``--trace 0`` reports the cell's
end-to-end metrics, ``--trace 1`` its per-layer metrics and the profiler's
view of the device.

Where JAX finds no TPU, or fewer chips than the cell asks for, the run
exits non-zero and prints no result.  The persistent compilation cache is
``<checkout>/.jax_cache``, so only a cell's first run in a checkout
compiles.  ``--control 1`` also computes the control of the cell's
``correct`` (the reference in the precision below the configuration's),
and for training a planted fault, and holds each one's readings to the same
limits as the program's: the result's ``control`` key gives each one's
``correct``, which has to read false.  The benchmark's own runs never pass
it.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
from pathlib import Path
from typing import Any, Callable, Dict, List, Optional

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))
if str(ROOT / "src") not in sys.path:
    sys.path.insert(1, str(ROOT / "src"))

from chipbench import common  # noqa: E402
from chipbench.common import BenchError  # noqa: E402


def process_age_s() -> float:
    """Seconds since this process started (the kernel's own record)."""
    with open("/proc/self/stat") as f:
        start_ticks = int(f.read().rsplit(")", 1)[1].split()[19])
    with open("/proc/uptime") as f:
        uptime = float(f.read().split()[0])
    return uptime - start_ticks / os.sysconf("SC_CLK_TCK")


def parse_args(argv: Optional[List[str]] = None) -> argparse.Namespace:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


class Run:
    """Everything a driver needs for one run of one cell."""

    def __init__(self, root: Path, args: argparse.Namespace,
                 hooks: Optional[Dict[str, Callable]] = None) -> None:
        self.root = root
        self.args = args
        self.hooks = hooks or {}
        self.bench = common.load_benchmark(root)
        self.cell = common.find_workload(self.bench, args.workload)
        self.conf = common.load_config(root, self.cell["config"])
        self.traffic = common.load_traffic(root, self.cell["traffic"])
        self.limits = common.load_limits(root, self.cell["name"])
        self.trace_dir = root / "results" / "chipbench" / "trace" / self.cell["name"]

    def hook(self, name: str, value: Any) -> Any:
        """Tests plant faults through these; a benchmark run has none."""
        fn = self.hooks.get(name)
        return value if fn is None else fn(value)


def applies(metric: Dict[str, Any], cell: str) -> bool:
    return cell in metric.get("workloads", [cell])


def verdict(readings: Dict[str, float], limits: Dict[str, float]) -> bool:
    """Whether every reading is within its limit (no readings: not correct)."""
    return bool(readings) and all(v <= limits[k] for k, v in readings.items())


def check_devices(chips: int, require_tpu: bool) -> Dict[str, Any]:
    import jax

    devices = jax.devices()
    platform = devices[0].platform
    if require_tpu and platform != "tpu":
        raise BenchError(f"JAX finds {platform!r}, not a TPU: no result")
    if len(devices) < chips:
        raise BenchError(f"the cell asks for {chips} chips, JAX finds {len(devices)}")
    return {"platform": platform, "kind": devices[0].device_kind,
            "count": chips}


def use_compile_cache(root: Path) -> None:
    """JAX's persistent cache at a fixed directory of the checkout, with every
    program kept, so a cell's second run loads what its first compiled."""
    import jax

    path = str(root / ".jax_cache")
    os.environ["JAX_COMPILATION_CACHE_DIR"] = path
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def main(argv: Optional[List[str]] = None, root: Path = ROOT,
         require_tpu: bool = True, cache: bool = True,
         hooks: Optional[Dict[str, Callable]] = None) -> Dict[str, Any]:
    args = parse_args(argv)
    # libtpu writes its logs under /tmp unless told otherwise
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    run = Run(root, args, hooks)
    device = check_devices(run.cell["chips"], require_tpu)
    peaks = common.peak(root, device["kind"])
    if cache:
        use_compile_cache(root)
    driver = common.driver(root, run.traffic["kind"])
    out = driver.run(run, process_age_s)

    out["ctx"]["peak"] = peaks
    cell = run.cell["name"]
    metrics: Dict[str, Dict[str, Any]] = {}
    if args.trace:
        for m in run.bench["per_layer"]:
            if applies(m, cell):
                value = common.metric_reader(root, m["name"])(out["ctx"])
                if value is not None:
                    metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        for m in run.bench["end_to_end"]:
            if applies(m, cell):
                if m["name"] not in out["end_to_end"]:
                    raise BenchError(f"the driver did not measure {m['name']}")
                metrics[m["name"]] = {"value": out["end_to_end"][m["name"]],
                                      "unit": m["unit"]}
    device["memory_peak_bytes"] = out["memory_peak_bytes"]
    trace = out["ctx"].get("trace")
    if args.trace and trace is not None:
        device["busy_s"] = trace["busy_s"]
        device["window_s"] = trace["window_s"]
    checks = out["checks"]
    correct = out["failed"] == 0 and verdict(
        {c["name"]: c["value"] for c in checks}, run.limits)
    result: Dict[str, Any] = {
        "correct": correct,
        "attempted": out["attempted"],
        "failed": out["failed"],
        "metrics": metrics,
        "device": device,
    }
    if args.trace and trace is not None:
        result["breakdown"] = {"device_ops": trace["device_ops"],
                               "idle_gaps": trace["idle_gaps"]}
    for name, readings in out.get("control", {}).items():
        result.setdefault("control", {})[name] = {
            "correct": verdict(readings, run.limits), "readings": readings}
        for k, v in readings.items():
            print(f"control {name} {k}: {v!r} (limit {run.limits[k]!r})",
                  file=sys.stderr, flush=True)
        print(f"control {name}: correct {result['control'][name]['correct']}",
              file=sys.stderr, flush=True)
    result["checks"] = {c["name"]: {"value": c["value"], "limit": c["limit"]}
                        for c in checks}
    for c in checks:
        print(f"check {c['name']}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return result


if __name__ == "__main__":
    try:
        main()
    except BenchError as e:
        print(f"chipbench: {e}", file=sys.stderr)
        sys.exit(2)
