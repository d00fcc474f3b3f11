"""What every part of the chip benchmark shares: finding a cell's files by
name, building the program's configuration from a configuration file, the
real-clock tracer the engine reports into, the compile meter, and the
statistics the end-to-end metrics are made of.

The benchmark is driven by data.  ``BENCHMARK.json`` names each cell's
configuration and traffic; the harness finds them as files:

* ``chipbench/configs/<config>.json``  the model configuration as it is run
* ``chipbench/traffic/<traffic>.json`` a traffic mix or training job; its
  ``kind`` names the driver ``chipbench/drivers/<kind>.py``
* ``chipbench/metrics/<metric>.py``    one reader per per-layer metric
* ``chipbench/limits/<workload>.json`` the limits that decide ``correct``
* ``chipbench/reference/<family>.py``  the plain float32 reference

so a later change adds a cell, a mix or a metric by adding files.
"""
from __future__ import annotations

import importlib.util
import json
import math
import statistics
import threading
import time
from pathlib import Path
from typing import Any, Callable, Dict, List, Optional

BENCH_DIR = "chipbench"

#: published configuration key -> attribute of the program's ModelConfig
CONFIG_KEYS = {
    "hidden_size": "d_model",
    "intermediate_size": "d_ff",
    "num_hidden_layers": "n_layers",
    "num_attention_heads": "n_heads",
    "num_key_value_heads": "n_kv_heads",
    "head_dim": "head_dim_",
    "vocab_size": "vocab_size",
    "rope_theta": "rope_theta",
    "rms_norm_eps": "norm_eps",
    "tie_word_embeddings": "tie_embeddings",
    "num_local_experts": "n_experts",
    "num_experts_per_tok": "top_k",
    "qk_norm": "qk_norm",
}


class BenchError(RuntimeError):
    """A cell that cannot run as its files describe: no result is printed."""


def load_json(path: Path) -> Any:
    with open(path) as f:
        return json.load(f)


def bench_file(root: Path, *parts: str) -> Path:
    path = Path(root, BENCH_DIR, *parts)
    if not path.is_file():
        raise BenchError(f"no file {path.relative_to(root)}")
    return path


def load_benchmark(root: Path) -> Dict[str, Any]:
    path = Path(root, "BENCHMARK.json")
    if not path.is_file():
        raise BenchError("no BENCHMARK.json at the root of the checkout")
    return load_json(path)


def find_workload(bench: Dict[str, Any], name: str) -> Dict[str, Any]:
    for cell in bench["workloads"]:
        if cell["name"] == name:
            return cell
    raise BenchError(f"no workload {name!r} in BENCHMARK.json")


def load_config(root: Path, name: str) -> Dict[str, Any]:
    return load_json(bench_file(root, "configs", f"{name}.json"))


def load_traffic(root: Path, name: str) -> Dict[str, Any]:
    return load_json(bench_file(root, "traffic", f"{name}.json"))


def load_limits(root: Path, workload: str) -> Dict[str, float]:
    return load_json(bench_file(root, "limits", f"{workload}.json"))


def peak(root: Path, device_kind: str) -> Dict[str, Any]:
    """The published peaks of one chip; a kind missing from the table is an
    error, never a default."""
    table = load_json(bench_file(root, "peaks.json"))
    if device_kind not in table:
        raise BenchError(f"no peaks for device kind {device_kind!r} in peaks.json")
    return table[device_kind]


def load_module(path: Path, name: str):
    """Import one file of the benchmark by path (file names may hold dots)."""
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def metric_reader(root: Path, metric: str) -> Callable[[Any], Optional[float]]:
    path = bench_file(root, "metrics", f"{metric}.py")
    return load_module(path, f"chipbench_metric_{metric.replace('.', '_')}").read


def driver(root: Path, kind: str):
    return load_module(bench_file(root, "drivers", f"{kind}.py"),
                       f"chipbench_driver_{kind}")


def reference(root: Path, family: str):
    return load_module(bench_file(root, "reference", f"{family}.py"),
                       f"chipbench_reference_{family}")


def program_config(conf: Dict[str, Any]):
    """The program's ModelConfig for a configuration file, checked against
    every published size the file states: a program whose registry entry
    drifted from the file fails here, not silently in the numbers."""
    from repro.configs import get_config

    prog = conf["program"]
    cfg = get_config(prog["arch"]).with_(**prog.get("overrides", {}))
    for key, attr in CONFIG_KEYS.items():
        if key in conf and getattr(cfg, attr) != conf[key]:
            raise BenchError(
                f"{conf['name']}: {key} is {conf[key]} in the file, "
                f"{getattr(cfg, attr)} in the program ({attr})"
            )
    return cfg


def optimizer_config(traffic: Dict[str, Any]):
    from repro.optim import AdamWConfig

    return AdamWConfig(**traffic["optimizer"])


def peak_bytes() -> Optional[int]:
    """Peak bytes in use on the fullest local device, as the runtime counts."""
    import jax

    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in jax.local_devices()]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def settle_host() -> None:
    """End set-up as a long-running server would: collect set-up's garbage
    and freeze what survives (the compiler's and tracer's objects), so the
    window's garbage collections scan only what the window allocates."""
    import gc

    gc.collect()
    gc.freeze()


def free_device() -> None:
    """Drop every array and compiled program the run still holds."""
    import gc

    import jax

    gc.collect()
    for a in jax.live_arrays():
        a.delete()
    jax.clear_caches()


# ---------------------------------------------------------------------------
# Clocks
# ---------------------------------------------------------------------------


class RealClockTracer:
    """Takes the engine's ``engine.prefill`` / ``engine.decode`` events and
    stamps each with the host's real clock when it is emitted.  The engine emits a step's event right after that
    step's ``block_until_ready``, so the stamp is when its tokens exist.

    Duck-typed to the two methods of ``repro.obs.Tracer`` the engine calls
    (``complete`` and ``instant``); it keeps no event list, only what the
    metrics read.
    """

    def __init__(self) -> None:
        self.token_times: Dict[int, List[float]] = {}
        self.prefill_spans: List[Dict[str, Any]] = []
        self.decode_spans: List[Dict[str, Any]] = []

    def complete(self, name: str, t0: float, t1: float, **attrs: Any) -> None:
        now = time.perf_counter()
        if name == "engine.prefill":
            for rid in attrs["rids"]:
                self.token_times.setdefault(rid, []).append(now)
            self.prefill_spans.append({"batch": attrs["batch"], "plen": attrs["plen"]})
        elif name == "engine.decode":
            ctx = []
            for rid in attrs["rids"]:
                times = self.token_times.setdefault(rid, [])
                # the row attends over its prompt and every token but the
                # one it now feeds in, which it writes first
                ctx.append(len(times))
                times.append(now)
            self.decode_spans.append(
                {"batch": attrs["batch"], "rids": list(attrs["rids"]),
                 "generated": ctx}
            )

    def instant(self, name: str, t: Optional[float] = None, **attrs: Any) -> None:
        """Admissions and retirements carry nothing the metrics read."""


class CompileMeter:
    """Backend compile seconds and count, and persistent-cache hits, summed
    from JAX's monitoring events (a copy of chip_smoke.CompileMeter that
    also counts compiles, so a window can show it compiled nothing)."""

    def __init__(self) -> None:
        import jax.monitoring as monitoring

        self._lock = threading.Lock()
        self.compile_s = 0.0
        self.compiles = 0
        self.cache_hits = 0
        monitoring.register_event_duration_secs_listener(self._on_duration)
        monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event: str, secs: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            with self._lock:
                self.compile_s += secs
                self.compiles += 1

    def _on_event(self, event: str, **_) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            with self._lock:
                self.cache_hits += 1


# ---------------------------------------------------------------------------
# Statistics
# ---------------------------------------------------------------------------


def percentile(values: List[float], q: float) -> float:
    """Nearest-rank percentile: the smallest value with at least ``q``% of
    the values at or below it, so a tail is a value some request saw."""
    if not values:
        raise BenchError("percentile of no values")
    ordered = sorted(values)
    rank = max(1, math.ceil(q / 100.0 * len(ordered)))
    return ordered[rank - 1]


def spread(values: List[float]) -> float:
    """Distance between the first and third quartile over the median."""
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)
