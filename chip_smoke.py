"""Bring-up smoke run of the main path on one TPU chip.

    python chip_smoke.py [--seed N]

One process, no child that touches JAX, through the functions a user's
entry points call, at full published width with random weights from
``--seed`` (nothing is downloaded, and no file of an earlier run is read):

1. device  - JAX must report a TPU; on any other platform the run exits
   non-zero.  It never falls back to the CPU.
2. kernels - the five registered Pallas kernels through ``autotuned(name)``
   with a fresh TuningDB, at the widths of ``kernel_cases.py``.  Each
   compiles natively (``tpu_custom_call``), its finalists are measured on
   the chip, no candidate is quarantined or prescreened to ``inf``, and the
   tuned kernel matches its jnp oracle.
3. serve   - qwen3-0.6b on the StreamingEngine through
   ``repro.launch.serve`` (bursty trace, 8 requests, background tuner,
   8 KV blocks).  Every request retires ``ok``, the tuner drains without
   errors, and at least one request's tokens equal the model's own
   one-request greedy decode, every logit of which is finite.
4. train   - 3 qwen3-0.6b steps at batch 1 x 2048 through the Trainer of
   ``repro.launch.train``; every loss is finite.

Each phase prints its wall seconds, backend compile seconds, persistent
compile-cache hits and the device's peak memory so far: bring-up facts,
not benchmark metrics.  Any failure raises, so the exit code is non-zero;
the last line of a passing run is the JSON verdict.
"""
import argparse
import json
import math
import shutil
import sys
import threading
import time
from importlib import metadata
from pathlib import Path

ROOT = Path(__file__).resolve().parent
OUT = ROOT / "results" / "chip_smoke"  # gitignored; cleared at start
sys.path.insert(0, str(ROOT / "src"))

SERVE_ARGV = [
    "--arch", "qwen3-0.6b", "--full", "--stream", "--trace", "bursty",
    "--requests", "8", "--background-tune", "--blocks", "8",
]
TRAIN_ARGV = [
    "--arch", "qwen3-0.6b", "--full", "--steps", "3", "--batch", "1",
    "--seq", "2048",
]


class CompileMeter:
    """Backend compile seconds and persistent-cache hits, summed from JAX's
    monitoring events (the background tuner compiles on its own thread)."""

    def __init__(self) -> None:
        import jax.monitoring as monitoring

        self._lock = threading.Lock()
        self.compile_s = 0.0
        self.cache_hits = 0
        monitoring.register_event_duration_secs_listener(self._on_duration)
        monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event: str, secs: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            with self._lock:
                self.compile_s += secs

    def _on_event(self, event: str, **_) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            with self._lock:
                self.cache_hits += 1


def _version(dist: str) -> str:
    try:
        return metadata.version(dist)
    except metadata.PackageNotFoundError:
        return "not installed"


def check_device():
    import jax

    devices = jax.devices()
    device = devices[0]
    if device.platform != "tpu":
        sys.exit(f"chip_smoke: JAX reports {device.platform!r}, not a TPU; "
                 "this run never falls back to another platform")
    print(f"device: {device.device_kind} x{len(devices)}, jax {jax.__version__}, "
          f"jaxlib {_version('jaxlib')}, libtpu {_version('libtpu')}",
          flush=True)
    return device


def run_phase(name: str, fn, meter: CompileMeter, device):
    c0, h0, t0 = meter.compile_s, meter.cache_hits, time.perf_counter()
    fn()
    wall = time.perf_counter() - t0
    stats = device.memory_stats() or {}
    print(f"[{name}] wall_s={wall:.1f} compile_s={meter.compile_s - c0:.1f} "
          f"cache_hits={meter.cache_hits - h0} "
          f"peak_bytes_in_use={stats.get('peak_bytes_in_use')}", flush=True)


def _assert_close(out, ref, tol: float, label: str) -> float:
    """Leafwise allclose; returns the largest absolute error."""
    import jax
    import numpy as np

    outs, refs = jax.tree.leaves(out), jax.tree.leaves(ref)
    if len(outs) != len(refs):
        raise AssertionError(f"{label}: {len(outs)} outputs, oracle {len(refs)}")
    worst = 0.0
    for o, r in zip(outs, refs):
        o, r = np.asarray(o, np.float32), np.asarray(r, np.float32)
        np.testing.assert_allclose(o, r, rtol=tol, atol=tol, err_msg=label)
        worst = max(worst, float(np.max(np.abs(o - r))))
    return worst


def kernels_phase(cases, seed: int) -> None:
    """Tune, run and check each kernel case through the registry."""
    import jax

    from repro.core import TuningDB, autotuned
    from repro.core.arch import default_interpret

    if default_interpret():
        raise RuntimeError("Pallas kernels would run in interpret mode")
    db = TuningDB(str(OUT / "kernels.tuning.json"))
    key = jax.random.PRNGKey(seed)
    for i, case in enumerate(cases):
        args = case.make_args(jax.random.fold_in(key, i))
        op = autotuned(case.name, db=db)
        out = jax.block_until_ready(op(*args))
        (state,) = op.states().values()
        winner = dict(state.region.selected)
        done = [e for e in db.events(state.bp) if e["kind"] == "search_completed"]
        if not (state.tuned and done and state.cost_evaluations > 0):
            raise RuntimeError(f"{case.name}: no measured search completed")
        quarantined = db.quarantined(state.bp)
        if quarantined:
            raise RuntimeError(f"{case.name}: quarantined {sorted(quarantined)}")
        excluded = done[-1].get("prescreen_excluded", 0)
        if excluded:
            raise RuntimeError(f"{case.name}: {excluded} prescreen scores inf")
        hlo = jax.jit(state.region.candidate(winner)).lower(*args).compile()
        if "tpu_custom_call" not in hlo.as_text():
            raise RuntimeError(f"{case.name}: {winner} is not a Mosaic kernel")
        with jax.default_matmul_precision("highest"):
            ref = case.oracle(*args)
        err = _assert_close(out, ref, case.tol, case.name)
        print(f"kernel {case.name} [{case.source}]: "
              f"{state.region.space.size()} candidates, "
              f"{state.prescreen_evaluations} prescreened, "
              f"{state.cost_evaluations} measured, winner {winner} "
              f"({db.best_cost(state.bp):.3e} s), max |err| {err:.2e}",
              flush=True)


def make_greedy_reference(cfg, params, capacity: int):
    """The model's own one-request greedy decode, as a function of one
    request returning ``(tokens, every logit finite)``."""
    import jax
    import jax.numpy as jnp

    from repro.models import decode_fn, prefill_fn
    from repro.runtime.serve import build_batch_inputs

    prefill = jax.jit(lambda p, b: prefill_fn(p, b, cfg, capacity=capacity))
    decode = jax.jit(lambda p, b, c: decode_fn(p, b, c, cfg))

    def greedy(request):
        batch = build_batch_inputs(cfg, [request], len(request.prompt))
        logits, cache = prefill(params, batch)
        finite = [jnp.isfinite(logits).all()]
        tokens = [int(jnp.argmax(logits[0]))]
        while len(tokens) < request.max_new_tokens:
            step = {"tokens": jnp.asarray([[tokens[-1]]], jnp.int32)}
            logits, cache = decode(params, step, cache)
            finite.append(jnp.isfinite(logits).all())
            tokens.append(int(jnp.argmax(logits[0])))
        return tokens, bool(jnp.stack(finite).all())

    return greedy


def serve_phase(argv) -> None:
    from repro.launch import serve

    args = serve.parse_args(argv)
    cfg, params = serve.load_model(args)
    tuner = serve.make_background_tuner(args)
    engine, requests, faults = serve.run_stream(cfg, params, args, tuner)
    if faults:
        raise RuntimeError("serve: " + "; ".join(faults))
    statuses = sorted(r.status for r in engine.results.values())
    if statuses != ["ok"] * len(requests):
        raise RuntimeError(f"serve: retired {statuses}")
    greedy = make_greedy_reference(cfg, params, engine.max_len)
    matches = 0
    for r in requests:
        tokens, finite = greedy(r)
        if not finite:
            raise RuntimeError(f"serve: request {r.rid} has non-finite logits")
        matches += engine.results[r.rid].tokens == tokens
    print(f"serve: {len(requests)} requests ok, {matches} equal the "
          f"one-request greedy decode, max_len {engine.max_len}", flush=True)
    if not matches:
        raise RuntimeError("serve: no request equals the greedy decode")


def train_phase(argv) -> None:
    from repro.launch import train

    trainer, ds = train.make_trainer(train.build_parser().parse_args(argv))
    losses = trainer.run(ds)["loss"]
    print(f"train: losses {losses}", flush=True)
    if len(losses) != trainer.loop.total_steps or not all(
        math.isfinite(x) for x in losses
    ):
        raise RuntimeError(f"train: losses {losses}")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0,
                    help="PRNG seed of weights, inputs and data")
    args = ap.parse_args()
    device = check_device()

    import jax

    from kernel_cases import kernel_cases
    from repro.launch import use_compile_cache

    print(f"compile cache: {use_compile_cache()}", flush=True)
    shutil.rmtree(OUT, ignore_errors=True)
    OUT.mkdir(parents=True)
    meter = CompileMeter()
    seed = ["--seed", str(args.seed)]
    run_phase("kernels", lambda: kernels_phase(kernel_cases(), args.seed),
              meter, device)
    run_phase("serve", lambda: serve_phase(SERVE_ARGV + seed), meter, device)
    run_phase("train", lambda: train_phase(TRAIN_ARGV + seed), meter, device)
    print(json.dumps({"ok": True, "device": {
        "platform": device.platform, "kind": device.device_kind,
        "count": len(jax.devices()),
    }}), flush=True)


if __name__ == "__main__":
    main()
