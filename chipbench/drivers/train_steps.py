"""Training driver: the program's autotuned train step, timed in a window.

Set-up builds one object, the Trainer's train region (``Trainer.region``,
the jitted step with donated state), with the weights made on the device
from the seed and AdamW's state.  It drives that object through the first
``checked_steps`` steps, on the window's own feed, and reads what the
comparison needs as it goes: each step's loss, the first gradient as the
optimizer got it (its first moment over ``1 - b1`` after one step), and the
change of the weights after the last checked step.  The window then goes on
stepping the same object until ``--seconds`` have passed, each step ending
in ``block_until_ready`` of its loss.  A traced run (``--trace 1``) traces
a window of ``trace_steps`` steps and reduces the trace once it has closed.

``correct``: once the window has closed, the plain float32 reference takes
the same weights (made again from the seed) through the same checked
steps, and three numbers are held to their limits: the widest gap between
the two losses of a step, and, by the worst leaf, the gap between the
norms of the first gradient and of the weights' change, each over the
larger of that leaf's reference norm and the median leaf's.
"""
from __future__ import annotations

import math
import time
from typing import Any, Callable, Dict, List

import numpy as np

from chipbench import common, trace_reduce, traffic as gen, weights


def _leaf_norms(tree) -> Dict[str, float]:
    import jax
    import jax.numpy as jnp

    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    norm = jax.jit(lambda x: jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32)))))
    return {"/".join(k.key for k in path): float(norm(x)) for path, x in flat}


def _change_norms(after, before) -> Dict[str, float]:
    import jax
    import jax.numpy as jnp

    diff = jax.jit(lambda a, b: jnp.sqrt(jnp.sum(jnp.square(
        a.astype(jnp.float32) - b.astype(jnp.float32)))))
    fa, _ = jax.tree_util.tree_flatten_with_path(after)
    fb = jax.tree.leaves(before)
    return {"/".join(k.key for k in path): float(diff(a, b))
            for (path, a), b in zip(fa, fb)}


def run(run, process_age_s: Callable[[], float]) -> Dict[str, Any]:
    import jax

    from repro.models import param_specs
    from repro.optim import adamw_init
    from repro.runtime import Trainer, TrainLoopConfig

    conf, t, seed = run.conf, run.traffic, run.args.seed
    cfg = common.program_config(conf)
    weights.check_layout(conf, param_specs(cfg))
    opt_cfg = common.optimizer_config(t)
    meter = common.CompileMeter()

    trainer = Trainer(cfg, opt_cfg, TrainLoopConfig(
        total_steps=opt_cfg.total_steps, seed=seed))
    step_fn = run.hook("train_step", trainer.region)
    make_batch = gen.train_batch_fn(t, conf["vocab_size"], seed)
    params = weights.make(conf, seed)
    opt_state = adamw_init(params, opt_cfg)

    def step(i, params, opt_state):
        with jax.profiler.TraceAnnotation("chipbench.batch"):
            batch = run.hook("batch", make_batch(i))
        with jax.profiler.TraceAnnotation("chipbench.train_step"):
            params, opt_state, metrics = step_fn(params, opt_state, batch)
            loss = float(metrics["loss"])
        return params, opt_state, loss

    losses: List[float] = []
    grads1 = change = None
    checked = int(t["checked_steps"])
    for i in range(checked):
        params, opt_state, loss = step(i, params, opt_state)
        losses.append(loss)
        if i == 0:
            first = jax.tree.map(lambda m: m / (1 - opt_cfg.b1), opt_state["m"])
            grads1 = _leaf_norms(first)
            del first
    start = weights.make(conf, seed)
    change = _change_norms(params, start)
    del start

    # -- the window ---------------------------------------------------------
    common.settle_host()
    compiles0 = meter.compiles
    tracing = bool(run.args.trace)
    setup_s = process_age_s()
    n, failed = 0, 0
    if tracing:
        trace_reduce.start(run.trace_dir)
    t0 = time.perf_counter()
    with jax.profiler.TraceAnnotation("chipbench.window"):
        while time.perf_counter() - t0 < run.args.seconds:
            params, opt_state, loss = step(checked + n, params, opt_state)
            failed += not math.isfinite(loss)
            n += 1
            if tracing and n == t["trace_steps"]:
                break
    t_end = time.perf_counter()
    trace = trace_reduce.stop(run.trace_dir) if tracing else None
    window_s = t_end - t0
    memory_peak = common.peak_bytes()
    tokens = n * t["batch"] * t["seq_len"]
    print(f"window: {n} steps of {t['batch']} x {t['seq_len']} in {window_s:.3f} s; "
          f"{meter.compiles - compiles0} compiles in the window; setup "
          f"{setup_s:.1f} s, {meter.compiles} compiles ({meter.compile_s:.1f} s), "
          f"{meter.cache_hits} cache hits; checked losses {losses}", flush=True)
    ctx = {"kind": "train", "conf": conf, "window_s": window_s, "steps": n,
           "batch": t["batch"], "seq_len": t["seq_len"],
           "window_compiles": meter.compiles - compiles0, "trace": trace}

    del params, opt_state, trainer, step_fn
    common.free_device()
    checks, control = _check(run, losses, grads1, change, make_batch)
    return {"end_to_end": {"train_tok_s": tokens / window_s, "setup_s": setup_s},
            **control,
            "ctx": ctx, "checks": checks, "attempted": n, "failed": failed,
            "memory_peak_bytes": memory_peak}


def worst_leaf(prog: Dict[str, float], ref: Dict[str, float],
               keep: List[str]) -> float:
    """Largest |program norm - reference norm| over the larger of the leaf's
    reference norm and the median leaf's."""
    median = float(np.median([ref[k] for k in keep]))
    return max(abs(prog[k] - ref[k]) / max(ref[k], median) for k in keep)


def compare(prog_losses, prog_grads, prog_change, ref_losses, ref_grads,
            ref_change) -> Dict[str, float]:
    """The three numbers ``correct`` holds, of a program against the
    reference.  Leaves whose reference gradient is under a thousandth of the
    median leaf's move by round-off alone and are left out."""
    median = float(np.median(list(ref_grads.values())))
    keep = [k for k, g in ref_grads.items() if g >= 1e-3 * median]
    return {
        "loss_gap": max(abs(a - b) for a, b in zip(prog_losses, ref_losses)),
        "first_grad_norm_gap": worst_leaf(prog_grads, ref_grads, keep),
        "change_norm_gap": worst_leaf(prog_change, ref_change, keep),
    }


def reference_readings(run, make_batch, precision: str, rows=None):
    """Losses, first-gradient norms and change norms of the reference;
    ``rows`` keeps only the first rows of each batch (a planted fault)."""
    import jax

    conf, t = run.conf, run.traffic
    ref = common.reference(run.root, conf["reference"])
    w0 = weights.make(conf, run.args.seed)
    batches = ({k: v[:rows] for k, v in make_batch(i).items()}
               for i in range(int(t["checked_steps"])))
    with jax.default_matmul_precision("highest"):
        losses, grads, w = ref.train_steps(conf, t["optimizer"], w0, batches,
                                           precision)
    change = _change_norms(w, w0)
    del w, w0
    common.free_device()
    return losses, grads, change


def _check(run, losses, grads1, change, make_batch):
    """The checks, and with ``--control 1`` the readings of the control
    (the reference in fp8) and of a planted fault (half of each batch)."""
    ref = reference_readings(run, make_batch, "f32")
    numbers = compare(losses, grads1, change, *ref)
    print(f"check: reference losses {ref[0]}", flush=True)
    checks = [{"name": k, "value": v, "limit": run.limits[k]}
              for k, v in numbers.items()]
    if not run.args.control:
        return checks, {}
    fp8 = compare(*reference_readings(run, make_batch, "fp8"), *ref)
    print(f"control fp8 {fp8!r}", flush=True)
    half = compare(*reference_readings(run, make_batch, "f32",
                                       rows=run.traffic["batch"] // 2), *ref)
    print(f"fault half_batch {half!r}", flush=True)
    return checks, {"control": {"fp8": fp8, "half_batch": half}}
